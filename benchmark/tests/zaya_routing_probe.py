#!/usr/bin/env python3
"""On the chip, at a ``zaya`` configuration's real widths: what the
cell's agreement check says of a sound program (bf16, what the
configuration states), of the nearest precision below it (the
program's own int8 matmuls) and of three planted faults of the
router: the program's ``apply`` against the family's own
``reference_logits``, which follows at near ties the choices of that
same program, by ``reference.compare`` and the family's limits.
``precision_probe.py`` cannot make these readings for this family: it
traces the reference outside the lower precision or the fault, so the
reference would follow the sound program's choices and not the tested
one's.

The faults: the router's matmuls rounded to bf16, where the program
computes them in float32; the second-best expert for the few tokens
whose two best lie between 2 and 2.04 ``NEAR_TIE_EPS`` apart, so as
many choices moved as bf16 moves by itself, each just outside eps; and
the router's state of the layer below ignored. ``family/zaya.py`` and
PERF.md record the readings.

    python3 benchmark/tests/zaya_routing_probe.py <config> [seed ...]
"""

import contextlib
import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.dirname(BENCH), BENCH]


@contextlib.contextmanager
def patched(module, **attrs):
    old = {name: getattr(module, name) for name in attrs}
    for name, value in attrs.items():
        setattr(module, name, value)
    try:
        yield
    finally:
        for name, value in old.items():
            setattr(module, name, value)


def readings(name, seeds):
    import jax
    import jax.numpy as jnp
    import numpy as np

    import families
    import lookup
    import reference
    from dlrover_tpu.common.backend import require_backend
    from dlrover_tpu.models import zaya as model
    from dlrover_tpu.ops.fp8 import quant_autocast
    from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh, set_mesh

    require_backend()
    sizes = lookup.data("configs", name)
    module = lookup.module("family", sizes["family"])
    family = families.build(sizes)
    mesh = build_mesh(MeshConfig(**sizes["mesh"]))
    set_mesh(mesh)
    dtype = jnp.dtype(family.model_config.dtype)

    def rounded_router(x, w, b=None):
        """``_dense32`` with its result rounded to bf16."""
        out = jnp.matmul(
            x.astype(jnp.bfloat16), w.astype(jnp.bfloat16)
        ).astype(jnp.float32)
        return out if b is None else out + b.astype(jnp.float32)

    def second_best_outside_eps(probs, p):
        """``_choose`` taking the second-best expert where the two best
        lie between 2 and 2.04 eps apart."""
        eps = module.NEAR_TIE_EPS
        score = probs + p["balance_bias"].astype(jnp.float32)
        best, index = jax.lax.top_k(score, 2)
        margin = best[..., 0] - best[..., 1]
        choice = jnp.where((margin >= 2 * eps) & (margin < 2.04 * eps),
                           index[..., 1], index[..., 0]).astype(jnp.int32)
        return choice, jnp.take_along_axis(
            probs, choice[..., None], -1)[..., 0]

    router = model._router

    def stateless_router(config, y, r_prev, p):
        return router(config, y, jnp.zeros_like(r_prev), p)

    cases = [
        (str(dtype), contextlib.nullcontext),
        ("int8", lambda: quant_autocast("int8")),
        ("router_bf16", lambda: patched(model, _dense32=rounded_router)),
        ("second_best_just_outside_eps",
         lambda: patched(model, _choose=second_best_outside_eps)),
        ("router_state_ignored",
         lambda: patched(model, _router=stateless_router)),
    ]

    def system(params, tokens):
        cast = jax.tree.map(lambda x: x.astype(dtype), params)
        logits, choices = model.zaya_apply(
            family.model_config, cast, tokens[None, :-1], choices=True)
        return (logits[0, -256:],
                reference.next_token_loss(logits[0], tokens), choices)

    def plain(params, tokens):
        logits = family.reference_logits(params, tokens[:-1])
        return logits[-256:], reference.next_token_loss(logits, tokens)

    with mesh:
        for seed in seeds:
            params = jax.jit(family.init)(jax.random.key(seed))
            tokens = jnp.asarray(np.random.RandomState(seed).randint(
                0, sizes["vocab_size"], (sizes["sequence"] + 1,)), jnp.int32)
            own = None
            for case, context in cases:
                with context():
                    # new functions a case: jit keys its traces by them
                    *got, choices = jax.jit(
                        lambda p, t: system(p, t))(params, tokens)
                    want = jax.jit(lambda p, t: plain(p, t))(params, tokens)
                    jax.effects_barrier()
                choices = np.asarray(choices)
                own = choices if own is None else own
                yield {
                    "config": name, "seed": seed, "case": case,
                    "device": jax.devices()[0].device_kind,
                    "choices": int(choices.size),
                    "choices_other_than_the_sound_programs":
                        int(np.sum(choices != own)),
                    **reference.compare(*got, *want, family.tolerances),
                }
            del params


if __name__ == "__main__":
    for reading in readings(
        sys.argv[1], [int(s) for s in sys.argv[2:]] or [0]
    ):
        print(json.dumps(reading), flush=True)
