#!/usr/bin/env python3
"""On the chip, at a ``laguna`` configuration's real widths: what the
cell's agreement check says of the program in bf16 (what the
configuration states) and of its own int8 matmuls (the nearest
precision below it, the control), each against the family's plain
float32 reference that follows, at near ties, the choices of *that*
forward pass, at several near-tie margins. ``precision_probe.py``
cannot make these readings for this family: the family's
``reference_logits`` follows the bf16 program's choices, so an int8
forward would be compared with the reference routed as bf16 routes.
``family/laguna.py`` and PERF.md record the readings.

The first sequence of ``run.py``'s batch for each seed, on the
configuration's own weights (``weights_seed``), as the cell's check
has them.

    python3 benchmark/tests/laguna_precision_probe.py <config> [seed ...]
"""

import contextlib
import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.dirname(BENCH), BENCH]

MARGINS = (0.0, 0.02, 0.05, 0.1)


def readings(name, seeds):
    import jax
    import jax.numpy as jnp

    import families
    import lookup
    import reference
    from dlrover_tpu.common.backend import require_backend
    from dlrover_tpu.models import laguna as model
    from dlrover_tpu.ops.fp8 import quant_autocast
    from run import seeded_tokens

    require_backend()
    sizes = lookup.data("configs", name)
    module = lookup.module("family", sizes["family"])
    family = families.build(sizes)
    config = family.model_config
    dtype = jnp.dtype(config.dtype)
    params = jax.jit(family.init)(jax.random.key(0))
    routed = sum(kind == "sparse" for kind in sizes["mlp_layer_types"])

    def system(mode):
        def forward(params, tokens):
            cast = jax.tree.map(lambda x: x.astype(dtype), params)
            with quant_autocast(mode) if mode else contextlib.nullcontext():
                logits, chosen = model.laguna_apply(
                    config, cast, tokens[None, :-1], choices=True)
            logits = logits[0]
            return (logits[-256:], reference.next_token_loss(logits, tokens),
                    chosen[:, 0])
        return jax.jit(forward)

    @jax.jit
    def plain(params, tokens, chosen, eps):
        logits, followed = module.logits(sizes, params, tokens[:-1], chosen,
                                         eps)
        return (logits[-256:], reference.next_token_loss(logits, tokens),
                followed)

    for seed in seeds:
        tokens = jnp.asarray(seeded_tokens(seed, sizes)[0])
        for mode in (None, "int8"):
            logits, loss, chosen = system(mode)(params, tokens)
            for eps in MARGINS:
                ref_logits, ref_loss, followed = plain(
                    params, tokens, chosen, jnp.float32(eps))
                report = reference.compare(logits, loss, ref_logits, ref_loss,
                                           family.tolerances)
                yield {"seed": seed, "mode": mode or "bf16", "eps": eps,
                       "followed": int(followed),
                       "choices": routed * sizes["sequence"], **report}


def main():
    name, seeds = sys.argv[1], [int(s) for s in sys.argv[2:]] or [0]
    for report in readings(name, seeds):
        print(json.dumps(report), flush=True)


if __name__ == "__main__":
    main()
