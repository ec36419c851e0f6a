#!/usr/bin/env python3
"""On the chip, at a ``glm_lite`` configuration's real widths: what the
cell's agreement check says of the program in bf16 (what the
configuration states) and of its own int8 matmuls (the nearest
precision below it, the control), each against the family's plain
float32 reference that follows, at near ties, the choices of *that*
forward pass, at several near-tie margins; and the same distance of
the main head's logits and of the MTP depth's alone.
``family/glm_lite.py`` and PERF.md record the readings.

The first sequence of ``run.py``'s batch for each seed, on the
configuration's own weights (``weights_seed``), as the cell's check
has them.

    python3 benchmark/tests/glm_lite_precision_probe.py <config> [seed ...]
"""

import contextlib
import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.dirname(BENCH), BENCH]

MARGINS = (0.0, 0.002, 0.005, 0.01, 0.02)


def readings(name, seeds):
    import jax
    import jax.numpy as jnp

    import families
    import lookup
    import reference
    from dlrover_tpu.common.backend import require_backend
    from dlrover_tpu.models import glm_lite as model
    from dlrover_tpu.ops.fp8 import quant_autocast
    from run import seeded_tokens

    require_backend()
    sizes = lookup.data("configs", name)
    module = lookup.module("family", sizes["family"])
    family = families.build(sizes)
    config = family.model_config
    dtype = jnp.dtype(config.dtype)
    params = jax.jit(family.init)(jax.random.key(0))
    vocab = sizes["vocab_size"]

    def system(mode):
        def forward(params, tokens):
            cast = jax.tree.map(lambda x: x.astype(dtype), params)
            with quant_autocast(mode) if mode else contextlib.nullcontext():
                main, ahead, chosen = model.glm_lite_apply(
                    config, cast, tokens[None, :-1], choices=True)
            return module.side_by_side(main[0], ahead[0])[-256:], \
                chosen[:, 0]
        return jax.jit(forward)

    @jax.jit
    def plain(params, tokens, chosen, eps):
        main, ahead, followed = module.logits(sizes, params, tokens[:-1],
                                              chosen, eps)
        return module.side_by_side(main, ahead)[-256:], followed

    def rel(got, want):
        diff = got - want
        scale = jnp.sqrt(jnp.mean(want ** 2))
        return (float(jnp.sqrt(jnp.mean(diff ** 2)) / scale),
                float(jnp.max(jnp.abs(diff)) / scale))

    for seed in seeds:
        tokens = jnp.asarray(seeded_tokens(seed, sizes)[0])
        for mode in (None, "int8"):
            got, chosen = system(mode)(params, tokens)
            for eps in MARGINS:
                want, followed = plain(params, tokens, chosen,
                                       jnp.float32(eps))
                report = reference.compare(got, 0.0, want, 0.0,
                                           family.tolerances)
                yield {"seed": seed, "mode": mode or "bf16", "eps": eps,
                       "followed": int(followed),
                       "choices": module._routed_layers(sizes)
                       * sizes["sequence"],
                       "main_rel_rms_max": rel(got[:-1, :vocab],
                                               want[:-1, :vocab]),
                       "mtp_rel_rms_max": rel(got[:-1, vocab:],
                                              want[:-1, vocab:]),
                       **report}


def main():
    name, seeds = sys.argv[1], [int(s) for s in sys.argv[2:]] or [0]
    for report in readings(name, seeds):
        print(json.dumps(report), flush=True)


if __name__ == "__main__":
    main()
