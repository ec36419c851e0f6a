#!/usr/bin/env python3
"""On the chip, at a configuration's real widths: how far the program's
forward pass lies from ``reference.py`` when it computes in bf16 (what
the configurations state) and when its matmuls are quantized to 8 bits
(the program's own ``quant_autocast``). The tolerances in
``reference.py`` sit between the two; PERF.md records the reading.

    python3 benchmark/tests/precision_probe.py <config> [seed]
"""

import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.dirname(BENCH), BENCH]


def main(name, seed=0):
    import jax
    import jax.numpy as jnp
    import numpy as np

    import families
    import reference
    from dlrover_tpu.common.backend import require_backend
    from dlrover_tpu.ops.fp8 import quant_autocast
    from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh, set_mesh

    require_backend()
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        sizes = json.load(f)
    family = families.build(sizes)
    mesh = build_mesh(MeshConfig(**sizes["mesh"]))
    set_mesh(mesh)
    params = jax.jit(family.init)(jax.random.key(seed))
    tokens = jnp.asarray(np.random.RandomState(seed).randint(
        0, sizes["vocab_size"], (sizes["sequence"] + 1,)), jnp.int32)
    dtype = jnp.dtype(family.model_config.dtype)

    def system(mode):
        def forward(params, tokens):
            cast = jax.tree.map(lambda x: x.astype(dtype), params)
            logits = family.apply(cast, tokens[None, :-1])[0]
            return logits[-256:], reference.next_token_loss(logits, tokens)

        if mode is None:
            return jax.jit(forward)
        def quantized(params, tokens):
            with quant_autocast(mode):
                return forward(params, tokens)
        return jax.jit(quantized)

    def plain(params, tokens):
        logits = family.reference_logits(params, tokens[:-1])
        return logits[-256:], reference.next_token_loss(logits, tokens)

    with mesh:
        ref = jax.jit(plain)(params, tokens)
        for mode in (None, "int8", "fp8"):
            got = system(mode)(params, tokens)
            print(json.dumps({
                "config": name, "compute": mode or str(dtype),
                "device": jax.devices()[0].device_kind,
                **reference.compare(*got, *ref, family.tolerances),
            }), flush=True)


if __name__ == "__main__":
    main(sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2 else 0)
