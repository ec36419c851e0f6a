#!/usr/bin/env python3
"""``precision_probe.py`` for a configuration whose mesh is several
chips: on the chips, at the configuration's real widths and on its own
mesh, how far the program's forward pass lies from the family's plain
reference when it computes in bf16 (what the configuration states) and
when its matmuls are quantized to 8 bits (the program's own
``quant_autocast``): the control of the agreement check. The program is
given one copy of the sequence a device, so that its sharded batch axis
divides, and the first is compared; the parameters lie sharded as the
Trainer's do, and the reference reads them where they lie.

    python3 benchmark/tests/precision_probe_mesh.py <config> [seed ...]
"""

import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.dirname(BENCH), BENCH]


def readings(name, seeds=(0,), modes=(None, "int8")):
    """One ``reference.compare`` report a seed and compute mode: the
    configuration's own precision (None), then the lower ones."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import families
    import lookup
    import reference
    from dlrover_tpu.common.backend import require_backend
    from dlrover_tpu.ops.fp8 import quant_autocast
    from dlrover_tpu.parallel.accelerate import param_shardings_for
    from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh, set_mesh

    require_backend()
    sizes = lookup.data("configs", name)
    family = families.build(sizes)
    mesh = build_mesh(MeshConfig(**sizes["mesh"]))
    set_mesh(mesh)
    init = jax.jit(family.init, out_shardings=param_shardings_for(
        family.logical_axes, mesh))
    dtype = jnp.dtype(family.model_config.dtype)

    def system(mode):
        def forward(params, tokens):
            cast = jax.tree.map(lambda x: x.astype(dtype), params)
            rows = jnp.broadcast_to(
                tokens[:-1], (mesh.devices.size, tokens.shape[0] - 1))
            logits = family.apply(cast, rows)[0]
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
        for seed in seeds:
            params = init(jax.random.key(seed))
            tokens = jnp.asarray(np.random.RandomState(seed % 2 ** 32).randint(
                0, sizes["vocab_size"], (sizes["sequence"] + 1,)), jnp.int32)
            ref = jax.jit(plain)(params, tokens)
            for mode in modes:
                got = system(mode)(params, tokens)
                yield {
                    "config": name, "seed": seed,
                    "compute": mode or str(dtype),
                    "device": jax.devices()[0].device_kind,
                    "devices": int(mesh.devices.size),
                    **reference.compare(*got, *ref, family.tolerances),
                }
            del params, ref, got


if __name__ == "__main__":
    for reading in readings(
        sys.argv[1], [int(s) for s in sys.argv[2:]] or [0]
    ):
        print(json.dumps(reading), flush=True)
