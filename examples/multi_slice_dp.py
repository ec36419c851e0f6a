"""Multi-slice training: hybrid ICI x DCN mesh with cross-process DP.

The reference scales past one node with nested cross-node NCCL process
groups (atorch/atorch/distributed/distributed.py:321-427). TPU-native
equivalent: ONE hybrid mesh whose DCN-tolerant axes (here ``data``)
stride across slice boundaries while fsdp/tensor/seq stay inside each
slice's ICI domain — XLA routes each collective over the right fabric.

Run 2 simulated "slices" on one machine (each a jax.distributed process
with 4 virtual CPU devices):

    python examples/multi_slice_dp.py            # parent: spawns both
    # or by hand, one process per slice:
    python examples/multi_slice_dp.py --process-id 0 --port 12345 &
    python examples/multi_slice_dp.py --process-id 1 --port 12345

On real multi-slice TPU the same MeshConfig works unchanged: devices
carry ``slice_index`` and ``mesh_utils.create_hybrid_device_mesh`` lays
the slices out over DCN.
"""

from __future__ import annotations

import argparse
import os
import socket
import subprocess
import sys

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

N_PROCS = 2
DEVICES_PER_PROC = 4


def worker(process_id: int, port: int) -> None:
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", DEVICES_PER_PROC)
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(
        coordinator_address=f"127.0.0.1:{port}",
        num_processes=N_PROCS,
        process_id=process_id,
    )

    import jax.numpy as jnp
    import numpy as np
    import optax

    from dlrover_tpu.models import (
        PRESETS,
        llama_init,
        llama_logical_axes,
        llama_loss_fn,
    )
    from dlrover_tpu.parallel import MeshConfig, Strategy, auto_accelerate

    config = PRESETS["tiny"]
    # data axis spans the slices (dcn_data=2): the once-per-step
    # gradient allreduce is the only cross-slice traffic; fsdp's
    # per-step param all-gathers stay inside each slice
    strategy = Strategy(
        mesh=MeshConfig(
            data=N_PROCS, fsdp=DEVICES_PER_PROC, dcn_data=N_PROCS
        ),
        compute_dtype="bfloat16",
        remat="none",
        donate=False,
    )
    res = auto_accelerate(
        llama_loss_fn(config),
        lambda rng: llama_init(config, rng),
        optax.adamw(1e-3),
        llama_logical_axes(config),
        strategy=strategy,
    )
    state = res.state
    tokens = jnp.asarray(
        np.random.RandomState(0).randint(
            0, config.vocab_size, (N_PROCS * DEVICES_PER_PROC, 65)
        )
    )
    for step in range(3):
        state, metrics = res.train_step(
            state, {"tokens": tokens}, jax.random.key(step)
        )
        if process_id == 0:
            print(f"step {step}: loss {float(metrics['loss']):.4f}",
                  flush=True)
    print(f"slice {process_id}: done", flush=True)


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--process-id", type=int, default=None)
    p.add_argument("--port", type=int, default=0)
    args = p.parse_args()
    if args.process_id is not None:
        worker(args.process_id, args.port)
        return
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    env = dict(os.environ)
    procs = [
        subprocess.Popen(
            [sys.executable, os.path.abspath(__file__),
             "--process-id", str(i), "--port", str(port)],
            env=env,
        )
        for i in range(N_PROCS)
    ]
    try:
        rcs = [q.wait(timeout=600) for q in procs]
    finally:
        # a dead sibling leaves the survivor blocked in a collective:
        # never orphan it
        for q in procs:
            if q.poll() is None:
                q.kill()
    if any(rcs):
        raise SystemExit(f"worker exit codes {rcs}")
    print("multi-slice example ok")


if __name__ == "__main__":
    main()
