"""Node health-check payload: device matmul + collective probe.

Equivalent capability: reference dlrover/trainer/torch/node_check/
nvidia_gpu.py:26 (matmul rounds + 10x allgather of 2^24 floats, elapsed
time written to a per-rank file; MOCK_ERR_RANK fault injection
utils.py:50). TPU-native redesign: the probe runs a bf16 matmul loop on
every local TPU device (MXU exercise) and a psum over all local devices
via a jitted shard_map (ICI exercise); multi-host probes run the same
program under jax.distributed so the collectives cross hosts. The
payload initialises a JAX backend, so the agent — which must leave the
chip to its workers — runs it as a child process that exits before any
worker is spawned (:func:`run_node_check_child`) and reports (normal,
elapsed) to the master, whose pairing logic (master/rendezvous.py
NetworkCheckRendezvousManager) isolates the faulty node.
"""

from __future__ import annotations

import json
import os
import time

from dlrover_tpu.common.constants import NodeEnv
from dlrover_tpu.common.log import get_logger

logger = get_logger(__name__)

MATMUL_SIZE = 1024
MATMUL_ROUNDS = 10
COLLECTIVE_ELEMS = 1 << 22  # 4M floats ~= 16MB, all_gather x devices
COLLECTIVE_ROUNDS = 10


def _mock_error() -> bool:
    """Fault injection: MOCK_ERR_RANK=<node_rank> makes that node fail."""
    mock_rank = os.environ.get(NodeEnv.MOCK_ERR_RANK, "")
    node_rank = os.environ.get(NodeEnv.NODE_RANK, "0")
    return mock_rank != "" and mock_rank == node_rank


def matmul_probe() -> float:
    """Time a bf16 matmul loop on each local device (MXU health)."""
    import jax
    import jax.numpy as jnp

    start = time.time()
    for dev in jax.local_devices():
        x = jax.device_put(
            jnp.ones((MATMUL_SIZE, MATMUL_SIZE), dtype=jnp.bfloat16), dev
        )
        for _ in range(MATMUL_ROUNDS):
            x = jnp.matmul(x, x) / MATMUL_SIZE
        x.block_until_ready()
    return time.time() - start


def collective_probe() -> float:
    """Time psum rounds across local devices (ICI health); with a
    multi-process jax.distributed setup the same collectives span DCN."""
    from dlrover_tpu.agent.probe import local_psum

    x, probe = local_psum(COLLECTIVE_ELEMS)
    start = time.time()
    for _ in range(COLLECTIVE_ROUNDS):
        out = probe(x)
    out.block_until_ready()
    return time.time() - start


def run_node_check() -> tuple[bool, float]:
    """The payload (the agent runs it through
    :func:`run_node_check_child`). A process that finds no accelerator
    without ``JAX_PLATFORMS=cpu`` fails the check.

    Returns (normal, elapsed_seconds)."""
    start = time.time()
    normal = True
    try:
        if _mock_error():
            raise RuntimeError("mock node failure injected via MOCK_ERR_RANK")
        from dlrover_tpu.common.backend import require_backend

        require_backend()
        matmul_probe()
        collective_probe()
    except Exception as e:  # noqa: BLE001
        logger.error("node check failed: %s", e)
        normal = False
    return normal, time.time() - start


def run_node_check_child() -> tuple[bool, float]:
    """:func:`run_node_check` in a child process that has exited — and
    released the chip — before this returns. A child that dies without
    a verdict fails the check."""
    from dlrover_tpu.agent.probe import run_json_child

    start = time.time()
    rc, verdict, tail = run_json_child("dlrover_tpu.agent.node_check")
    if not isinstance(verdict, dict) or "normal" not in verdict:
        logger.error(
            "node check child exited %s without a verdict: %s", rc, tail
        )
        return False, time.time() - start
    return bool(verdict["normal"]), float(verdict["elapsed"])


def main():
    normal, elapsed = run_node_check()
    logger.info("node check: normal=%s elapsed=%.2fs", normal, elapsed)
    # the last stdout line is the verdict (run_json_child's contract)
    print(json.dumps({"normal": normal, "elapsed": elapsed}), flush=True)
    raise SystemExit(0 if normal else 1)


if __name__ == "__main__":
    main()
