"""Trainer-process APIs: distributed init, elastic data, flash checkpoint."""

from __future__ import annotations

import os

from dlrover_tpu.common.constants import NodeEnv


def init_distributed():
    """Initialise JAX multi-process training from the agent's env contract.

    The TPU analogue of torch's init_process_group bootstrap: the master's
    rendezvous designated a coordinator (rank-0 host); every worker calls
    jax.distributed.initialize against it. Single-process jobs skip
    that — but every worker checks here, at start-up, that it got the
    accelerator: without ``JAX_PLATFORMS=cpu`` a worker that finds none
    fails now, with one message, instead of training on JAX's silent
    CPU fallback.
    """
    from dlrover_tpu.common.backend import require_backend

    num = int(os.environ.get(NodeEnv.JAX_NUM_PROCESSES, "1"))
    if num <= 1:
        require_backend()
        return False
    import jax

    coordinator = os.environ[NodeEnv.JAX_COORDINATOR_ADDR]
    process_id = int(os.environ[NodeEnv.JAX_PROCESS_ID])
    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=num,
        process_id=process_id,
    )
    require_backend()
    return True


def global_rank() -> int:
    return int(os.environ.get(NodeEnv.RANK, "0"))


def world_size() -> int:
    return int(os.environ.get(NodeEnv.WORLD_SIZE, "1"))


def local_rank() -> int:
    return int(os.environ.get(NodeEnv.LOCAL_RANK, "0"))


def node_rank() -> int:
    return int(os.environ.get(NodeEnv.NODE_RANK, "0"))


def __getattr__(name):
    # lazy: Trainer pulls in jax/optax/parallel machinery; keep bare
    # `import dlrover_tpu.trainer` cheap for the agent process
    if name in ("Trainer", "TrainingArgs"):
        from dlrover_tpu.trainer import trainer as _t

        return getattr(_t, name)
    raise AttributeError(name)
