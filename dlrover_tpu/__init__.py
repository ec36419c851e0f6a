"""dlrover_tpu: a TPU-native elastic distributed training framework.

A brand-new JAX/XLA implementation of the capabilities of DLRover
(reference: longer-is-better/dlrover): an elastic per-job master that
schedules/heals/scales TPU workers, a per-host elastic agent with
master-coordinated rendezvous and ICI/DCN mesh health checks, Flash
Checkpoint (async HBM->host-shared-memory checkpointing), elastic data
sharding with mid-epoch resume, and an ``auto_accelerate``-style strategy
layer that emits mesh/sharding plans (DP/FSDP/TP/SP/EP/PP).

Layering (mirrors SURVEY.md section 1):
  common/     L1 substrate: RPC protocol, shm IPC, node model, storage
  master/     L6 job master: node mgmt, rendezvous, data sharding, scaling
  scheduler/  L5 platform backends: local / k8s / ray
  agent/      L4 per-host elastic agent: master client, run loop, ckpt saver
  trainer/    L3 in-process APIs: tpu-run CLI, flash ckpt engines, elastic data
  accel/      L2 acceleration: strategy search -> mesh + shardings
  parallel/   mesh axes, TP/SP/PP/EP building blocks (shard_map/pjit)
  models/     flagship model zoo (llama, gpt2, mnist toy)
  ops/        Pallas TPU kernels + optimizers (flash attn, fused CE, AGD/WSAM)
"""

import time as _time

# the package's first import line, on the wall clock: where a process's
# ``start.exec`` leg ends and ``start.imports`` begins (common/tracing)
IMPORT_T = _time.time()

__version__ = "0.1.0"
