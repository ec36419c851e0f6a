"""Examples must keep running through the real tpu-run path (the
reference ships runnable examples; these smoke-run each on the CPU
mesh so they can't rot)."""

import os
import subprocess
import sys

import pytest

import jax

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_example(args, job, tmp_path, extra_env=None, timeout=240):
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        "PYTHONPATH": REPO,
        "DLROVER_TPU_SOCKET_DIR": str(tmp_path / "socks"),
        "ELASTIC_JOB_NAME": job,
        **(extra_env or {}),
    }
    env.pop("DLROVER_MASTER_ADDR", None)
    proc = subprocess.run(
        [
            sys.executable, "-m", "dlrover_tpu.trainer.run",
            "--nnodes", "1", "--nproc_per_node", "1",
        ] + args,
        env=env, cwd=REPO, capture_output=True, timeout=timeout,
    )
    assert proc.returncode == 0, (
        proc.stdout.decode()[-2000:] + "\n--- stderr ---\n"
        + proc.stderr.decode()[-2000:]
    )
    return proc


def _cleanup_job_shm(job):
    from dlrover_tpu.common.ipc import PersistentSharedMemory

    for name in (f"dlrtpu_ckpt_{job}_0", f"dlrtpu_timer_{job}"):
        try:
            seg = PersistentSharedMemory(name=name)
            seg.close()
            seg.unlink()
        except FileNotFoundError:
            pass


@pytest.mark.parametrize("args", [
    ["examples/llama_pretrain.py", "--preset", "tiny", "--steps", "10",
     "--seq-len", "64", "--batch-size", "4", "--save-steps", "5"],
    ["examples/kv_ctr_train.py", "--steps", "50"],
    ["examples/ppo_rlhf.py", "--iterations", "3"],
    ["examples/coworker_pipeline.py"],
    ["examples/long_context_ring.py", "--steps", "2"],
])
def test_example_runs(args, tmp_path):
    # per-test job name: the subprocesses' persistent checkpoint/timer
    # segments must not be shared across (or survive) tests
    job = f"ex{os.getpid()}_{os.path.basename(args[0]).split('.')[0]}"
    if "llama_pretrain" in args[0]:
        args = args + ["--output-dir", str(tmp_path / "out")]
    try:
        run_example(args, job, tmp_path)
    finally:
        _cleanup_job_shm(job)


@pytest.mark.skipif(
    not hasattr(jax.config, "jax_num_cpu_devices"),
    reason="this jax predates jax_num_cpu_devices: the multi-slice "
    "workers cannot shape their per-process CPU device count",
)
def test_multi_slice_example_runs(tmp_path):
    """multi_slice_dp spawns its own jax.distributed processes (one per
    simulated slice), so it runs directly rather than through tpu-run;
    the parent env must not force a device count onto the workers."""
    env = {**os.environ, "PYTHONPATH": REPO}
    for k in ("XLA_FLAGS", "JAX_PLATFORMS"):
        env.pop(k, None)
    proc = subprocess.run(
        [sys.executable, "examples/multi_slice_dp.py"],
        env=env, cwd=REPO, capture_output=True, timeout=600,
    )
    assert proc.returncode == 0, (
        proc.stdout.decode()[-2000:] + "\n--- stderr ---\n"
        + proc.stderr.decode()[-2000:]
    )
    assert b"multi-slice example ok" in proc.stdout
