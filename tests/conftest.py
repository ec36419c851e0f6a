"""Test bootstrap: force the JAX CPU backend with 8 virtual devices.

Must run before anything imports jax and initialises a backend. Mirrors
the reference test strategy (SURVEY.md section 4): multi-device behavior is
tested on a virtual host-platform mesh, no accelerators needed.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    flags = (flags + " --xla_force_host_platform_device_count=8").strip()
# Tier-1 is a functional gate, not a perf gate: XLA backend optimization
# buys nothing here but dominates the suite's wall clock on CPU (compile
# >> execute for every jitted step). -O0 keeps numerics deterministic
# per-compilation, so bit-exactness assertions between two functions
# compiled in the same process still hold.
if "xla_backend_optimization_level" not in flags:
    flags = (flags + " --xla_backend_optimization_level=0").strip()
os.environ["XLA_FLAGS"] = flags

import jax  # noqa: E402

try:
    jax.config.update("jax_platforms", "cpu")
except Exception:  # noqa: BLE001 - already initialised to cpu
    pass

import pytest  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from dlrover_tpu.common.rpc import find_free_port  # noqa: E402
from dlrover_tpu.master.master import LocalJobMaster  # noqa: E402
from dlrover_tpu.scheduler.job import new_job_args  # noqa: E402


def count_eqns(jaxpr, prim_names) -> int:
    """Equations of ``jaxpr`` (a ``Jaxpr``), sub-jaxprs included,
    whose primitive is one of ``prim_names``."""
    total = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in prim_names:
            total += 1
        for val in eqn.params.values():
            for sub in _subjaxprs(val):
                total += count_eqns(sub, prim_names)
    return total


def _subjaxprs(val):
    if hasattr(val, "jaxpr"):  # ClosedJaxpr
        yield val.jaxpr
    elif hasattr(val, "eqns"):  # Jaxpr
        yield val
    elif isinstance(val, (tuple, list)):
        for v in val:
            yield from _subjaxprs(v)


def start_local_master(node_num: int = 1):
    """In-process LocalJobMaster on a free port (the key fixture of the
    reference test suite, test_utils.start_local_master)."""
    job_args = new_job_args("local", "test-job", node_num=node_num)
    master = LocalJobMaster(0, job_args)
    master.prepare()
    return master


@pytest.fixture
def local_master():
    master = start_local_master()
    yield master
    master.stop()


@pytest.fixture
def local_master_2nodes():
    master = start_local_master(node_num=2)
    yield master
    master.stop()


@pytest.fixture
def free_port():
    return find_free_port()


@pytest.fixture(autouse=True)
def _reset_global_mesh():
    """Tests that set a process-global mesh must not leak it into later
    tests (e.g. a seq/pipe mesh changing model forward dispatch)."""
    yield
    from dlrover_tpu.parallel import mesh as mesh_mod

    mesh_mod._global_mesh = None


@pytest.fixture
def isolated_ckpt_env(tmp_path, monkeypatch):
    """Job-scoped socket dir + shm + saver-singleton isolation shared by
    the flash-checkpoint / trainer / chaos test files."""
    from dlrover_tpu.agent.ckpt_saver import AsyncCheckpointSaver

    monkeypatch.setenv("DLROVER_TPU_SOCKET_DIR", str(tmp_path / "socks"))
    job = f"iso{os.getpid()}"
    monkeypatch.setenv("ELASTIC_JOB_NAME", job)
    # clear any saver/factory a PREVIOUS test left behind (tests that
    # run agents without this fixture leave a factory thread bound to
    # their socket dir, which would make this test's saver a no-op)
    AsyncCheckpointSaver.reset()
    yield job
    from dlrover_tpu.common.ipc import PersistentSharedMemory

    AsyncCheckpointSaver.reset()
    names = [f"dlrtpu_ckpt_{job}_{rank}" for rank in range(4)]
    names.append(f"dlrtpu_timer_{job}")  # StepTimer ring (Trainer)
    for name in names:
        try:
            seg = PersistentSharedMemory(name=name)
            seg.close()
            seg.unlink()
        except FileNotFoundError:
            pass


@pytest.fixture(scope="session", autouse=True)
def _session_shm_sweep():
    """Agent subprocesses spawned by e2e tests create persistent timer
    rings (by design they survive process death); sweep them when the
    test session ends so repeated runs don't accumulate segments."""
    import glob

    before = set(glob.glob("/dev/shm/dlrtpu_timer_*"))
    yield
    from dlrover_tpu.common.ipc import PersistentSharedMemory

    for path in set(glob.glob("/dev/shm/dlrtpu_timer_*")) - before:
        name = os.path.basename(path)
        try:
            seg = PersistentSharedMemory(name=name)
            seg.close()
            seg.unlink()
        except FileNotFoundError:
            pass


@pytest.fixture
def profiled_spans(tmp_path):
    """``profiled_spans(body)`` runs ``body`` inside a CPU profiler
    session (host annotations only, no Python tracer) and returns the
    program's own spans from its ``.xplane.pb``
    (``tracing.spans_from_xplane``)."""
    def run(body):
        import jax

        from dlrover_tpu.common import trace_summary, tracing

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        trace_dir = str(tmp_path / "profiler_session")
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        try:
            body()
        finally:
            jax.profiler.stop_trace()
        (path,) = trace_summary.xplane_paths(trace_dir)
        return tracing.spans_from_xplane(path)

    return run


@pytest.fixture(scope="session", autouse=True)
def _test_runner_is_no_launch():
    """A process roots a ``launch`` trace at its own start and cuts it
    into ``start.*`` legs up to its first completed train step
    (``tracing.startup``). A test runner is no launch: the first
    Trainer of whichever test came first would close a root as old as
    the session. Tests of the legs arm their own
    (``tracing.reset_startup``)."""
    from dlrover_tpu.common import tracing

    tracing.reset_startup(None)
    yield
