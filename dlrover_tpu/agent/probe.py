"""Join-time hardware health probe: per-leg timings shipped with the join.

Equivalent capability: the reference admits a node only after a
``NetworkCheckElasticAgent`` runs a matmul + repeated-allgather payload
and kills hosts that fail it (node_check/nvidia_gpu.py); our
node_check.py reproduces the pass/fail half for dedicated probe rounds.
This module is the *graded* half: three timed legs run BEFORE
``rdzv.join``, with per-leg milliseconds shipped in
``JoinRendezvousRequest.probe_report`` so the master's health gate
(master/health.py) can judge the host against the fleet median AND its
own persisted fingerprint — pass / quarantine / refuse instead of the
binary normal flag.

Where the legs run (one process per chip): the device legs initialise a
JAX backend, and a process that has done so owns the chip. The agent
must hand that chip to its workers, so it never runs a leg itself: the
join-time probe is :func:`run_probe_child` — ``python -m
dlrover_tpu.agent.probe`` as a short-lived child that exits before any
worker is spawned — and the in-band re-probe runs inside the worker
that holds the chip, at a step boundary (``Trainer._maybe_reprobe``).

Legs (TPU; host stand-ins, taken only under ``JAX_PLATFORMS=cpu``, in
parentheses):

- ``hbm``        — HBM-bandwidth microbench: on-device array copy
                   rounds (host memcpy over a scaled buffer).
- ``matmul``     — an MXU matmul round per local device (numpy matmul
                   — a jitted jax matmul on CPU would time XLA
                   compilation, not the hardware).
- ``collective`` — N ICI psum rounds over the local mesh via a
                   jitted shard_map (loopback-socket round trips: the
                   only stand-in that still exercises a real network
                   stack).

Every leg opens its timed window with ``chaos_point("probe.degrade",
leg=..., rank=...)`` — the ``degrade`` action (common/chaos.py) injects
a seeded, scaled sleep *inside* the measurement, so a chaos rule with a
MOCK_ERR-style rank anchor makes exactly that host's legs look slow and
the master's 2x-median rule (the straggler blamer's constant) does the
rest. ``MOCK_ERR_RANK`` itself is honored too: the anchored host's
probe reports an error and the gate refuses it, mirroring node_check.
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import time

from dlrover_tpu.common import backend as backend_mod
from dlrover_tpu.common import tracing
from dlrover_tpu.common.chaos import chaos_point
from dlrover_tpu.common.constants import NodeEnv
from dlrover_tpu.common.log import get_logger

logger = get_logger(__name__)

PROBE_LEGS = ("hbm", "matmul", "collective")

# leg sizing (env-overridable: chaos arms shrink them, soak arms grow
# them). Defaults keep the whole CPU smoke-arm probe well under the 5 s
# join-overhead budget the bad-host schedule asserts.
HBM_BYTES = int(os.environ.get("DLROVER_PROBE_HBM_BYTES", str(1 << 24)))
HBM_ROUNDS = int(os.environ.get("DLROVER_PROBE_HBM_ROUNDS", "4"))
MATMUL_SIZE = int(os.environ.get("DLROVER_PROBE_MATMUL_SIZE", "256"))
MATMUL_ROUNDS = int(os.environ.get("DLROVER_PROBE_MATMUL_ROUNDS", "4"))
COLLECTIVE_BYTES = int(
    os.environ.get("DLROVER_PROBE_COLLECTIVE_BYTES", str(1 << 20))
)
COLLECTIVE_ROUNDS = int(
    os.environ.get("DLROVER_PROBE_COLLECTIVE_ROUNDS", "8")
)

# re-probe cadence: a quarantined host re-probes on the master's
# backoff schedule; an ADMITTED host re-probes in band at this floor
# cadence (stretched by the cost governor below, never tightened)
REPROBE_INTERVAL_S = float(
    os.environ.get("DLROVER_PROBE_REPROBE_INTERVAL", "600")
)
# the in-band re-probe's steady-state overhead budget, as a percent of
# the interval it rides — same contract (and default) as the device-
# time sampler's window governor (common/profiling.py)
REPROBE_OVERHEAD_PCT = float(
    os.environ.get("DLROVER_PROBE_OVERHEAD_PCT", "2.0")
)


def _node_rank() -> int:
    try:
        return int(os.environ.get(NodeEnv.NODE_RANK, "0"))
    except ValueError:
        return 0


def _mock_error() -> bool:
    """MOCK_ERR_RANK=<node_rank> makes that node's probe error out —
    the same injection contract node_check honors."""
    mock_rank = os.environ.get(NodeEnv.MOCK_ERR_RANK, "")
    return mock_rank != "" and mock_rank == os.environ.get(
        NodeEnv.NODE_RANK, "0"
    )


def _device_backend() -> str:
    """Accelerator backend name, or '' for the host stand-in legs.

    The stand-ins are for a CPU the caller pinned (tests, CPU
    harnesses) and never touch JAX. Anywhere else the legs must reach
    the accelerator: a backend that fails to initialise, or JAX's
    silent CPU fallback, raises — the report then carries the error and
    the gate refuses the host instead of grading host memcpy timings as
    the health of a TPU."""
    if backend_mod.cpu_pinned():
        return ""
    return backend_mod.require_backend()


# ---------------------------------------------------------------- legs


def hbm_probe(rank: int, device: bool) -> float:
    """HBM-bandwidth leg: on-device copy rounds (host memcpy on the
    smoke arm). Returns elapsed milliseconds.

    The warmup pass runs OUTSIDE the timed window: allocation and
    page-fault noise on a first touch is 2x-scale — big enough to trip
    the gate's 2x-median rule on a perfectly healthy host."""
    if device:
        import jax
        import jax.numpy as jnp

        x = jax.device_put(
            jnp.ones((HBM_BYTES // 4,), dtype=jnp.float32)
        )
        (x + 0.0).block_until_ready()  # warmup
        t0 = time.perf_counter()
        chaos_point("probe.degrade", leg="hbm", rank=rank)
        for _ in range(HBM_ROUNDS):
            x = x + 0.0
        x.block_until_ready()
    else:
        src = bytearray(HBM_BYTES)
        dst = bytearray(HBM_BYTES)  # preallocated: copies, no allocs
        dst[:] = src  # warmup (faults both buffers in)
        t0 = time.perf_counter()
        chaos_point("probe.degrade", leg="hbm", rank=rank)
        for _ in range(HBM_ROUNDS):
            dst[:] = src
    return (time.perf_counter() - t0) * 1000.0


def matmul_probe(rank: int, device: bool) -> float:
    """MXU leg: a matmul round per local device (numpy on the smoke
    arm — a jitted CPU matmul would time XLA compilation instead).
    Returns elapsed milliseconds. Warmup outside the window (lazy BLAS
    init / XLA compile must not read as slow hardware)."""
    if device:
        import jax
        import jax.numpy as jnp

        xs = [
            jax.device_put(
                jnp.ones(
                    (MATMUL_SIZE, MATMUL_SIZE), dtype=jnp.bfloat16
                ),
                dev,
            )
            for dev in jax.local_devices()
        ]
        # EVERY device: each one loads its own copy of the program, and
        # warming only the first left that inside the window (930 ms on
        # four real chips against 3 ms on one)
        for x in xs:
            (jnp.matmul(x, x) / MATMUL_SIZE).block_until_ready()
        t0 = time.perf_counter()
        chaos_point("probe.degrade", leg="matmul", rank=rank)
        for x in xs:
            for _ in range(MATMUL_ROUNDS):
                x = jnp.matmul(x, x) / MATMUL_SIZE
            x.block_until_ready()
    else:
        import numpy as np

        x = np.ones((MATMUL_SIZE, MATMUL_SIZE), dtype=np.float32)
        (x @ x) / MATMUL_SIZE  # warmup
        t0 = time.perf_counter()
        chaos_point("probe.degrade", leg="matmul", rank=rank)
        for _ in range(MATMUL_ROUNDS):
            x = (x @ x) / MATMUL_SIZE
    return (time.perf_counter() - t0) * 1000.0


def collective_probe(rank: int, device: bool) -> float:
    """ICI leg: psum rounds over the local mesh (loopback-socket round
    trips on the smoke arm — the one stand-in that still pushes bytes
    through a real network stack). Returns elapsed milliseconds.
    Setup and a warmup round run outside the timed window
    (compilation / socket handshake are not the hardware under test)."""
    if device:
        x, probe = local_psum(COLLECTIVE_BYTES // 4)
        probe(x).block_until_ready()  # warmup (compile)
        t0 = time.perf_counter()
        chaos_point("probe.degrade", leg="collective", rank=rank)
        out = x
        for _ in range(COLLECTIVE_ROUNDS):
            out = probe(x)
        out.block_until_ready()
        return (time.perf_counter() - t0) * 1000.0
    server, sender, conn = _loopback_pair()
    try:
        _loopback_rounds(sender, conn, 1)  # warmup
        t0 = time.perf_counter()
        chaos_point("probe.degrade", leg="collective", rank=rank)
        _loopback_rounds(sender, conn, COLLECTIVE_ROUNDS)
        return (time.perf_counter() - t0) * 1000.0
    finally:
        sender.close()
        conn.close()
        server.close()


def local_psum(elems: int):
    """``(x, fn)``: a float32 array of about ``elems`` elements, one
    row per local device, and the jitted all-reduce over those devices
    (shared with node_check's ICI leg)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devices = jax.local_devices()
    n = len(devices)
    mesh = Mesh(np.array(devices), ("d",))
    x = jax.device_put(
        jnp.ones((n, max(elems // n, 1)), jnp.float32),
        NamedSharding(mesh, P("d")),
    )
    fn = jax.jit(jax.shard_map(
        lambda v: jax.lax.psum(v, "d"),
        mesh=mesh, in_specs=P("d"), out_specs=P("d"),
    ))
    return x, fn


def _loopback_pair():
    """A connected 127.0.0.1 socket pair (server, sender, receiver)."""
    server = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    try:
        server.bind(("127.0.0.1", 0))
        server.listen(1)
        sender = socket.create_connection(
            server.getsockname(), timeout=10
        )
        conn, _ = server.accept()
    except Exception:
        server.close()
        raise
    return server, sender, conn


def _loopback_rounds(sender, conn, rounds: int):
    """Push COLLECTIVE_BYTES through the pair per round — send and
    drain on the same thread in chunks small enough to never deadlock
    against the kernel buffers."""
    chunk = b"\x00" * 65536
    for _ in range(rounds):
        remaining = COLLECTIVE_BYTES
        while remaining > 0:
            part = chunk[: min(len(chunk), remaining)]
            sender.sendall(part)
            got = 0
            while got < len(part):
                got += len(conn.recv(len(part) - got))
            remaining -= len(part)


# --------------------------------------------------------------- probe


def run_probe(node_rank: int | None = None) -> dict:
    """Run all three legs; returns the join-payload report::

        {"legs": {"hbm": ms, "matmul": ms, "collective": ms},
         "elapsed_s": s, "host": rank, "backend": "tpu"|"host",
         "error": "", "t": wall}

    A leg failure (or MOCK_ERR_RANK) lands in ``error`` — the master's
    gate refuses hosts whose probe errored, exactly like node_check's
    binary fail. Never raises."""
    rank = _node_rank() if node_rank is None else int(node_rank)
    t0 = time.perf_counter()
    backend = ""
    legs: dict[str, float] = {}
    error = ""
    try:
        if _mock_error():
            raise RuntimeError(
                "mock probe failure injected via MOCK_ERR_RANK"
            )
        backend = _device_backend()
        device = bool(backend)
        legs["hbm"] = round(hbm_probe(rank, device), 3)
        legs["matmul"] = round(matmul_probe(rank, device), 3)
        legs["collective"] = round(collective_probe(rank, device), 3)
    except Exception as e:  # noqa: BLE001 - a probe failure is a
        # verdict (refuse), not an agent crash
        logger.error("hardware probe failed: %s", e)
        error = str(e)
    elapsed = time.perf_counter() - t0
    report = {
        "legs": legs,
        "elapsed_s": round(elapsed, 4),
        "host": rank,
        "backend": backend or ("unknown" if error else "host"),
        "error": error,
        "t": time.time(),
    }
    logger.info(
        "hardware probe: %s (%.0f ms total)%s",
        {k: f"{v:.1f}ms" for k, v in legs.items()},
        elapsed * 1000,
        f" ERROR={error}" if error else "",
    )
    return report


# a join-time child pays process start, backend initialisation and
# three small compiles before its first timed window
CHILD_TIMEOUT_S = 300.0


def run_json_child(
    module: str, args: tuple = (), timeout: float = CHILD_TIMEOUT_S
):
    """Run ``python -m <module>`` to its end and return ``(rc, payload,
    tail)``: the JSON object on its last stdout line (None when there
    is none) and the end of its stderr. The agent's seam to payloads
    that initialise a JAX backend: by the time this returns the child
    has exited and released whatever chip it took."""
    import dlrover_tpu

    env = dict(os.environ)
    pkg_root = os.path.dirname(
        os.path.dirname(os.path.abspath(dlrover_tpu.__file__))
    )
    env["PYTHONPATH"] = os.pathsep.join(
        [pkg_root]
        + [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p]
    )
    # the child's start (interpreter, imports, backend) shows under
    # the span that waits for it (``rdzv.probe``)
    tracing.export_ambient(env)
    # spawn seam (dlint DL003): agent.spawn covers workers; this is the
    # payload-child counterpart
    chaos_point("probe.spawn", module=module)
    try:
        proc = subprocess.run(  # noqa: S603
            [sys.executable, "-m", module, *args],
            env=env, capture_output=True, text=True, timeout=timeout,
        )
    except subprocess.TimeoutExpired as e:
        tail = (e.stderr or b"")[-2000:]
        if isinstance(tail, bytes):
            tail = tail.decode(errors="replace")
        return 124, None, f"timed out after {timeout:.0f}s: {tail}"
    try:
        payload = json.loads(proc.stdout.strip().splitlines()[-1])
    except (IndexError, ValueError):
        payload = None
    return proc.returncode, payload, proc.stderr[-2000:]


def run_probe_child(node_rank: int | None = None) -> dict:
    """The join-time probe as the agent runs it: :func:`run_probe` in a
    child process that has exited before this returns, so the chip it
    measured is free for the workers. A child that dies without a
    report becomes an error report — the gate refuses the host, exactly
    like a leg failure. Never raises."""
    rank = _node_rank() if node_rank is None else int(node_rank)
    t0 = time.perf_counter()
    rc, report, tail = run_json_child(
        "dlrover_tpu.agent.probe", ("--node-rank", str(rank))
    )
    if not isinstance(report, dict) or "legs" not in report:
        logger.error(
            "hardware probe child exited %s without a report: %s", rc, tail
        )
        report = {
            "legs": {},
            "host": rank,
            "backend": "unknown",
            "error": f"probe child exited {rc}: {tail[-300:]}",
            "t": time.time(),
        }
    # the join pays the child's whole life, not just its legs
    report["elapsed_s"] = round(time.perf_counter() - t0, 4)
    logger.info(
        "hardware probe (child): %s backend=%s (%.0f ms total)%s",
        {k: f"{v:.1f}ms" for k, v in report["legs"].items()},
        report["backend"], report["elapsed_s"] * 1000,
        f" ERROR={report['error']}" if report["error"] else "",
    )
    return report


class ProbeScheduler:
    """Cadence governor for the in-band re-probe, mirroring the
    device-time sampler's window governor: ``interval`` is the FLOOR,
    and each probe's measured cost stretches the next gap until the
    steady-state overhead stays under ``overhead_pct`` of the wait — an
    always-on health signal that self-limits instead of taxing the
    monitor loop. The join-time report seeds the cache so a fresh join
    never immediately re-pays the probe."""

    def __init__(
        self,
        interval_s: float | None = None,
        overhead_pct: float | None = None,
    ):
        self.interval = float(
            REPROBE_INTERVAL_S if interval_s is None else interval_s
        )
        frac = (
            REPROBE_OVERHEAD_PCT if overhead_pct is None else overhead_pct
        )
        self._overhead_frac = max(float(frac), 0.0) / 100.0
        self._next_t = 0.0
        self.last_report: dict | None = None
        self.last_gap = self.interval

    def seed(self, report: dict, now: float | None = None):
        """Adopt a join-time report as the freshest sample."""
        now = time.time() if now is None else now
        self.last_report = report
        self._arm(float(report.get("elapsed_s", 0.0)), now)

    def _arm(self, cost_s: float, now: float):
        gap = self.interval
        if self._overhead_frac > 0 and cost_s > 0:
            gap = max(gap, cost_s / self._overhead_frac)
        self.last_gap = gap
        self._next_t = now + gap

    def due(self, now: float | None = None) -> bool:
        now = time.time() if now is None else now
        return now >= self._next_t

    def run(self, node_rank: int | None = None, probe_fn=None) -> dict:
        """Probe now (in this process, or through ``probe_fn``) and
        re-arm from the measured cost."""
        report = (probe_fn or run_probe)(node_rank)
        self.seed(report)
        return report


def probe_disabled() -> bool:
    """DLROVER_PROBE_DISABLE=1 skips the probe entirely: joins carry an
    empty report, which the master's gate admits (pre-health-plane
    behavior) — the opt-out for arms where even milliseconds matter."""
    return os.environ.get("DLROVER_PROBE_DISABLE", "") == "1"


_SCHEDULER: ProbeScheduler | None = None


def default_scheduler() -> ProbeScheduler:
    """The process-wide scheduler: in the agent the rendezvous handlers
    (elastic training AND network check) share one cache, so
    back-to-back joins don't each re-pay the probe; in a worker it
    paces the in-band re-probe."""
    global _SCHEDULER
    if _SCHEDULER is None:
        _SCHEDULER = ProbeScheduler()
    return _SCHEDULER


def main(argv=None):
    import argparse

    parser = argparse.ArgumentParser(prog="dlrover_tpu.agent.probe")
    parser.add_argument("--node-rank", type=int, default=None)
    args = parser.parse_args(argv)
    report = run_probe(args.node_rank)
    # the last stdout line is the report (run_json_child's contract)
    print(json.dumps(report), flush=True)
    raise SystemExit(0 if not report["error"] else 1)


if __name__ == "__main__":
    main()
