"""dlint (tools/dlint) coverage: one positive and one negative fixture
per checker, escape-hatch comment parsing, baseline round-trip, the CLI
contract, and — the actual tier-1 gate — a full run over the repo that
fails on any unbaselined finding.
"""

import json
import os
import subprocess
import sys
import textwrap
import time

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)

from tools.dlint import Baseline, run_checks  # noqa: E402

pytestmark = pytest.mark.lint


def lint_file(tmp_path, source, checker, relpath="dlrover_tpu/common/mod.py"):
    """Write one fixture module and run a single checker over it."""
    path = tmp_path / relpath
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(textwrap.dedent(source))
    return run_checks(
        [str(path)], repo_root=str(tmp_path), checkers=[checker]
    )


# ---------------------------------------------------------------- DL001


class TestLockOrder:
    def test_inconsistent_nesting_order_flagged(self, tmp_path):
        found = lint_file(tmp_path, """
            import threading

            class A:
                def __init__(self):
                    self._alpha_lock = threading.Lock()
                    self._beta_lock = threading.Lock()

                def forward(self):
                    with self._alpha_lock:
                        with self._beta_lock:
                            pass

                def backward(self):
                    with self._beta_lock:
                        with self._alpha_lock:
                            pass
        """, "lock-order")
        assert len(found) == 1
        assert found[0].code == "DL001"
        assert "inconsistent lock order" in found[0].message

    def test_cycle_through_call_is_flagged(self, tmp_path):
        """The PR-2 shape: the second acquisition hides one call away."""
        found = lint_file(tmp_path, """
            import threading

            class A:
                def forward(self):
                    with self._alpha_lock:
                        self._grab_beta()

                def _grab_beta(self):
                    with self._beta_lock:
                        pass

                def backward(self):
                    with self._beta_lock:
                        with self._alpha_lock:
                            pass
        """, "lock-order")
        assert len(found) == 1
        assert "potential deadlock cycle" in found[0].message

    def test_self_reacquire_flagged_unless_rlock(self, tmp_path):
        src = """
            import threading

            class A:
                def __init__(self):
                    self._lock = threading.{ctor}()

                def outer(self):
                    with self._lock:
                        self.inner()

                def inner(self):
                    with self._lock:
                        pass
        """
        found = lint_file(tmp_path, src.format(ctor="Lock"), "lock-order")
        assert len(found) == 1
        assert "self-deadlock" in found[0].message
        clean = lint_file(tmp_path, src.format(ctor="RLock"), "lock-order")
        assert clean == []

    def test_consistent_order_is_clean(self, tmp_path):
        assert lint_file(tmp_path, """
            class A:
                def one(self):
                    with self._alpha_lock:
                        with self._beta_lock:
                            pass

                def two(self):
                    with self._alpha_lock:
                        with self._beta_lock:
                            pass
        """, "lock-order") == []


# ---------------------------------------------------------------- DL002


class TestBlockingUnderLock:
    def test_sleep_under_lock_flagged(self, tmp_path):
        found = lint_file(tmp_path, """
            import time

            class C:
                def poll(self):
                    with self._lock:
                        time.sleep(2)
        """, "blocking-under-lock")
        assert len(found) == 1
        assert found[0].code == "DL002"
        assert "time.sleep" in found[0].message

    def test_rpc_client_call_and_rmtree_flagged(self, tmp_path):
        found = lint_file(tmp_path, """
            import shutil

            class C:
                def report(self):
                    with self._lock:
                        self._client.report_task_result("ds", 3)

                def clean(self, delete_func):
                    with self._state_lock:
                        delete_func("/ckpt/step_5")
                        shutil.rmtree("/ckpt/step_6")
        """, "blocking-under-lock")
        kinds = {f.message.split(" (")[0] for f in found}
        assert "RPC round-trip" in kinds
        assert "file deletion" in kinds
        assert "recursive tree deletion" in kinds

    def test_acquire_release_span(self, tmp_path):
        found = lint_file(tmp_path, """
            import time

            class C:
                def locked_then_free(self):
                    self._lock.acquire()
                    time.sleep(1)
                    self._lock.release()
                    time.sleep(2)
        """, "blocking-under-lock")
        assert len(found) == 1  # only the sleep inside the span

    def test_one_liner_with_lock_body_flagged(self, tmp_path):
        """A body call sharing the `with` line is still under the lock
        — only the acquisition expression itself is exempt."""
        found = lint_file(tmp_path, """
            import time

            class C:
                def poll(self):
                    with self._lock: time.sleep(2)

                def flocked(self):
                    with self._py_lock():
                        pass
        """, "blocking-under-lock")
        assert len(found) == 1
        assert "time.sleep" in found[0].message

    def test_try_lock_idiom_not_flagged(self, tmp_path):
        """The ckpt_saver shape: `if acquire(): return` — the sleep on
        the not-acquired path is NOT under the lock."""
        assert lint_file(tmp_path, """
            import time

            class C:
                def wait_for(self, lock):
                    while True:
                        if lock.acquire(blocking=False):
                            return True
                        time.sleep(0.2)
        """, "blocking-under-lock") == []

    def test_negated_try_lock_holds_after(self, tmp_path):
        found = lint_file(tmp_path, """
            import time

            class C:
                def save(self):
                    if not self._shm_lock.acquire(blocking=False):
                        return False
                    time.sleep(1)
                    self._shm_lock.release()
        """, "blocking-under-lock")
        assert len(found) == 1

    def test_deferred_closures_under_lock_not_flagged(self, tmp_path):
        """Work defined under a lock but executed later (lambda /
        nested def handed to a thread) does not run under the hold."""
        assert lint_file(tmp_path, """
            import threading
            import time

            class C:
                def spawn(self):
                    with self._lock:
                        t = threading.Thread(
                            target=lambda: self._sock.recv(4)
                        )

                        def worker():
                            time.sleep(5)

                        self._pending = worker
                        t.start()
        """, "blocking-under-lock") == []

    def test_allow_blocking_escape_hatch(self, tmp_path):
        assert lint_file(tmp_path, """
            import time

            class C:
                def poll(self):
                    # dlint: allow-blocking(the hold is the contract)
                    with self._lock:
                        time.sleep(2)
        """, "blocking-under-lock") == []


# ---------------------------------------------------------------- DL003


class TestChaosCoverage:
    def test_uncovered_write_seam_flagged(self, tmp_path):
        found = lint_file(tmp_path, """
            def persist(path, data):
                with open(path, "wb") as f:
                    f.write(data)
        """, "chaos-coverage")
        assert len(found) == 1
        assert found[0].code == "DL003"
        assert "write-mode open" in found[0].message

    def test_chaos_point_in_function_covers(self, tmp_path):
        assert lint_file(tmp_path, """
            from dlrover_tpu.common.chaos import chaos_point

            def persist(path, data):
                chaos_point("storage.write", path=path)
                with open(path, "wb") as f:
                    f.write(data)
        """, "chaos-coverage") == []

    def test_caller_site_covers_within_hops(self, tmp_path):
        assert lint_file(tmp_path, """
            from dlrover_tpu.common.chaos import chaos_point

            def entry(path, data):
                chaos_point("storage.write", path=path)
                _helper(path, data)

            def _helper(path, data):
                with open(path, "wb") as f:
                    f.write(data)
        """, "chaos-coverage") == []

    def test_out_of_scope_layers_and_reads_ignored(self, tmp_path):
        # models/ is not a fault-injectable layer; read-mode open is
        # not a seam
        assert lint_file(tmp_path, """
            import subprocess

            def load(path):
                subprocess.run(["ls"])
                return open(path).read()
        """, "chaos-coverage",
            relpath="dlrover_tpu/models/zoo.py") == []
        assert lint_file(tmp_path, """
            def load(path):
                return open(path, "rb").read()
        """, "chaos-coverage") == []

    def test_subprocess_spawn_flagged_and_allow(self, tmp_path):
        found = lint_file(tmp_path, """
            import subprocess

            def launch():
                return subprocess.Popen(["master"])
        """, "chaos-coverage")
        assert len(found) == 1 and "subprocess spawn" in found[0].message
        assert lint_file(tmp_path, """
            import subprocess

            def launch():
                # dlint: allow-chaos(covered by master.spawn upstream)
                return subprocess.Popen(["master"])
        """, "chaos-coverage") == []

    def test_probe_loopback_seams_covered_by_timed_window_site(
        self, tmp_path
    ):
        """The health probe's shape: the chaos site fires INSIDE the
        timed window (probe.degrade) and the socket helpers sit one
        hop below it — within the hop budget, so agent/probe.py's
        loopback seams stay chaos-coverable without a per-helper
        site."""
        assert lint_file(tmp_path, """
            import socket

            from dlrover_tpu.common.chaos import chaos_point

            def collective_probe(rank):
                server, sender, conn = _loopback_pair()
                chaos_point("probe.degrade", leg="collective",
                            rank=rank)
                _loopback_rounds(sender, conn, 4)

            def _loopback_pair():
                server = socket.socket()
                sender = socket.create_connection(("127.0.0.1", 1))
                conn, _ = server.accept()
                return server, sender, conn

            def _loopback_rounds(sender, conn, rounds):
                for _ in range(rounds):
                    sender.sendall(b"x" * 8)
                    conn.recv(8)
        """, "chaos-coverage",
            relpath="dlrover_tpu/agent/probe.py") == []

    def test_uncovered_probe_socket_seam_flagged(self, tmp_path):
        """A probe helper whose socket op no chaos site can reach is a
        seam every schedule silently skips — flagged."""
        found = lint_file(tmp_path, """
            from dlrover_tpu.common.chaos import chaos_point

            def run_probe(rank):
                chaos_point("probe.degrade", leg="hbm", rank=rank)

            def _side_channel(conn):
                return conn.recv(4)
        """, "chaos-coverage",
            relpath="dlrover_tpu/agent/probe.py")
        assert len(found) == 1
        assert found[0].code == "DL003"
        assert "socket op" in found[0].message


# ---------------------------------------------------------------- DL004


class TestSignalSafety:
    def test_logging_in_handler_flagged(self, tmp_path):
        found = lint_file(tmp_path, """
            import signal
            from dlrover_tpu.common.log import get_logger

            logger = get_logger(__name__)

            def _handler(signum, frame):
                logger.warning("dying")

            signal.signal(signal.SIGTERM, _handler)
        """, "signal-safety")
        assert len(found) == 1
        assert found[0].code == "DL004"
        assert "logging call" in found[0].message

    def test_reachable_callee_checked_and_lock_flagged(self, tmp_path):
        found = lint_file(tmp_path, """
            import signal
            from dlrover_tpu.common import telemetry

            def _handler(signum, frame):
                _dump()

            def _dump():
                snap = telemetry.snapshot()
                with _REG_LOCK:
                    pass

            signal.signal(signal.SIGTERM, _handler)
        """, "signal-safety")
        kinds = {f.message.split(" in ")[0] for f in found}
        assert "telemetry.snapshot call" in kinds
        assert "unbounded lock acquire" in kinds

    def test_raw_fd_write_and_bounded_acquire_clean(self, tmp_path):
        assert lint_file(tmp_path, """
            import os
            import signal

            def _handler(signum, frame):
                os.write(2, b"dying\\n")
                if _REG_LOCK.acquire(timeout=0.5):
                    _REG_LOCK.release()

            signal.signal(signal.SIGTERM, _handler)
        """, "signal-safety") == []

    def test_allow_signal_escape_hatch(self, tmp_path):
        assert lint_file(tmp_path, """
            import signal
            from dlrover_tpu.common.log import get_logger

            logger = get_logger(__name__)

            def _handler(signum, frame):
                # dlint: allow-signal(guarded by _quiet upstream)
                logger.warning("dying")

            signal.signal(signal.SIGTERM, _handler)
        """, "signal-safety") == []

    def test_profiler_trace_in_handler_flagged(self, tmp_path):
        """Capture-trigger scope: starting/stopping jax.profiler
        within handler reach (here: one hop) is a DL004 finding."""
        found = lint_file(tmp_path, """
            import signal
            import jax

            def _handler(signum, frame):
                _emergency_profile()

            def _emergency_profile():
                jax.profiler.start_trace("/tmp/t")
                jax.profiler.stop_trace()

            signal.signal(signal.SIGTERM, _handler)
        """, "signal-safety")
        kinds = {f.message.split(" in ")[0] for f in found}
        assert "profiler start_trace call" in kinds
        assert "profiler stop_trace call" in kinds

    def test_capture_artifact_write_in_handler_flagged(self, tmp_path):
        found = lint_file(tmp_path, """
            import signal
            from dlrover_tpu.common import profiling

            def _handler(signum, frame):
                profiling.write_capture_artifact("/tmp/a", {}, {})

            signal.signal(signal.SIGTERM, _handler)
        """, "signal-safety")
        assert len(found) == 1
        assert "capture-artifact write" in found[0].message

    def test_profiler_outside_handler_clean(self, tmp_path):
        """The same calls OUTSIDE signal reach are fine — the sampler's
        step-boundary path must not need an allow hatch."""
        assert lint_file(tmp_path, """
            import jax
            from dlrover_tpu.common import profiling

            def sample_window(out_dir, summary, snap):
                jax.profiler.start_trace(out_dir)
                jax.profiler.stop_trace()
                profiling.write_capture_artifact(out_dir, summary, snap)
        """, "signal-safety") == []


# ---------------------------------------------------------------- DL005


class TestJitPurity:
    def test_item_and_asarray_on_param_flagged(self, tmp_path):
        found = lint_file(tmp_path, """
            import jax
            import numpy as np

            @jax.jit
            def step(params, batch):
                loss = compute(params, batch)
                host = np.asarray(batch)
                return loss.item() + host.sum()
        """, "jit-purity")
        labels = {f.message.split(" inside ")[0] for f in found}
        assert ".item() host sync" in labels
        assert any("np.asarray on traced argument" in x for x in labels)

    def test_wrap_call_time_and_print_flagged(self, tmp_path):
        found = lint_file(tmp_path, """
            import time
            import jax

            def step(x):
                print("step", time.time())
                return x

            fast_step = jax.jit(step)
        """, "jit-purity")
        labels = {f.message.split(" inside ")[0] for f in found}
        assert any("host clock read" in x for x in labels)
        assert any("print" in x for x in labels)

    def test_unjitted_and_debug_print_clean(self, tmp_path):
        assert lint_file(tmp_path, """
            import jax
            import numpy as np
            from functools import partial

            def host_side(x):
                return x.item()

            @partial(jax.jit, static_argnums=0)
            def step(n, x):
                jax.debug.print("x={x}", x=x)
                table = np.asarray([1.0, 2.0])  # literal: trace-time
                return x * n + table[0]
        """, "jit-purity") == []

    def test_allow_jit_escape_hatch(self, tmp_path):
        assert lint_file(tmp_path, """
            import jax

            @jax.jit
            def step(x):
                # dlint: allow-jit(trace-time banner, fires once)
                print("tracing step")
                return x
        """, "jit-purity") == []

    def test_pallas_kernel_impurities_flagged(self, tmp_path):
        found = lint_file(tmp_path, """
            import time
            import jax
            from jax.experimental import pallas as pl

            def kernel(x_ref, o_ref):
                print("inside", time.time())
                jax.pure_callback(lambda v: v, x_ref[0], x_ref[0])
                head = x_ref[0]
                o_ref[:] = x_ref[:] * head.item()

            def run(x):
                return pl.pallas_call(
                    kernel,
                    out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
                )(x)
        """, "jit-purity")
        labels = {f.message.split(" inside ")[0] for f in found}
        assert any("print" in x for x in labels)
        assert any("host clock read" in x for x in labels)
        assert any("host callback" in x for x in labels)
        assert ".item() host sync" in labels
        assert all("pallas kernel" in f.message for f in found)

    def test_pallas_kernel_bare_imported_callback_flagged(self, tmp_path):
        # `from jax import pure_callback` then a bare call: same defect
        # class as the dotted form, must not slip past the bare-name
        # exemption (which only covers a generic local `callback(...)`)
        found = lint_file(tmp_path, """
            from jax import pure_callback
            from jax.experimental import pallas as pl

            def kernel(x_ref, o_ref):
                pure_callback(lambda v: v, x_ref[0], x_ref[0])
                o_ref[:] = x_ref[:]

            def run(x, out_shape):
                return pl.pallas_call(kernel, out_shape=out_shape)(x)
        """, "jit-purity")
        assert len(found) == 1
        assert "host callback (pure_callback)" in found[0].message

    def test_pallas_kernel_bare_generic_callback_clean(self, tmp_path):
        # a local helper that happens to be NAMED `callback` is not a
        # host callback — only the unambiguous pure/io names are
        # flagged without a dotted qualifier
        assert lint_file(tmp_path, """
            from jax.experimental import pallas as pl

            def callback(v):
                return v * 2.0

            def kernel(x_ref, o_ref):
                o_ref[:] = callback(x_ref[:])

            def run(x, out_shape):
                return pl.pallas_call(kernel, out_shape=out_shape)(x)
        """, "jit-purity") == []

    def test_pallas_kernel_via_partial_flagged(self, tmp_path):
        found = lint_file(tmp_path, """
            import functools
            from jax.experimental import pallas as pl

            def kernel(x_ref, o_ref, *, scale):
                print("bad")
                o_ref[:] = x_ref[:] * scale

            def run(x, out_shape):
                return pl.pallas_call(
                    functools.partial(kernel, scale=2.0),
                    out_shape=out_shape,
                )(x)
        """, "jit-purity")
        assert len(found) == 1
        assert "pallas kernel" in found[0].message

    def test_pallas_clean_kernel_and_debug_print_ok(self, tmp_path):
        assert lint_file(tmp_path, """
            import jax.numpy as jnp
            from jax.experimental import pallas as pl

            def kernel(x_ref, o_ref):
                pl.debug_print("row max {}", jnp.max(x_ref[:]))
                o_ref[:] = x_ref[:] * 2.0

            def run(x, out_shape):
                return pl.pallas_call(kernel, out_shape=out_shape)(x)
        """, "jit-purity") == []


# ---------------------------------------------------------------- DL006


class TestMessageDrift:
    def _tree(self, tmp_path, messages, servicer, client):
        for rel, src in [
            ("dlrover_tpu/common/messages.py", messages),
            ("dlrover_tpu/master/servicer.py", servicer),
            ("dlrover_tpu/agent/master_client.py", client),
        ]:
            p = tmp_path / rel
            p.parent.mkdir(parents=True, exist_ok=True)
            p.write_text(textwrap.dedent(src))
        return run_checks(
            [str(tmp_path / "dlrover_tpu")], repo_root=str(tmp_path),
            checkers=["message-drift"],
        )

    MESSAGES = """
        from dataclasses import dataclass

        @dataclass
        class Message: pass

        @dataclass
        class PingRequest(Message):
            n: int = 0

        @dataclass
        class PingReply(Message):
            ok: bool = True

        @dataclass
        class GhostRequest(Message):
            pass

        @dataclass
        class DeadMessage(Message):
            pass
    """

    def test_missing_arm_unknown_and_dead(self, tmp_path):
        found = self._tree(
            tmp_path,
            self.MESSAGES,
            servicer="""
                from dlrover_tpu.common import messages as msg

                class Servicer:
                    def get(self, node_type, node_id, message):
                        if isinstance(message, msg.PingRequest):
                            return msg.PingReply(ok=True)
                        return None
            """,
            client="""
                from dlrover_tpu.common import messages as msg

                class Client:
                    def ping(self):
                        return self._get(msg.PingRequest(n=1))

                    def ghost(self):
                        return self._get(msg.GhostRequest())

                    def typo(self):
                        return self._get(msg.NoSuchMessage())
            """,
        )
        details = {f.detail for f in found}
        assert "missing-arm|GhostRequest" in details
        assert "unknown|NoSuchMessage" in details
        assert "dead|DeadMessage" in details
        # dispatched + response types are NOT dead
        assert not any("PingRequest" in d or "PingReply" in d
                       for d in details)

    def test_partial_scope_without_endpoints_is_silent(self, tmp_path):
        """Pre-commit on a path subset: messages.py in scope but the
        servicer/client endpoints not — reference sets are incomplete,
        so the checker must skip rather than call live messages dead."""
        p = tmp_path / "dlrover_tpu" / "common" / "messages.py"
        p.parent.mkdir(parents=True)
        p.write_text(textwrap.dedent(self.MESSAGES))
        assert run_checks(
            [str(p)], repo_root=str(tmp_path),
            checkers=["message-drift"],
        ) == []

    def test_fully_wired_protocol_clean(self, tmp_path):
        found = self._tree(
            tmp_path,
            """
                from dataclasses import dataclass

                @dataclass
                class Message: pass

                @dataclass
                class PingRequest(Message):
                    n: int = 0

                @dataclass
                class PingReply(Message):
                    ok: bool = True
            """,
            servicer="""
                from dlrover_tpu.common import messages as msg

                class Servicer:
                    def get(self, node_type, node_id, message):
                        if isinstance(message, msg.PingRequest):
                            return msg.PingReply(ok=True)
            """,
            client="""
                from dlrover_tpu.common import messages as msg

                class Client:
                    def ping(self):
                        reply = self._get(msg.PingRequest(n=1))
                        return isinstance(reply, msg.PingReply)
            """,
        )
        assert found == []


# ---------------------------------------------------------------- DL007


class TestMetricDrift:
    """Metric-name drift: names the operator surfaces QUERY must be
    EMITTED somewhere in the package (the DL006 idea applied to
    telemetry names)."""

    EMITTER = """
        from dlrover_tpu.common import telemetry

        def instrument():
            telemetry.gauge_set("ckpt.restore.read_gbps", 1.0)
            telemetry.counter_inc("live.metric")
            telemetry.observe("rpc.seconds", 0.1)
            telemetry.event("step.end", dur=0.1)
    """

    def _tree(self, tmp_path, consumer, emitter=None, **kw):
        pkg = tmp_path / "dlrover_tpu"
        pkg.mkdir(exist_ok=True)
        (pkg / "emit.py").write_text(
            textwrap.dedent(emitter or self.EMITTER)
        )
        tools = tmp_path / "tools"
        tools.mkdir(exist_ok=True)
        (tools / "obs_report.py").write_text(textwrap.dedent(consumer))
        return run_checks(
            [str(pkg), str(tools)], repo_root=str(tmp_path),
            checkers=["metric-drift"], **kw,
        )

    CONSUMER_MIXED = """
        def summary(metrics):
            out = {}
            for g in metrics["gauges"]:
                if g["name"] == "live.metric":
                    out[g["name"]] = g["value"]
                if g["name"] == "ghost.metric":
                    out[g["name"]] = g["value"]
                if g["name"].startswith(("ckpt.restore.", "ghost.")):
                    out[g["name"]] = g["value"]
            return out
    """

    def test_dead_query_and_prefix_flagged_live_pass(self, tmp_path):
        found = self._tree(tmp_path, self.CONSUMER_MIXED)
        details = sorted(f.detail for f in found)
        assert details == ["name|ghost.metric", "prefix|ghost."], details
        assert all(f.code == "DL007" for f in found)

    def test_event_kinds_count_as_emitted(self, tmp_path):
        found = self._tree(tmp_path, """
            def summary(timeline):
                return [e for e in timeline
                        if e["name"] == "step.end"]
        """)
        assert found == []

    def test_allow_hatch(self, tmp_path):
        found = self._tree(tmp_path, """
            def summary(metrics):
                return [
                    g for g in metrics
                    # dlint: allow-metric-drift(emitted w/ computed name)
                    if g["name"] == "dyn.metric"
                ]
        """)
        assert found == []

    def test_partial_scope_without_consumer_is_silent(self, tmp_path):
        """Only the package in scope: nothing queries, nothing to
        check (and no spurious dead-name findings)."""
        pkg = tmp_path / "dlrover_tpu"
        pkg.mkdir()
        (pkg / "emit.py").write_text(textwrap.dedent(self.EMITTER))
        assert run_checks(
            [str(pkg)], repo_root=str(tmp_path),
            checkers=["metric-drift"],
        ) == []

    def test_partial_scope_without_package_is_silent(self, tmp_path):
        """Only the consumer in scope (pre-commit on tools/): every
        queried name would look dead — the checker must skip."""
        tools = tmp_path / "tools"
        tools.mkdir()
        (tools / "obs_report.py").write_text(
            textwrap.dedent(self.CONSUMER_MIXED)
        )
        assert run_checks(
            [str(tools)], repo_root=str(tmp_path),
            checkers=["metric-drift"],
        ) == []

    def test_baseline_entry_path(self, tmp_path):
        """A justified false positive (e.g. a name emitted only with a
        computed first arg) can ride the baseline like every other
        checker's findings — and the fingerprint is line-stable."""
        found = self._tree(tmp_path, self.CONSUMER_MIXED)
        bl = Baseline(path=str(tmp_path / "baseline.json"))
        bl.update(found, note="emitted via variable name table")
        bl.save()
        bl = Baseline.load(str(tmp_path / "baseline.json"))
        new, stale = bl.diff(found)
        assert new == [] and stale == []
        assert bl.unjustified() == []


# -------------------------------------------------- escape-hatch parsing


class TestAllowDirectives:
    def test_reason_required(self, tmp_path):
        found = lint_file(tmp_path, """
            import time

            class C:
                def poll(self):
                    # dlint: allow-blocking
                    with self._lock:
                        time.sleep(2)
        """, "blocking-under-lock")
        codes = {f.code for f in found}
        # the reasonless allow is itself a finding AND does not suppress
        assert codes == {"DL000", "DL002"}

    def test_bare_allow_suppresses_everything_on_line(self, tmp_path):
        assert lint_file(tmp_path, """
            import time

            class C:
                def poll(self):
                    with self._lock:
                        time.sleep(2)  # dlint: allow(migration shim)
        """, "blocking-under-lock") == []

    def test_hash_inside_string_is_not_a_directive(self, tmp_path):
        found = lint_file(tmp_path, """
            import time

            class C:
                def poll(self):
                    with self._lock:
                        time.sleep(2)
                        tag = "# dlint: allow-blocking(fake)"
        """, "blocking-under-lock")
        assert len(found) == 1

    def test_wrong_checker_allow_does_not_suppress(self, tmp_path):
        found = lint_file(tmp_path, """
            import time

            class C:
                def poll(self):
                    # dlint: allow-chaos(wrong hatch)
                    with self._lock:
                        time.sleep(2)
        """, "blocking-under-lock")
        assert len(found) == 1


# ------------------------------------------------------ baseline + CLI


FIXTURE = """
import time


class C:
    def poll(self):
        with self._lock:
            time.sleep(2)
"""


class TestBaselineRoundTrip:
    def test_add_baseline_remove(self, tmp_path):
        mod = tmp_path / "pkg" / "mod.py"
        mod.parent.mkdir()
        mod.write_text(FIXTURE)
        bl_path = str(tmp_path / "baseline.json")

        findings = run_checks([str(mod)], repo_root=str(tmp_path))
        assert len(findings) == 1

        # 1) unbaselined -> shows as new
        bl = Baseline.load(bl_path)
        new, stale = bl.diff(findings)
        assert len(new) == 1 and stale == []

        # 2) baselined (with a justification) -> clean diff, survives
        #    a save/load round-trip
        bl.update(findings, note="fixture: demonstrates the loop")
        bl.save()
        bl2 = Baseline.load(bl_path)
        new, stale = bl2.diff(findings)
        assert new == [] and stale == []
        assert bl2.unjustified() == []

        # 3) code gets fixed -> entry is stale, not a failure
        mod.write_text(FIXTURE.replace("time.sleep(2)", "pass"))
        findings = run_checks([str(mod)], repo_root=str(tmp_path))
        assert findings == []
        new, stale = bl2.diff(findings)
        assert new == [] and len(stale) == 1

        # 4) --update-baseline semantics prune the stale entry
        bl2.update(findings)
        assert bl2.entries == {}

    def test_partial_update_preserves_out_of_scope_entries(self, tmp_path):
        """A --checker/path-subset update must not wipe justified
        entries the partial run never observed."""
        bl = Baseline(path=str(tmp_path / "b.json"))
        bl.entries = {"deadbeef00000000": {
            "fingerprint": "deadbeef00000000", "code": "DL003",
            "file": "other.py", "note": "justified elsewhere",
        }}
        mod = tmp_path / "mod.py"
        mod.write_text(FIXTURE)
        findings = run_checks([str(mod)], repo_root=str(tmp_path),
                              checkers=["blocking-under-lock"])
        bl.update(findings, prune=False)
        assert "deadbeef00000000" in bl.entries
        assert len(bl.entries) == 2
        bl.update(findings, prune=True)
        assert "deadbeef00000000" not in bl.entries

    def test_fingerprint_stable_across_line_drift(self, tmp_path):
        mod = tmp_path / "mod.py"
        mod.write_text(FIXTURE)
        fp1 = run_checks([str(mod)], repo_root=str(tmp_path))[0].fingerprint
        mod.write_text("# a new header comment\n\n" + FIXTURE)
        fp2 = run_checks([str(mod)], repo_root=str(tmp_path))[0].fingerprint
        assert fp1 == fp2


class TestCli:
    def _run(self, args, cwd):
        return subprocess.run(
            [sys.executable, os.path.join(REPO_ROOT, "tools", "lint.py"),
             *args],
            capture_output=True, text=True, timeout=120, cwd=cwd,
        )

    def test_exit_codes_and_json(self, tmp_path):
        mod = tmp_path / "mod.py"
        mod.write_text(FIXTURE)
        bl = str(tmp_path / "baseline.json")

        # new finding -> exit 1, listed in --json
        proc = self._run(
            ["--json", "--baseline", bl, str(mod)], str(tmp_path)
        )
        assert proc.returncode == 1, proc.stdout + proc.stderr
        payload = json.loads(proc.stdout)
        assert payload["total"] == 1 and len(payload["new"]) == 1

        # --update-baseline absorbs it (exit 0) but leaves a
        # placeholder note -> the next run exits 2 until justified
        proc = self._run(
            ["--update-baseline", "--baseline", bl, str(mod)],
            str(tmp_path),
        )
        assert proc.returncode == 0
        proc = self._run(["--baseline", bl, str(mod)], str(tmp_path))
        assert proc.returncode == 2, proc.stdout
        # --json stdout stays parseable even in the exit-2 case (the
        # unjustified diagnostics go to stderr / the payload)
        proc_json = self._run(
            ["--json", "--baseline", bl, str(mod)], str(tmp_path)
        )
        assert proc_json.returncode == 2
        payload = json.loads(proc_json.stdout)
        assert len(payload["unjustified_baseline"]) == 1
        data = json.load(open(bl))
        for e in data["findings"]:
            e["note"] = "fixture: justified"
        json.dump({"version": 1, "findings": data["findings"]},
                  open(bl, "w"))
        proc = self._run(["--baseline", bl, str(mod)], str(tmp_path))
        assert proc.returncode == 0, proc.stdout


# ---------------------------------------------------------------- DL008


class TestSharedMutation:
    def test_two_thread_roots_unguarded_flagged(self, tmp_path):
        found = lint_file(tmp_path, """
            import threading

            class C:
                def start(self):
                    threading.Thread(target=self._ticker).start()
                    threading.Thread(target=self._drainer).start()

                def _ticker(self):
                    self.count = self.count + 1

                def _drainer(self):
                    self.count = 0
        """, "shared-mut")
        assert len(found) == 1
        assert found[0].code == "DL008"
        assert "C.count" in found[0].message
        assert "no common lock" in found[0].message

    def test_common_lock_is_clean(self, tmp_path):
        assert lint_file(tmp_path, """
            import threading

            class C:
                def start(self):
                    threading.Thread(target=self._ticker).start()
                    threading.Thread(target=self._drainer).start()

                def _ticker(self):
                    with self._lock:
                        self.count = self.count + 1

                def _drainer(self):
                    with self._lock:
                        self.count = 0
        """, "shared-mut") == []

    def test_lock_flows_into_callee(self, tmp_path):
        """A write in a helper called under the lock is guarded —
        the held context follows the call graph."""
        assert lint_file(tmp_path, """
            import threading

            class C:
                def start(self):
                    threading.Thread(target=self._ticker).start()
                    threading.Thread(target=self._drainer).start()

                def _ticker(self):
                    with self._lock:
                        self._bump()

                def _bump(self):
                    self.count = self.count + 1

                def _drainer(self):
                    with self._lock:
                        self.count = 0
        """, "shared-mut") == []

    def test_condition_aliases_to_wrapped_lock(self, tmp_path):
        """The kvstore idiom: Condition(self._lock) and the lock
        itself guard the same critical sections."""
        assert lint_file(tmp_path, """
            import threading

            class C:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._cond = threading.Condition(self._lock)

                def start(self):
                    threading.Thread(target=self._put).start()
                    threading.Thread(target=self._take).start()

                def _put(self):
                    with self._cond:
                        self.pending = self.pending + 1

                def _take(self):
                    with self._lock:
                        self.pending = 0
        """, "shared-mut") == []

    def test_disjoint_locks_flagged(self, tmp_path):
        found = lint_file(tmp_path, """
            import threading

            class C:
                def start(self):
                    threading.Thread(target=self._put).start()
                    threading.Thread(target=self._take).start()

                def _put(self):
                    with self._a_lock:
                        self.pending = self.pending + 1

                def _take(self):
                    with self._b_lock:
                        self.pending = 0
        """, "shared-mut")
        assert len(found) == 1
        assert "C.pending" in found[0].message

    def test_loop_spawn_counts_as_two_roots(self, tmp_path):
        """N sibling threads of ONE target race each other — the
        ckpt-saver per-rank shape."""
        found = lint_file(tmp_path, """
            import threading

            class C:
                def start(self):
                    for i in range(4):
                        threading.Thread(
                            target=self._persist, args=(i,)
                        ).start()

                def _persist(self, i):
                    self.last_step = i
        """, "shared-mut")
        assert len(found) == 1
        assert "C.last_step" in found[0].message

    def test_single_root_single_thread_clean(self, tmp_path):
        assert lint_file(tmp_path, """
            import threading

            class C:
                def start(self):
                    threading.Thread(target=self._loop).start()

                def _loop(self):
                    self.beat = self.beat + 1
        """, "shared-mut") == []

    def test_two_spawn_sites_of_one_target_flagged(self, tmp_path):
        """Spawn sites are roots, not targets: two spawns of ONE
        target are two concurrent siblings sharing self."""
        found = lint_file(tmp_path, """
            import threading

            class C:
                def start(self):
                    threading.Thread(target=self._work).start()

                def boost(self):
                    threading.Thread(target=self._work).start()

                def _work(self):
                    self.count = self.count + 1
        """, "shared-mut")
        assert len(found) == 1
        assert "C.count" in found[0].message

    def test_thread_subclass_run_races_other_root(self, tmp_path):
        """run() of a Thread subclass is a root: its write races the
        timer tick's write to the same instance field."""
        found = lint_file(tmp_path, """
            import threading

            class Worker(threading.Thread):
                def arm(self):
                    threading.Timer(1.0, self._tick).start()

                def run(self):
                    self.count = self.count + 1

                def _tick(self):
                    self.count = 0
        """, "shared-mut")
        assert len(found) == 1
        assert "Worker.count" in found[0].message

    def test_servicer_arms_are_roots(self, tmp_path):
        """get/report run thread-per-connection: a bare field write
        from either is concurrent with itself."""
        found = lint_file(tmp_path, """
            class FooServicer(RpcService):
                def get(self, node_type, node_id, message):
                    self.calls = self.calls + 1
                    return None

                def report(self, node_type, node_id, message):
                    return True
        """, "shared-mut")
        assert len(found) == 1
        assert "FooServicer.calls" in found[0].message

    def test_mutator_on_component_not_flagged(self, tmp_path):
        """self.store.update(...) on a non-container component is that
        component's locking discipline, not a bare-container write."""
        assert lint_file(tmp_path, """
            import threading

            class C:
                def __init__(self, store):
                    self.store = store

                def start(self):
                    threading.Thread(target=self._a).start()
                    threading.Thread(target=self._b).start()

                def _a(self):
                    self.store.update({"x": 1})

                def _b(self):
                    self.store.update({"y": 2})
        """, "shared-mut") == []

    def test_mutator_on_plain_container_flagged(self, tmp_path):
        found = lint_file(tmp_path, """
            import threading

            class C:
                def __init__(self):
                    self.items = []

                def start(self):
                    threading.Thread(target=self._a).start()
                    threading.Thread(target=self._b).start()

                def _a(self):
                    self.items.append(1)

                def _b(self):
                    self.items.clear()
        """, "shared-mut")
        assert len(found) == 1
        assert "C.items" in found[0].message

    def test_allow_dl008_suppresses(self, tmp_path):
        found = lint_file(tmp_path, """
            import threading

            class C:
                def start(self):
                    threading.Thread(target=self._a).start()
                    threading.Thread(target=self._b).start()

                def _a(self):
                    # dlint: allow-DL008(single-writer by protocol: _b only runs after _a joins)
                    self.x = 1

                def _b(self):
                    self.x = 2
        """, "shared-mut")
        assert found == []


# ------------------------------------------------------- the tier-1 gate


class TestRepoGate:
    def test_repo_is_clean_against_baseline(self):
        """THE gate: any unbaselined finding on dlrover_tpu/ or tools/
        fails tier-1. Fix the code, add a one-line-justified
        ``# dlint: allow-<checker>(reason)``, or (false positives
        only) baseline it with a justification."""
        t0 = time.monotonic()
        findings = run_checks(
            [os.path.join(REPO_ROOT, "dlrover_tpu"),
             os.path.join(REPO_ROOT, "tools")],
            repo_root=REPO_ROOT,
        )
        elapsed = time.monotonic() - t0
        bl = Baseline.load(
            os.path.join(REPO_ROOT, "tools", "dlint", "baseline.json")
        )
        new, _stale = bl.diff(findings)
        assert new == [], "unbaselined dlint findings:\n" + "\n".join(
            f"  {f.file}:{f.line} [{f.code}] {f.message}" for f in new
        )
        assert bl.unjustified() == []
        # the gate must stay cheap enough to live in tier-1 (budget
        # raised 5→8 s after PR 12: the package grew ~1k lines and a
        # clean run takes ~4 s standalone but 5-6 s under full-suite
        # neighbor load on this shared VM)
        assert elapsed < 8.0, f"dlint gate took {elapsed:.1f}s"

    def test_baseline_entries_still_anchored(self):
        """Every baseline entry should still correspond to a live
        finding — stale entries mean fixed code, prune them."""
        findings = run_checks(
            [os.path.join(REPO_ROOT, "dlrover_tpu"),
             os.path.join(REPO_ROOT, "tools")],
            repo_root=REPO_ROOT,
        )
        bl = Baseline.load(
            os.path.join(REPO_ROOT, "tools", "dlint", "baseline.json")
        )
        _new, stale = bl.diff(findings)
        assert stale == [], (
            "stale baseline entries (code already fixed): "
            + ", ".join(e["fingerprint"] for e in stale)
        )
