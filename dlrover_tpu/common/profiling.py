"""Deep profiling plane, worker half: always-on device-time accounting,
anomaly-triggered deep captures, and the unified host+device timeline.

Equivalent capability: the reference pairs every job with **xpu_timer**
— an always-on native profiler timing GEMMs and collectives, exported
via Prometheus, with on-demand stack/trace dumps for a stuck process
(atorch/dev/xpu_timer). The TPU-native equivalent built here rides
jax.profiler's XPlane capture instead of an LD_PRELOAD hook:

- **Always-on accounting** (:class:`DeviceTimeSampler`): one profiled
  step every ``DLROVER_PROF_SAMPLE_STEPS`` steps, parsed in a
  background thread through the shared summarizer
  (:mod:`~dlrover_tpu.common.trace_summary`), published as
  ``device.optime_ms{category=...}`` gauges — per-op-category device
  time as a first-class telemetry series riding the live metrics
  plane, not a trace file someone has to fetch.
- **Op-cost baseline** (:class:`OpCostBaseline`): per
  (model-fingerprint, mesh-shape) persisted category costs, so a
  regression is attributable to a NAMED op category ("collective-
  permute +38% vs baseline"), not just "step got slower".
- **Deep capture** (:class:`CaptureChannel` + the sampler's capture
  window): the agent relays a master directive into the live worker
  over an atomic file channel (the reshape-channel idiom); the worker
  captures N steps of device trace plus the flight-recorder payload
  (span window, all-thread stacks, metrics-series tails) and writes a
  self-contained artifact including the merged Perfetto timeline.
- **One timeline** (:func:`merge_perfetto`): the cross-host span
  forest and the captured device time merged into a single
  Chrome-trace/Perfetto JSON, so a goodput dip is scrubbed on one
  screen from RPC to kernel.

Cost contract: with sampling disabled (``DLROVER_PROF_SAMPLE_STEPS=0``
or no parse toolchain) the per-step hooks are one attribute load and
one ``is None``/counter branch. Enabled, the steady-state cost is one
modulo per step plus one capture+parse every N steps. What each sample
cost the training loop is the counter ``prof.sample.cost_s``, on the
Trainer's own step clock; the governor spaces samples out until that
cost amortizes under the budget (PERF.md has the chip readings).
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import queue
import threading
import time

from dlrover_tpu.common import telemetry, trace_summary, tracing
from dlrover_tpu.common.chaos import chaos_point
from dlrover_tpu.common.log import get_logger

logger = get_logger(__name__)

ENV_SAMPLE_STEPS = "DLROVER_PROF_SAMPLE_STEPS"
ENV_CAPTURE_DIR = "DLROVER_PROF_CAPTURE_DIR"
ENV_BASELINE_PATH = "DLROVER_PROF_BASELINE_PATH"
ENV_REGRESSION_RATIO = "DLROVER_PROF_REGRESSION_RATIO"
# the sampler's steady-state overhead budget as a percent of training
# wall-clock: the cost governor stretches the sampling gap until the
# measured per-window cost amortizes under this. 0 disables governing
# (fixed cadence — tests).
ENV_OVERHEAD_PCT = "DLROVER_PROF_OVERHEAD_PCT"
# a share of the job's throughput, really spent since the governor
# amortizes against the true step time (PERF.md section 6, PR 26, has
# what a sample costs on a v5e and what 2 % then took)
DEFAULT_OVERHEAD_PCT = 0.2

DEFAULT_SAMPLE_STEPS = 64
DEFAULT_CAPTURE_STEPS = 2
# the one gauge family the always-on accounting publishes: per-category
# device self time per sampled step (Prometheus family
# ``dlrtpu_device_optime_ms{category=...,source=...}``)
OPTIME_GAUGE = "device.optime_ms"
# a sampled category this much above its stored baseline is a named
# regression (event ``device.optime.regression``), and the baseline
# freezes instead of folding the anomaly in
REGRESSION_RATIO = float(os.environ.get(ENV_REGRESSION_RATIO, "1.3"))
# EWMA weight of a fresh healthy sample folding into the baseline
BASELINE_EWMA = 0.25
# ignore sub-threshold categories when diffing: a 0.01 ms category
# tripling is noise, not an attribution
_MIN_ATTRIB_MS = 0.05

_READY_FILE = "capture_ready.json"
_REQUEST_FILE = "capture_request.json"
_ACK_FILE = "capture_ack.json"


def _write_atomic(path: str, payload: dict):
    # every durable write of the profiling plane funnels here: one
    # chaos seam covers the channel files, baselines and artifacts
    chaos_point("prof.write", path=os.path.basename(path))
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(payload, f)
    os.replace(tmp, path)


def _read_json(path: str) -> dict | None:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        return None
    except (OSError, ValueError):
        return None  # torn/absent: poll again


# -------------------------------------------------------------------------
# baseline keying
# -------------------------------------------------------------------------


def model_fingerprint(params) -> str:
    """Stable fingerprint of a model's parameter STRUCTURE (leaf paths,
    shapes, dtypes — not values): the baseline key half that survives
    restarts and reshapes of the same model."""
    try:
        import jax

        leaves = jax.tree_util.tree_flatten_with_path(params)[0]
        desc = [
            (
                jax.tree_util.keystr(path),
                tuple(getattr(leaf, "shape", ())),
                str(getattr(leaf, "dtype", type(leaf).__name__)),
            )
            for path, leaf in leaves
        ]
    except Exception:  # noqa: BLE001 - non-pytree state still gets a
        # deterministic (if coarser) key
        desc = repr(type(params))
    return hashlib.sha1(
        json.dumps(desc, sort_keys=True, default=str).encode()
    ).hexdigest()[:16]


def mesh_shape_key(mesh) -> str:
    """The mesh half of the baseline key: axis sizes in axis order
    (``data=2,fsdp=4``), device count as the fallback."""
    try:
        shape = dict(mesh.shape)
        return ",".join(f"{a}={n}" for a, n in shape.items())
    except Exception:  # noqa: BLE001
        try:
            return f"devices={len(mesh.devices.flat)}"
        except Exception:  # noqa: BLE001
            return "devices=?"


class OpCostBaseline:
    """Persisted per-(model-fingerprint, mesh-shape) op-category costs.

    One JSON file, atomically rewritten: ``{key: {"categories":
    {cat: ms}, "samples": n, "updated": t}}``. Updates fold healthy
    samples in with an EWMA; a sample where any significant category
    exceeds ``regression_ratio`` x its baseline FREEZES the baseline
    (the anomaly must stay attributable against the healthy past, not
    erode it)."""

    def __init__(self, path: str, regression_ratio: float = REGRESSION_RATIO):
        self.path = path
        self.regression_ratio = regression_ratio
        self._lock = threading.Lock()
        self._data: dict[str, dict] = {}
        loaded = _read_json(path)
        if isinstance(loaded, dict):
            self._data = loaded

    @staticmethod
    def key(fingerprint: str, mesh_key: str) -> str:
        return f"{fingerprint}|{mesh_key}"

    def get(self, key: str) -> dict | None:
        with self._lock:
            entry = self._data.get(key)
            return dict(entry["categories"]) if entry else None

    def update(self, key: str, categories: dict) -> tuple[dict, bool]:
        """Fold one sample in. Returns ``(baseline_after, regressed)``
        — ``regressed`` True when the sample breached the freeze ratio
        against the stored baseline (which then did NOT move)."""
        categories = {
            k: float(v) for k, v in (categories or {}).items()
        }
        with self._lock:
            entry = self._data.get(key)
            if entry is None:
                self._data[key] = {
                    "categories": dict(categories),
                    "samples": 1,
                    "updated": time.time(),
                }
                self._persist_locked()
                return dict(categories), False
            base = entry["categories"]
            regressed = any(
                base.get(cat, 0.0) > _MIN_ATTRIB_MS
                and ms > self.regression_ratio * base[cat]
                for cat, ms in categories.items()
                if ms > _MIN_ATTRIB_MS
            )
            if not regressed:
                a = BASELINE_EWMA
                for cat, ms in categories.items():
                    prev = base.get(cat)
                    base[cat] = (
                        ms if prev is None else (1 - a) * prev + a * ms
                    )
                entry["samples"] = int(entry.get("samples", 0)) + 1
                entry["updated"] = time.time()
                self._persist_locked()
            return dict(base), regressed

    def diff(self, key: str, categories: dict) -> list[dict]:
        """Attribution of a sample against the stored baseline, worst
        first: ``[{category, current_ms, baseline_ms, delta_pct}]``.
        Empty when no baseline exists for the key."""
        base = self.get(key)
        if base is None:
            return []
        out = []
        for cat in sorted(set(base) | set(categories or {})):
            cur = float((categories or {}).get(cat, 0.0))
            prev = float(base.get(cat, 0.0))
            if max(cur, prev) <= _MIN_ATTRIB_MS:
                continue
            delta = (
                (cur / prev - 1.0) * 100 if prev > 0 else float("inf")
            )
            out.append({
                "category": cat,
                "current_ms": round(cur, 4),
                "baseline_ms": round(prev, 4),
                "delta_pct": (
                    round(delta, 1) if delta != float("inf") else None
                ),
            })
        out.sort(
            key=lambda d: -(
                d["delta_pct"] if d["delta_pct"] is not None else 1e12
            )
        )
        return out

    def _persist_locked(self):
        try:
            os.makedirs(
                os.path.dirname(self.path) or ".", exist_ok=True
            )
            _write_atomic(self.path, self._data)
        except OSError as e:
            logger.warning("op-cost baseline persist failed: %s", e)


def baseline_from_env(out_dir: str) -> OpCostBaseline:
    """The baseline store at its well-known location:
    ``DLROVER_PROF_BASELINE_PATH`` wins, else the telemetry dir (shared
    across worker incarnations), else ``out_dir``."""
    path = os.environ.get(ENV_BASELINE_PATH, "")
    if not path:
        base = os.environ.get(telemetry.ENV_DIR, "") or out_dir
        path = os.path.join(base, "op_cost_baseline.json")
    return OpCostBaseline(path)


# -------------------------------------------------------------------------
# agent <-> worker capture channel (the reshape-channel idiom)
# -------------------------------------------------------------------------


@dataclasses.dataclass
class CaptureRequest:
    """One deep-capture directive, as handed to the live worker."""

    capture_id: str = ""
    steps: int = DEFAULT_CAPTURE_STEPS
    reason: str = ""

    def to_json(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_json(cls, payload: dict) -> "CaptureRequest":
        fields = {f.name for f in dataclasses.fields(cls)}
        return cls(**{
            k: v for k, v in payload.items() if k in fields
        })


class CaptureChannel:
    """Both halves of the capture file channel (the agent constructs
    one per local worker; the worker builds one from
    ``DLROVER_PROF_CAPTURE_DIR``)."""

    def __init__(self, directory: str):
        self.directory = directory
        os.makedirs(directory, exist_ok=True)
        # poll() decision cache: (request-file stat, last_id) whose
        # outcome was "nothing new" — the per-step cost contract is
        # ONE stat, so an already-consumed request must not be
        # re-opened and re-parsed on every subsequent step
        self._seen: tuple | None = None

    # ------------------------------------------------------- worker side

    def mark_ready(self):
        _write_atomic(
            os.path.join(self.directory, _READY_FILE),
            {"pid": os.getpid(), "t": time.time()},
        )

    def poll(self, last_id: str) -> CaptureRequest | None:
        path = os.path.join(self.directory, _REQUEST_FILE)
        try:
            st = os.stat(path)
        except OSError:
            return None
        stamp = (st.st_mtime_ns, st.st_size, last_id)
        if stamp == self._seen:
            return None  # unchanged file, already decided: stat only
        payload = _read_json(path)
        if not payload:
            return None
        req = CaptureRequest.from_json(payload)
        if not req.capture_id or req.capture_id == last_id:
            self._seen = stamp
            return None
        return req

    def ack(self, capture_id: str, ok: bool, artifact: str = "",
            summary: dict | None = None, error: str = ""):
        _write_atomic(
            os.path.join(self.directory, _ACK_FILE),
            {
                "capture_id": capture_id,
                "ok": bool(ok),
                "artifact": artifact,
                "summary": summary or {},
                "error": error,
                "t": time.time(),
            },
        )

    # -------------------------------------------------------- agent side

    def worker_ready(self) -> bool:
        return os.path.exists(
            os.path.join(self.directory, _READY_FILE)
        )

    def signal(self, request: CaptureRequest):
        _write_atomic(
            os.path.join(self.directory, _REQUEST_FILE),
            request.to_json(),
        )

    def read_ack(self, capture_id: str) -> dict | None:
        payload = _read_json(os.path.join(self.directory, _ACK_FILE))
        if payload and payload.get("capture_id") == capture_id:
            return payload
        return None

    def await_ack(
        self, capture_id: str, timeout: float, alive_fn=None,
        poll: float = 0.1,
    ) -> dict | None:
        deadline = time.time() + timeout
        while time.time() < deadline:
            ack = self.read_ack(capture_id)
            if ack is not None:
                return ack
            if alive_fn is not None and not alive_fn():
                return None
            time.sleep(poll)
        return None

    def clear(self):
        for name in (_REQUEST_FILE, _ACK_FILE, _READY_FILE):
            try:
                os.remove(os.path.join(self.directory, name))
            except OSError:
                pass


def execute_capture(
    directive: dict, channel: CaptureChannel, report_fn,
    timeout: float = 90.0, alive_fn=None,
) -> bool:
    """The agent half of a deep capture: relay the master's directive
    into the live worker over the channel, wait (bounded) for the
    artifact, and report the outcome. ``report_fn(capture_id, ok,
    artifact, summary, error)`` is the master report — factored out so
    the training agent and in-process harnesses run the SAME path."""
    cid = str(directive.get("capture_id", ""))
    if not cid:
        return False
    telemetry.event(
        "prof.capture.dispatch", capture=cid,
        reason=directive.get("reason", ""),
    )
    if not channel.worker_ready():
        report_fn(cid, False, "", {}, "no capture watcher on worker")
        return False
    channel.signal(CaptureRequest(
        capture_id=cid,
        steps=int(directive.get("steps") or DEFAULT_CAPTURE_STEPS),
        reason=str(directive.get("reason", "")),
    ))
    ack = channel.await_ack(cid, timeout, alive_fn=alive_fn)
    if ack is None:
        report_fn(cid, False, "", {}, "capture ack timeout")
        return False
    report_fn(
        cid, bool(ack.get("ok")), ack.get("artifact", ""),
        ack.get("summary") or {}, ack.get("error", ""),
    )
    return bool(ack.get("ok"))


# -------------------------------------------------------------------------
# the per-step sampler + deep-capture executor (worker side)
# -------------------------------------------------------------------------


class _JaxProfilerBackend:
    """Thin seam over jax.profiler so tests can swap the capture
    mechanism without touching jax."""

    def start(self, log_dir: str) -> bool:
        import jax

        os.makedirs(log_dir, exist_ok=True)
        try:
            jax.profiler.start_trace(log_dir)
            return True
        except Exception as e:  # noqa: BLE001 - a trace already active
            # (another profiler session in this process) must not kill
            # the training step; skip this sample window
            logger.warning("profiler start skipped: %s", e)
            return False

    def stop(self, block_on=None):
        import jax

        if block_on is not None:
            jax.block_until_ready(block_on)
        jax.profiler.stop_trace()


class DeviceTimeSampler:
    """Always-on per-step device-time accounting + deep-capture
    execution, driven by the trainer at step boundaries:

    - ``on_step_start(step)`` — may open a capture window (one sampled
      step every ``sample_steps``, or the N steps of a pending deep
      capture picked up from the channel).
    - ``on_step_end(step, dur_s, block_on)`` — closes a finished
      window and hands the trace to the background parse thread; the
      step loop never blocks on xprof.

    ``parse_fn(trace_dir, steps) -> {raw_category: ms_per_step}``
    defaults to the shared summarizer; when neither it nor the xprof
    toolchain is available, SAMPLING disables itself (capturing traces
    nobody can parse fails the <2% overhead contract for nothing) but
    deep captures still run — the raw trace plus the span/stack/series
    payload is worth shipping even unparsed.

    **Cost governor**: ``sample_steps`` is the FLOOR of the sampling
    gap, not a promise. What a window cost the loop — the step thread's
    time in the two hooks (profiler start/stop + dir churn) plus what
    the window's steps took, completion to completion as the Trainer
    hands it in, beyond as many EWMA steps — is counted into
    ``prof.sample.cost_s`` and amortized against the EWMA step time:
    the next sample is pushed out until the steady-state cost stays
    under ``overhead_pct`` (default 0.2 %), so "always-on" self-limits
    instead of taxing a fast-stepping job. Deep captures bypass the
    governor (someone explicitly asked). No window opens while the
    parse thread is still converting the last one: a second profiler
    session beside a running conversion stalled the loop for seconds.
    """

    def __init__(
        self,
        out_dir: str,
        sample_steps: int | None = None,
        parse_fn=None,
        baseline: OpCostBaseline | None = None,
        capture_channel: CaptureChannel | None = None,
        backend=None,
        artifact_root: str | None = None,
        overhead_pct: float | None = None,
    ):
        self.out_dir = out_dir
        if sample_steps is None:
            raw = os.environ.get(
                ENV_SAMPLE_STEPS, str(DEFAULT_SAMPLE_STEPS)
            ).strip().lower()
            sample_steps = (
                0 if raw in ("0", "off", "false", "no", "")
                else int(raw)
            )
        self.sample_steps = int(sample_steps)
        self.parse_fn = parse_fn
        self._backend = backend or _JaxProfilerBackend()
        self.baseline = baseline or baseline_from_env(out_dir)
        self.fingerprint = ""
        self.mesh_key = ""
        if capture_channel is None:
            cdir = os.environ.get(ENV_CAPTURE_DIR, "")
            capture_channel = CaptureChannel(cdir) if cdir else None
        self.channel = capture_channel
        if self.channel is not None:
            self.channel.mark_ready()
        self._artifact_root = artifact_root or os.path.join(
            os.environ.get(telemetry.ENV_DIR, "") or out_dir,
            "captures",
        )
        # sampling is viable only when something can parse the trace
        self._sampling = self.sample_steps > 0 and (
            parse_fn is not None or trace_summary.toolchain_available()
        )
        if overhead_pct is None:
            overhead_pct = float(
                os.environ.get(ENV_OVERHEAD_PCT,
                               str(DEFAULT_OVERHEAD_PCT))
            )
        self._overhead_frac = max(float(overhead_pct), 0.0) / 100.0
        # governor state: next step a sample is due at, EWMA of
        # untraced step time, last window's measured overhead cost
        self._next_sample = self.sample_steps
        self._step_ewma = 0.0
        self.last_window_cost_s = 0.0
        self.last_gap = self.sample_steps
        self._window: dict | None = None
        self._pending: CaptureRequest | None = None
        self._last_capture_id = ""
        self._sample_seq = 0
        self._sample_failures = 0
        self._emitted_cats: set = set()
        self._queue: queue.Queue = queue.Queue()
        self._worker: threading.Thread | None = None
        self._stopped = False

    # ------------------------------------------------------------ context

    def set_context(self, fingerprint: str, mesh_key: str):
        """The baseline key for subsequent samples — refreshed by the
        trainer once per (re)shape, never in the step loop."""
        self.fingerprint = fingerprint
        self.mesh_key = mesh_key

    @property
    def baseline_key(self) -> str:
        return OpCostBaseline.key(self.fingerprint, self.mesh_key)

    @property
    def sampling_enabled(self) -> bool:
        return self._sampling

    @property
    def _parsing(self) -> int:
        """Windows handed to the parse thread and not yet converted."""
        return self._queue.unfinished_tasks

    # --------------------------------------------------------- step hooks

    def on_step_start(self, step: int):
        if self._stopped:
            return
        if self.channel is not None and self._pending is None:
            req = self.channel.poll(self._last_capture_id)
            if req is not None:
                self._pending = req
                telemetry.event(
                    "prof.capture.begin", capture=req.capture_id,
                    steps=req.steps, reason=req.reason, step=step,
                )
        if self._window is not None:
            return
        if self._pending is not None:
            if self._parsing:
                return  # the capture opens once the conversion is over
            req = self._pending
            self._pending = None
            self._last_capture_id = req.capture_id
            tdir = os.path.join(
                self._artifact_root, req.capture_id, "trace"
            )
            if self._backend.start(tdir):
                self._window = {
                    "kind": "capture",
                    "dir": tdir,
                    "start_step": step,
                    "steps": max(int(req.steps), 1),
                    "request": req,
                    "t0": time.monotonic(),
                }
            elif self.channel is not None:
                self.channel.ack(
                    req.capture_id, False,
                    error="profiler start failed",
                )
            return
        if self._sampling and step > 0 and step >= self._next_sample:
            if self._parsing:
                # as with a refused start below: skip this sample
                self._next_sample = step + self.sample_steps
                return
            tdir = os.path.join(self.out_dir, "sample")
            import shutil

            t_begin = time.perf_counter()
            with tracing.span("prof.sample.start", step=step):
                shutil.rmtree(tdir, ignore_errors=True)
                started = self._backend.start(tdir)
            cost = time.perf_counter() - t_begin
            if started:
                self._window = {
                    "kind": "sample",
                    "dir": tdir,
                    "start_step": step,
                    "steps": 1,
                    "t0": time.monotonic(),
                    "cost_s": cost,
                    "steps_s": 0.0,
                }
            else:
                # a refused start (another trace active) still re-arms
                # at the floor cadence, never a hot retry every step
                self._next_sample = step + self.sample_steps

    def on_step_end(self, step: int, dur_s: float = 0.0, block_on=None):
        win = self._window
        if win is None:
            # untraced steps feed the governor's step-time EWMA (a
            # TRACED step runs under instrumentation and would bias
            # the denominator the overhead is amortized against)
            if dur_s > 0:
                self._step_ewma = (
                    dur_s if self._step_ewma <= 0
                    else 0.9 * self._step_ewma + 0.1 * dur_s
                )
            return
        if win["kind"] == "sample" and step >= win["start_step"]:
            win["steps_s"] += dur_s
        if step < win["start_step"] + win["steps"] - 1:
            return
        self._window = None
        t_begin = time.perf_counter()
        try:
            with tracing.span(
                f"prof.{win['kind']}.stop", step=step
            ) as stop_span:
                try:
                    self._backend.stop(block_on=block_on)
                finally:
                    if win["kind"] == "sample":
                        # neither hook's time is inside a step of the
                        # Trainer's clock (a step starts at its
                        # dispatch), so the two add
                        slower = (
                            win["steps_s"] - win["steps"] * self._step_ewma
                            if self._step_ewma > 0 else 0.0
                        )
                        cost = (
                            win["cost_s"] + (time.perf_counter() - t_begin)
                            + max(slower, 0.0)
                        )
                        stop_span.annotate(cost_s=cost)
                        self._govern(step, cost)
        except Exception:  # noqa: BLE001 - a stop failure must not
            # take the training step down; the window is simply lost
            logger.warning("profiler stop failed", exc_info=True)
            if win["kind"] == "capture" and self.channel is not None:
                self.channel.ack(
                    win["request"].capture_id, False,
                    error="profiler stop failed",
                )
            return
        win["wall_s"] = time.monotonic() - win["t0"]
        win["end_step"] = step
        self._ensure_worker()
        self._queue.put(win)

    def _govern(self, step: int, window_cost_s: float):
        """Re-arm the next sample so the measured per-window overhead
        amortizes under the budget: gap >= cost / (budget * step_time)
        makes steady-state overhead <= budget by construction."""
        self.last_window_cost_s = window_cost_s
        telemetry.counter_inc("prof.sample.cost_s", window_cost_s)
        gap = self.sample_steps
        if self._overhead_frac > 0 and self._step_ewma > 0:
            gap = max(gap, int(
                window_cost_s
                / (self._overhead_frac * self._step_ewma)
            ) + 1)
        self._next_sample = step + gap
        self.last_gap = gap
        telemetry.gauge_set("device.optime.sample_gap", gap)
        telemetry.gauge_set(
            "device.optime.window_cost_ms",
            round(window_cost_s * 1e3, 3),
        )

    # ----------------------------------------------------- parse worker

    def _ensure_worker(self):
        if self._worker is None or not self._worker.is_alive():
            self._worker = threading.Thread(
                target=self._run, name="prof-parse", daemon=True
            )
            self._worker.start()

    def _run(self):
        while True:
            job = self._queue.get()
            if job is None:
                return
            try:
                with tracing.span("prof.parse", window=job["kind"]):
                    if job["kind"] == "sample":
                        self._parse_sample(job)
                    else:
                        self._finish_capture(job)
            except Exception:  # noqa: BLE001 - the parse thread must
                # survive a bad trace; a capture failure is acked below
                logger.warning(
                    "profile %s parse failed", job["kind"], exc_info=True
                )
                if job["kind"] == "sample":
                    # a parser that REPEATEDLY cannot parse will not
                    # parse the next sample either: stop paying the
                    # capture overhead. One failure is tolerated —
                    # trace finalization races and transient I/O must
                    # not turn always-on accounting off for good.
                    self._sample_failures += 1
                    if self._sample_failures >= 2:
                        self._sampling = False
                        logger.warning(
                            "device-time sampling disabled after %d "
                            "consecutive parse failures",
                            self._sample_failures,
                        )
                elif self.channel is not None:
                    self.channel.ack(
                        job["request"].capture_id, False,
                        error="capture parse/artifact failed",
                    )
            finally:
                self._queue.task_done()

    @staticmethod
    def _await_xplane(trace_dir: str, timeout: float = 5.0) -> bool:
        """The profiler plugin finalizes the ``*.xplane.pb`` file
        ASYNCHRONOUSLY after ``stop_trace`` returns — poll (off the
        step thread) until it lands or the timeout passes."""
        deadline = time.time() + timeout
        while time.time() < deadline:
            if trace_summary.xplane_paths(trace_dir):
                return True
            time.sleep(0.05)
        return bool(trace_summary.xplane_paths(trace_dir))

    def _parse(self, trace_dir: str, steps: int) -> dict:
        if self.parse_fn is not None:
            # an injected parser owns its own input contract (it may
            # not read trace files at all — tests)
            return dict(self.parse_fn(trace_dir, steps) or {})
        self._await_xplane(trace_dir)
        summary = trace_summary.summarize(trace_dir, steps=steps)
        return dict((summary or {}).get("by_category") or {})

    def _parse_sample(self, job: dict):
        raw = self._parse(job["dir"], job["steps"])
        self._sample_failures = 0
        cats = trace_summary.canonical_breakdown(raw)
        if not cats:
            return
        total = sum(cats.values())
        # a category that vanished from this sample (optimization
        # landed, mesh reshaped) must drop to 0, not freeze at its
        # last value on /metrics forever
        for stale in self._emitted_cats - set(cats):
            telemetry.gauge_set(OPTIME_GAUGE, 0.0, category=stale)
        self._emitted_cats = set(cats)
        for cat, ms in sorted(cats.items()):
            telemetry.gauge_set(OPTIME_GAUGE, ms, category=cat)
        telemetry.gauge_set("device.optime.total_ms", total)
        telemetry.gauge_set(
            "device.optime.sample_step", job["start_step"]
        )
        telemetry.counter_inc("prof.samples")
        self._sample_seq += 1
        key = self.baseline_key
        base, regressed = self.baseline.update(key, cats)
        if regressed:
            attribution = self.baseline.diff(key, cats)
            worst = attribution[0] if attribution else {}
            telemetry.event(
                "device.optime.regression",
                step=job["start_step"],
                category=worst.get("category", "?"),
                delta_pct=worst.get("delta_pct"),
                baseline_key=key,
            )
            telemetry.counter_inc("prof.regressions")
            logger.warning(
                "device-time regression at step %s: %s",
                job["start_step"], worst,
            )

    def _finish_capture(self, job: dict):
        req: CaptureRequest = job["request"]
        raw = {}
        parse_error = ""
        try:
            raw = self._parse(job["dir"], job["steps"])
        except Exception as e:  # noqa: BLE001 - the trace + flight
            # payload still ship; attribution is just absent
            parse_error = f"{type(e).__name__}: {e}"[:200]
        cats = trace_summary.canonical_breakdown(raw)
        key = self.baseline_key
        attribution = self.baseline.diff(key, cats) if cats else []
        snap = telemetry.snapshot() or {}
        summary = {
            "capture_id": req.capture_id,
            "reason": req.reason,
            "steps": job["steps"],
            "start_step": job["start_step"],
            "end_step": job["end_step"],
            "wall_s": round(job["wall_s"], 4),
            "baseline_key": key,
            "categories": {
                c: round(v, 4) for c, v in sorted(cats.items())
            },
            "attribution": attribution,
            "parse_error": parse_error,
            "source": snap.get("source", ""),
        }
        artifact_dir = os.path.join(self._artifact_root, req.capture_id)
        write_capture_artifact(artifact_dir, summary, snap)
        telemetry.event(
            "prof.capture.done", capture=req.capture_id,
            dur=job["wall_s"], artifact=artifact_dir,
        )
        telemetry.counter_inc("prof.captures")
        if self.channel is not None:
            self.channel.ack(
                req.capture_id, True, artifact=artifact_dir,
                summary=summary,
            )

    def close(self):
        self._stopped = True
        if self._window is not None:
            try:
                self._backend.stop()
            except Exception:  # noqa: BLE001 - shutting down anyway
                pass
            self._window = None
        if self._worker is not None and self._worker.is_alive():
            self._queue.put(None)
            self._worker.join(timeout=10)


# -------------------------------------------------------------------------
# capture artifacts + the unified Perfetto timeline
# -------------------------------------------------------------------------


def write_capture_artifact(
    artifact_dir: str, summary: dict, snap: dict,
) -> dict:
    """Write a self-contained capture artifact next to the raw trace:

    - ``summary.json`` — per-category device times + the attribution
      diff vs the stored baseline,
    - ``flight.json`` — the flight-recorder payload (span/event window,
      all-thread stacks, metrics-series tails),
    - ``timeline.perfetto.json`` — host spans and device time merged
      into one Chrome-trace/Perfetto timeline.

    NOT signal-safe (lock-taking snapshot, multi-file I/O): dlint DL004
    flags any path that reaches this within two hops of a signal
    handler — crash paths keep :func:`flight.dump`.
    Returns ``{name: path}`` for the written files."""
    from dlrover_tpu.common import flight

    os.makedirs(artifact_dir, exist_ok=True)
    out = {}
    out["summary"] = os.path.join(artifact_dir, "summary.json")
    _write_atomic(out["summary"], summary)
    flight_rec = flight.build_record(
        snap, f"capture:{summary.get('reason', '')}"
    )
    out["flight"] = os.path.join(artifact_dir, "flight.json")
    _write_atomic(out["flight"], flight_rec)
    window = None
    if summary.get("wall_s"):
        end = flight_rec["time"]
        window = (end - float(summary["wall_s"]), end)
    merged = merge_perfetto(
        snap.get("events", []),
        device_categories=summary.get("categories"),
        device_window=window,
        device_trace_events=device_trace_from_xplane(
            os.path.join(artifact_dir, "trace")
        ),
    )
    out["perfetto"] = os.path.join(
        artifact_dir, "timeline.perfetto.json"
    )
    _write_atomic(out["perfetto"], merged)
    return out


def device_trace_from_xplane(trace_dir: str) -> list | None:
    """Chrome-trace events of the captured device timeline via xprof's
    ``trace_viewer`` conversion, or None when the toolchain (or the
    trace) is unavailable — the merge then falls back to the category
    summary rendered as proportional slices."""
    if not trace_summary.toolchain_available():
        return None
    paths = trace_summary.xplane_paths(trace_dir)
    if not paths:
        return None
    try:
        from xprof.convert import raw_to_tool_data as rtd

        data, _ = rtd.xspace_to_tool_data(paths, "trace_viewer", {})
        if isinstance(data, bytes):
            data = data.decode()
        obj = json.loads(data)
        events = obj.get("traceEvents")
        return list(events) if events else None
    except Exception:  # noqa: BLE001 - converter drift: degrade to the
        # summary-slice rendering rather than lose the whole artifact
        logger.warning("trace_viewer conversion failed", exc_info=True)
        return None


def merge_perfetto(
    events,
    device_categories: dict | None = None,
    device_window: tuple | None = None,
    device_trace_events: list | None = None,
) -> dict:
    """Merge a (host) telemetry timeline with captured device time into
    ONE Chrome-trace/Perfetto JSON.

    - Host side: every ``span`` event becomes a complete slice on its
      source's track (other ``dur``-carrying events too; instantaneous
      events become instants), so rdzv rounds, ckpt stages, reshape
      drains and DATA_WAIT scrub on the same screen.
    - Device side: the real per-event device timeline when xprof's
      trace_viewer conversion produced one (``device_trace_events``),
      else the per-category accounting rendered as proportional slices
      across the capture window — an honest accounting view when the
      full converter is absent.

    Timestamps are wall-clock microseconds rebased to the earliest
    event so Perfetto's UI opens at t=0.
    """
    events = list(events or ())
    starts = []
    for ev in events:
        t = float(ev.get("t", 0.0))
        dur = float(ev.get("dur") or 0.0)
        starts.append(t - dur)
    if device_window:
        starts.append(float(device_window[0]))
    t0 = min(starts) if starts else 0.0

    pids: dict[str, int] = {}

    def pid_of(source: str) -> int:
        if source not in pids:
            pids[source] = len(pids) + 1
        return pids[source]

    trace: list[dict] = []
    for ev in events:
        source = str(ev.get("source", "") or "host")
        pid = pid_of(source)
        t = float(ev.get("t", 0.0))
        dur = float(ev.get("dur") or 0.0)
        name = (
            str(ev.get("name"))
            if ev.get("kind") == "span" and ev.get("name")
            else str(ev.get("kind", "event"))
        )
        args = {
            k: v for k, v in ev.items()
            if k not in ("t", "mono", "seq", "source", "kind", "dur")
            and isinstance(v, (str, int, float, bool))
        }
        if dur > 0:
            trace.append({
                "ph": "X",
                "name": name,
                "cat": "host",
                "pid": pid,
                "tid": 1,
                "ts": round((t - dur - t0) * 1e6, 1),
                "dur": round(dur * 1e6, 1),
                "args": args,
            })
        else:
            trace.append({
                "ph": "i",
                "s": "p",
                "name": name,
                "cat": "host",
                "pid": pid,
                "tid": 1,
                "ts": round((t - t0) * 1e6, 1),
                "args": args,
            })
    device_pid = len(pids) + 1
    if device_trace_events:
        # the real device timeline: keep its internal tids, re-home it
        # onto the device track's pid — and REBASE its timestamps onto
        # the host timeline (xprof events carry their own trace-start
        # timebase; copied verbatim they would render at t=0 instead
        # of inside the capture window). Anchor the earliest device
        # event at the capture window start when known, else at the
        # host t0.
        dev_ts = [
            float(ev["ts"]) for ev in device_trace_events
            if "ts" in ev
        ]
        dev_min = min(dev_ts) if dev_ts else 0.0
        anchor_us = (
            (float(device_window[0]) - t0) * 1e6
            if device_window else 0.0
        )
        offset = anchor_us - dev_min
        for ev in device_trace_events:
            ev = dict(ev)
            ev["pid"] = device_pid
            ev.setdefault("cat", "device")
            if "ts" in ev:
                ev["ts"] = round(float(ev["ts"]) + offset, 1)
            trace.append(ev)
    elif device_categories:
        if device_window:
            w0, w1 = float(device_window[0]), float(device_window[1])
        else:
            w0 = t0
            w1 = t0 + sum(device_categories.values()) / 1e3
        span = max(w1 - w0, 1e-6)
        total = sum(device_categories.values()) or 1.0
        cursor = w0
        for cat, ms in sorted(
            device_categories.items(), key=lambda kv: -kv[1]
        ):
            frac = ms / total
            trace.append({
                "ph": "X",
                "name": cat,
                "cat": "device",
                "pid": device_pid,
                "tid": 1,
                "ts": round((cursor - t0) * 1e6, 1),
                "dur": round(span * frac * 1e6, 1),
                "args": {"self_ms_per_step": round(ms, 4)},
            })
            cursor += span * frac
    for source, pid in pids.items():
        trace.append({
            "ph": "M", "name": "process_name", "pid": pid,
            "args": {"name": source},
        })
    trace.append({
        "ph": "M", "name": "process_name", "pid": device_pid,
        "args": {"name": "device"},
    })
    return {"traceEvents": trace, "displayTimeUnit": "ms"}
