#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

reads ``workloads/<name>.json``, from it ``configs/<config>.json``, and
runs the cell. The last line of standard output is the result, one
JSON object; everything else goes to standard error. Nothing about one
cell, one configuration or one metric is in this file: a cell is a
workload file (and perhaps a configuration file) plus an entry in
``BENCHMARK.json``.

It runs the program's ``Trainer`` in this process (the checkpoint
engine's standalone mode), on weights the Trainer makes on the device
from ``--seed`` and one seeded batch, repeated.

- set-up: backend, weights, agreement with the plain reference on the
  first sequence, ``warmup_steps`` steps (compiles, or loads from
  ``<checkout>/.jax_cache``; in a save cell they hold the save that
  creates the shm segment);
- the window opens and closes at an *edge*, a moment at which the host
  has waited for the device: a loss read-back of the Trainer (timed by
  a tap on its log record) or, where the workload says ``"edge":
  "save"``, the return of a save. It opens at the first edge at or
  after step ``warmup_steps`` and closes at the first edge ``--seconds``
  later, so a window is whole sync intervals, or whole save cycles
  (``save_steps`` steps and the save that follows them). A rate is all
  the window's steps over all its seconds, stalls included;
- ``--trace 1`` keeps the profiler on from the first edge a third of
  ``--seconds`` into the window to the next edge (one sync interval,
  or one save cycle) and reports the per-layer metrics the workload
  file lists (``layer_metrics/<name>.json``) in place of the
  end-to-end ones.
"""

import time

T_PROCESS_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import logging  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
SEARCH = (HERE, os.path.join(HERE, "tests"))
# The program names its shm segments and sockets after the job, under
# /dev/shm, outside the checkout: a name of this process alone, so that
# two checkouts running side by side share nothing there.
JOB_NAME = f"bench{os.getpid()}"
SOCKET_PATH_LIMIT = 70   # sun_path holds 108 bytes; names add ~35
# A traced run turns the profiler on a third of the way into its window
# and not at the opening: the program's own device-time sampler takes
# its first sample in warm-up and parses it in a thread for seconds
# after; a second profiler session started meanwhile stalled the
# training loop for 9 s (PERF.md, section 5).
TRACE_AFTER = 1 / 3

EXIT_NO_DEVICE = 3


def log(*parts):
    """To standard error, with the seconds since the process started."""
    print(f"[bench {time.time() - T_PROCESS_START:7.2f}s]", *parts,
          file=sys.stderr, flush=True)


def load(kind: str, name: str) -> dict:
    for base in SEARCH:
        path = os.path.join(base, kind, name + ".json")
        if os.path.exists(path):
            with open(path) as f:
                return json.load(f)
    raise SystemExit(f"no {kind}/{name}.json under {SEARCH}")


def layer_metrics(names) -> dict:
    """The per-layer metric files the workload file lists."""
    return {name: load("layer_metrics", name) for name in names}


# ---------------------------------------------------------------- run dir


def prepare_run_dir(workload_name: str) -> str:
    """A scratch directory inside the checkout, emptied, and the
    program's side files (sockets, shm names, progress file) pointed
    into it so that a run shares nothing with another checkout."""
    run_dir = os.path.join(ROOT, ".bench_run", workload_name)
    shutil.rmtree(run_dir, ignore_errors=True)
    os.makedirs(run_dir)
    socket_dir = os.path.join(run_dir, "s")
    if len(socket_dir) > SOCKET_PATH_LIMIT:
        # unix socket paths are short: fall back to this run's TMPDIR
        socket_dir = tempfile.mkdtemp(prefix="dlb")
    os.makedirs(socket_dir, exist_ok=True)
    os.environ["DLROVER_TPU_SOCKET_DIR"] = socket_dir
    os.environ["ELASTIC_JOB_NAME"] = JOB_NAME
    os.environ["DLROVER_RUNTIME_METRICS_PATH"] = os.path.join(
        run_dir, "runtime_metrics.json"
    )
    unlink_shm()
    return run_dir


def remove_run_dir(run_dir: str) -> list:
    """Leave nothing behind; returns the shm segments that were there."""
    left = unlink_shm()
    shutil.rmtree(run_dir, ignore_errors=True)
    socket_dir = os.environ["DLROVER_TPU_SOCKET_DIR"]
    if not socket_dir.startswith(run_dir):
        shutil.rmtree(socket_dir, ignore_errors=True)
    return left


def unlink_shm() -> list:
    """Remove this job's shm segments (PersistentSharedMemory outlives
    its creator by design). Returns what was there."""
    found = []
    if os.path.isdir("/dev/shm"):
        for entry in os.listdir("/dev/shm"):
            if entry.startswith("dlrtpu_") and f"_{JOB_NAME}_" in entry + "_":
                found.append(entry)
                try:
                    os.unlink(os.path.join("/dev/shm", entry))
                except OSError:
                    pass
    return found


# ---------------------------------------------------------- the profiler


def start_trace(trace_dir: str):
    """Profiler on, host spans at TraceAnnotation level, no Python
    tracer; returns the open ``bench.window`` span that
    ``trace_reduce`` takes as the traced stretch."""
    import jax

    import trace_reduce

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    span = jax.profiler.TraceAnnotation(trace_reduce.WINDOW_NAME)
    span.__enter__()
    return span


def stop_trace(span):
    import jax

    span.__exit__(None, None, None)
    jax.profiler.stop_trace()


def reduced_trace(trace_dir: str):
    """The reduction of the newest trace under ``trace_dir``, or None."""
    import trace_reduce

    xplane = trace_reduce.find_xplane(trace_dir)
    return xplane and trace_reduce.reduce(trace_reduce.load_xplane(xplane))


# ------------------------------------------------------------ the window


class WindowOver(Exception):
    """Raised into ``Trainer.train`` from the data iterator (or the
    save wrapper) once the window has closed: the run ends there and
    not in the Trainer's final save and persist wait, 8 GB to disk that
    every run of every later check would pay for after its window."""


class Window:
    """Phases of a run, driven by the Trainer's sync points: its loss
    read-backs and the returns of its saves. At either the host has
    waited for the device. Those of the kind ``edge`` names are the
    edges at which the window opens and closes."""

    def __init__(self, seconds, warmup_steps, edge, trace_dir):
        if edge not in ("readback", "save"):
            raise SystemExit(f"unknown edge {edge!r}")
        self.seconds, self.warmup_steps, self.edge = seconds, warmup_steps, edge
        self.trace_dir = trace_dir
        self.state = "warmup"
        self.t_open_wall = None
        self.steps = 0          # of the whole window
        self.elapsed = 0.0      # its seconds, stalls included
        self.intervals = []     # sync point to read-back: steps, seconds, ...
        self.saves = []         # in-window saves
        self.warmup_saves = []
        self.losses = []        # every loss read back, from the opening on
        self.tracing = False
        self.traced_steps = 0
        self._t_open = self._open_step = self._trace_step = None
        self._last_t = self._last_step = None
        self._host_span = None
        self._window_span = None

    # -- profiler -------------------------------------------------------

    def _start_trace(self):
        self._window_span = start_trace(self.trace_dir)
        self.tracing = True

    def _stop_trace(self):
        self._end_host_span()
        stop_trace(self._window_span)
        self.tracing = False

    def span(self, name):
        """A host span in the profiler's own trace while it is on."""
        import contextlib

        import jax

        if not self.tracing:
            return contextlib.nullcontext()
        return jax.profiler.TraceAnnotation("bench." + name)

    def _begin_host_span(self, name):
        if self.tracing:
            self._host_span = self.span(name)
            self._host_span.__enter__()

    def _end_host_span(self):
        if self._host_span is not None:
            self._host_span.__exit__(None, None, None)
            self._host_span = None

    # -- events ---------------------------------------------------------

    def _sync_point(self, kind, step):
        """The device has finished every step up to ``step``."""
        now = time.perf_counter()
        if kind == self.edge:
            if self.state == "open":
                self.steps = step - self._open_step
                self.elapsed = now - self._t_open
                closing = self.elapsed >= self.seconds
                if self.tracing:
                    self.traced_steps = step - self._trace_step
                    self._stop_trace()
                elif (self.trace_dir and not self.traced_steps and not closing
                        and self.elapsed >= self.seconds * TRACE_AFTER):
                    # the traced stretch: from this edge to the next
                    self._start_trace()
                    self._trace_step = step
                if closing:
                    self.state = "closed"
            elif self.state == "warmup" and step >= self.warmup_steps:
                self.state = "open"
                self._t_open, self._open_step = now, step
                self.t_open_wall = time.time()
            now = time.perf_counter()
        self._last_t, self._last_step = now, step

    def on_readback(self, step, loss):
        """The Trainer has just read the loss of ``step`` back."""
        if self.state == "open" and step > self._last_step:
            # the steps since the last sync point: an interval that
            # holds no save, because a save's return is a sync point too
            self.intervals.append({
                "steps": step - self._last_step,
                "seconds": time.perf_counter() - self._last_t,
                "traced": self.tracing, "step": step, "loss": loss,
            })
        self._sync_point("readback", step)
        if self.state != "warmup":
            self.losses.append(loss)
        # from here to the next batch pull the host logs and flushes
        self._begin_host_span("log_flush")

    def on_save(self, record):
        """A save of the Trainer has just returned."""
        (self.saves if self.state == "open" else self.warmup_saves).append(
            record
        )
        self._sync_point("save", record["step"])

    def before_step(self):
        """The Trainer pulls the next batch, or enters a save."""
        self._end_host_span()
        if self.state == "closed":
            raise WindowOver


class LogTap(logging.Handler):
    """The Trainer's own log record at each loss read-back carries the
    step and the loss; it is emitted right after ``float(loss)`` has
    waited for the device."""

    def __init__(self, window):
        super().__init__()
        self.window = window

    def emit(self, record):
        if str(record.msg).startswith("step %d epoch %d loss"):
            step, _epoch, loss = record.args
            self.window.on_readback(int(step), float(loss))


class RepeatedBatch:
    """One seeded batch, every step (the loss must fall on it)."""

    def __init__(self, batch, window):
        self.batch, self.window = batch, window

    def __iter__(self):
        while True:
            self.window.before_step()
            with self.window.span("data_iterator"):
                batch = self.batch
            yield batch


# ------------------------------------------------------------ the trainer


def seeded_tokens(seed: int, sizes: dict):
    """The one batch of a run, from the seed alone."""
    import numpy as np

    return np.random.RandomState(seed % 2 ** 32).randint(
        0, sizes["vocab_size"], (sizes["batch"], sizes["sequence"] + 1)
    ).astype(np.int32)


def build_trainer(family, sizes, workload, seed, run_dir, train_data):
    """The program's Trainer on the configuration's mesh; it makes the
    weights on the device from the seed in one jitted call. The
    workload's ``training_args`` pass through as they are."""
    from dlrover_tpu.parallel import MeshConfig, Strategy
    from dlrover_tpu.trainer.trainer import Trainer, TrainingArgs

    return Trainer(
        family.loss_fn, family.init, family.logical_axes,
        TrainingArgs(
            output_dir=os.path.join(run_dir, "out"), seed=seed,
            strategy=Strategy(mesh=MeshConfig(**sizes["mesh"])),
            **workload["training_args"],
        ),
        train_data=train_data,
    )


# ------------------------------------------------------------ correctness


def _leaf_names(tree):
    """Dotted leaf names as the checkpoint engine writes them."""
    import jax

    def part(entry):
        for attr in ("key", "idx", "name"):
            if hasattr(entry, attr):
                return str(getattr(entry, attr))
        return str(entry)

    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [(".".join(part(e) for e in path), leaf) for path, leaf in flat]


def agreement_check(trainer, family, rows):
    """(a): the program's forward pass and loss against the plain
    reference, on the Trainer's own initial parameters and the first
    sequence of the batch ``rows``. The program's forward is given as
    many sequences as the mesh has devices (one on one chip), so that a
    sharded batch axis divides; only the first is compared."""
    import jax
    import jax.numpy as jnp

    import reference

    dtype = jnp.dtype(family.model_config.dtype)
    mesh = trainer._accel.mesh
    rows = jnp.asarray(rows[:min(len(rows), mesh.devices.size)], jnp.int32)

    def system(params, rows):
        cast = jax.tree.map(
            lambda x: x.astype(dtype)
            if jnp.issubdtype(x.dtype, jnp.floating) else x, params,
        )
        logits = family.apply(cast, rows[:, :-1])[0]
        # the loss of the first sequence alone, from the program's own
        # loss function (a batch of copies has the loss of one)
        loss = family.loss_fn(
            cast, {"tokens": jnp.broadcast_to(rows[0], rows.shape)},
            jax.random.key(0),
        )
        return logits[-256:], loss

    def plain(params, tokens):
        logits = family.reference_logits(params, tokens[:-1])
        return logits[-256:], reference.next_token_loss(logits, tokens)

    params = trainer.state.params
    with mesh:
        sys_logits, sys_loss = jax.jit(system)(params, rows)
        ref_logits, ref_loss = jax.jit(plain)(params, rows[0])
    report = reference.compare(
        sys_logits, sys_loss, ref_logits, ref_loss, family.tolerances
    )
    del sys_logits, ref_logits
    return report


def save_readback_check(trainer, seed, samples=2):
    """(c): what the save put into shm equals the live state, bit for
    bit, on a seeded sample of leaves. Called between steps, right
    after a save returned, when the state has not moved on."""
    import random

    import numpy as np

    loaded = trainer._engine.load(zero_copy=True)
    if not loaded or loaded.get("step") != trainer.global_step:
        return False
    named = _leaf_names({"train": trainer.state})
    rng = random.Random(seed * 1000003 + trainer.global_step)
    for name, leaf in rng.sample(named, min(samples, len(named))):
        saved = loaded["state"].get(name)
        live = np.asarray(leaf)
        if (saved is None or saved.dtype != live.dtype
                or saved.shape != live.shape
                or not np.array_equal(saved.reshape(-1).view(np.uint8),
                                      live.reshape(-1).view(np.uint8))):
            log("save read-back differs at", name)
            return False
    return True


# ------------------------------------------------------------- the metrics


def per_step_seconds(window, traced: bool):
    """Per-step seconds of the window's sync intervals (none holds a
    save) that, in a traced run, lie outside the profiler's stretch."""
    return [
        i["seconds"] / i["steps"] for i in window.intervals
        if not (traced and i["traced"])
    ]


def step_tokens_per_s(window, tokens_per_step):
    """The rate of the median sync interval of a traced run: of the
    step program alone, whatever stalls the window holds."""
    steps = per_step_seconds(window, True)
    return tokens_per_step / statistics.median(steps) if steps else None


def window_tokens_per_s(window, tokens_per_step):
    """All the window's steps over all its seconds, stalls included."""
    return window.steps * tokens_per_step / window.elapsed \
        if window.elapsed else None


# the quantities a workload file's ``reports`` may give a metric's name
# to: quantity -> (unit, value from (window, tokens per step))
END_TO_END = {
    "window_tokens_per_s": ("tokens/s", window_tokens_per_s),
}


def per_layer(names, record):
    """(values, units) of the per-layer metric files ``names``."""
    import readers

    files = layer_metrics(names)
    return ({n: readers.read(m, record) for n, m in files.items()},
            {n: m["unit"] for n, m in files.items()})


def metrics_object(values, units, on_device):
    """The result line's ``metrics``. No chip, no number: a CPU
    rehearsal shows what it read on standard error and prints none."""
    metrics = {
        name: {"value": value, "unit": units[name]}
        for name, value in values.items() if value is not None
    }
    if on_device:
        return metrics
    log("rehearsal, not printed:", json.dumps(metrics))
    return {}


# ---------------------------------------------------------------- train


def run_train(opts, workload, sizes):
    from dlrover_tpu.common.backend import enable_compile_cache, require_backend

    rehearsal = bool(workload.get("rehearsal"))
    run_dir = prepare_run_dir(opts.workload)
    cache_dir = enable_compile_cache()
    import jax

    backend = require_backend()
    devices = jax.devices()
    if backend == "cpu" and not rehearsal:
        log("no accelerator: only a rehearsal workload runs on the CPU")
        raise SystemExit(EXIT_NO_DEVICE)
    if len(devices) < workload["chips"]:
        log(f"{len(devices)} devices, the cell needs {workload['chips']}")
        raise SystemExit(EXIT_NO_DEVICE)
    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks = json.load(f)["by_device_kind"]
    kind = devices[0].device_kind
    if backend != "cpu" and kind not in peaks:
        raise SystemExit(f"device kind {kind!r} is not in peaks.json")
    log("device", backend, kind, len(devices), "cache", cache_dir)

    import families

    family = families.build(sizes)
    tokens = seeded_tokens(opts.seed, sizes)
    trace_dir = os.path.join(run_dir, "trace") if opts.trace else None
    window = Window(
        opts.seconds, workload["warmup_steps"],
        workload["edge"], trace_dir,
    )
    trainer = build_trainer(
        family, sizes, workload, opts.seed, run_dir,
        RepeatedBatch({"tokens": tokens}, window),
    )
    log("trainer built: weights on the device")
    checks = {}
    save_ok = []
    try:
        agreement = agreement_check(trainer, family, tokens)
        log("agreement with the reference:", json.dumps(agreement))
        checks["reference"] = agreement["ok"]

        plain_save = trainer.save_checkpoint

        def timed_save(persist=False):
            window.before_step()
            with window.span("save_checkpoint"):
                t0 = time.perf_counter()
                ok = plain_save(persist=persist)
                stall = time.perf_counter() - t0
            record = {"stall_s": stall, "ok": bool(ok), "persist": persist,
                      "step": trainer.global_step,
                      **trainer._engine.last_save_stats}
            log("save", json.dumps(record))
            # (c) is the benchmark's own cost and stays outside the
            # window: a save is read back where the window is not open
            # after it, so the one that makes the shm segment and, in
            # a window of save cycles, the last one
            before = window.state == "warmup"
            if ok and before:
                save_ok.append(save_readback_check(trainer, opts.seed))
            window.on_save(record)
            if ok and not before and window.state == "closed":
                save_ok.append(save_readback_check(trainer, opts.seed))
            return ok

        trainer.save_checkpoint = timed_save
        logging.getLogger("dlrover_tpu.trainer.trainer").addHandler(
            LogTap(window)
        )
        try:
            trainer.train()
            log("the data ran out before the window closed")
            checks["window"] = False
        except WindowOver:
            checks["window"] = True
        # ---------------------------------------------- after the window
        memory = [d.memory_stats() or {} for d in devices]
        fullest = max(memory, key=lambda m: m.get("peak_bytes_in_use", 0))
        trace = reduced_trace(trace_dir) if trace_dir else None
        if trace_dir and opts.keep_trace:
            shutil.copytree(trace_dir, opts.keep_trace, dirs_exist_ok=True)
    finally:
        trainer.close()
        from dlrover_tpu.agent.ckpt_saver import AsyncCheckpointSaver

        saver = AsyncCheckpointSaver.get_ckpt_saver()
        if saver is not None:
            saver.stop()
        remove_run_dir(run_dir)

    # ------------------------------------------------------------ result
    losses = window.losses
    finite = bool(losses) and all(math.isfinite(v) for v in losses)
    checks["loss_finite"] = finite
    checks["loss_fell"] = finite and len(losses) > 1 and losses[-1] < losses[0]
    if window.saves or window.warmup_saves:
        checks["save_readback"] = bool(save_ok) and all(save_ok)
    bad_steps = sum(
        i["steps"] for i in window.intervals if not math.isfinite(i["loss"])
    )
    bad_saves = sum(1 for s in window.saves if not s["ok"])
    tokens_per_step = sizes["batch"] * sizes["sequence"]
    log("window:", window.steps, "steps and", len(window.saves), "saves in",
        round(window.elapsed, 4), "s;", len(window.intervals),
        "sync intervals; checks", json.dumps(checks))
    log("per-step s:", json.dumps(
        [round(i["seconds"] / i["steps"], 5) for i in window.intervals]))

    on_device = backend != "cpu"
    if not opts.trace:
        values, units = {}, {}
        for name, quantity in workload["reports"].items():
            units[name], value = END_TO_END[quantity]
            values[name] = value(window, tokens_per_step)
        values["setup_s"] = (window.t_open_wall or time.time()) - T_PROCESS_START
        units["setup_s"] = "s"
    else:
        record = {
            "trace": trace, "traced_steps": window.traced_steps,
            "saves": window.saves, "memory_stats": fullest,
            "step_seconds": per_step_seconds(window, True),
            "tokens_per_s": step_tokens_per_s(window, tokens_per_step)
            if on_device else None,
            "flops_per_token": family.flops_per_token,
            "peak_flops": peaks.get(kind, {}).get("bf16_flops"),
            "chips": workload["chips"],
        }
        values, units = per_layer(workload["per_layer"], record)
    metrics = metrics_object(values, units, on_device)
    device = {
        "platform": devices[0].platform, "kind": kind, "count": len(devices),
        "memory_peak_bytes": fullest.get("peak_bytes_in_use", 0),
    }
    result = {
        "correct": all(checks.values()),
        "attempted": window.steps + len(window.saves),
        "failed": bad_steps + bad_saves, "metrics": metrics, "device": device,
        "checks": checks,
    }
    if trace:
        device["busy_s"], device["window_s"] = trace["busy_s"], trace["window_s"]
        result["breakdown"] = {
            "device_ops": trace["device_ops"], "idle_gaps": trace["idle_gaps"],
        }
    return result


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument(
        "--keep-trace", default="",
        help="copy the raw .xplane.pb of a --trace 1 run into this directory",
    )
    opts = parser.parse_args(argv)
    # the program is the checkout this file sits in
    sys.path.insert(0, ROOT)
    workload = load("workloads", opts.workload)
    sizes = load("configs", workload["config"])
    result = run_train(opts, workload, sizes)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
