"""Which JAX backend a process runs on, and where it caches compiles.

One decision, made here for every entry point (tpu-run workers, the
decode engine, the probe payloads, the benchmark, chip_smoke):

- JAX drops to the CPU with a warning when it cannot take the chip.
  Nothing in this package may ride that fallback: a process runs on the
  CPU only when its caller pinned it there (``JAX_PLATFORMS=cpu`` —
  tests, rehearsals, the CPU harnesses), and Pallas interpret mode
  exists only on that pinned CPU. Anything else that finds no
  accelerator fails at start-up with one message.
- A chip belongs to one process at a time, so the agent and the master
  never import JAX. :func:`cpu_pinned` answers from the environment
  alone for code that must stay off JAX.
- The persistent compilation cache lives where
  ``JAX_COMPILATION_CACHE_DIR`` says; unset, at one fixed git-ignored
  path inside the checkout (the path is part of the cache key, so it
  must not move between the processes of a run).
- What the runtime says of its own compiles is heard here, once:
  ``jax.monitoring`` listeners registered where JAX is first brought
  up feed the ``compile.*`` counters, the labels of the ``start.compile``
  leg and the ``compile.late`` event (``common/tracing``'s start-up
  legs; none of it is a line on the step path).
"""

from __future__ import annotations

import os
import time
import weakref

from dlrover_tpu.common import telemetry, tracing

NO_ACCELERATOR = (
    "no accelerator: JAX fell back to the CPU backend. Free the chip "
    "(one process per chip) or set JAX_PLATFORMS=cpu to run on the CPU "
    "on purpose."
)

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
_REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
DEFAULT_CACHE_DIR = os.path.join(_REPO_ROOT, ".jax_cache")


def _is_cpu_pin(platforms) -> bool:
    return (platforms or "").strip().lower() == "cpu"


def cpu_pinned() -> bool:
    """The caller asked for the CPU backend. Reads the environment
    only — safe in processes that must never import JAX."""
    return _is_cpu_pin(os.environ.get("JAX_PLATFORMS"))


class _Compiles:
    """What ``jax.monitoring`` has reported of this process's compiles:
    the legs' seconds and the persistent cache's verdicts, summed (the
    ``compile.*`` counters carry the same), and of the newest backend
    compile its end, its program and its verdict."""

    DURATIONS = {
        "/jax/core/compile/jaxpr_trace_duration": "trace_s",
        "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
        "/jax/core/compile/backend_compile_duration": "backend_s",
    }
    EVENTS = {
        "/jax/compilation_cache/cache_hits": "cache_hits",
        "/jax/compilation_cache/cache_misses": "cache_misses",
    }

    def __init__(self):
        self.registered = False
        self.totals = dict.fromkeys(
            (*self.DURATIONS.values(), *self.EVENTS.values()), 0.0
        )
        self.mark = dict(self.totals)  # at the start.compile leg's begin
        self.last_end_t = 0.0
        self.last_program = ""
        self.last_hit = 0
        self.trainer = None  # weakref: whose step a late compile is in

    def on_event(self, event, **_kw):
        key = self.EVENTS.get(event)
        if key is not None:
            self.totals[key] += 1
            self.last_hit = int(key == "cache_hits")
            telemetry.counter_inc("compile." + key)

    def on_duration(self, event, seconds, **kw):
        key = self.DURATIONS.get(event)
        if key is None:
            return
        self.totals[key] += seconds
        telemetry.counter_inc("compile." + key, seconds)
        if key != "backend_s":
            return
        self.last_end_t = time.time()
        self.last_program = str(kw.get("fun_name", ""))
        legs = tracing.startup()
        if legs is not None and legs.closed:
            # a compile after the first completed step: the step it
            # held up would otherwise read as one long ``step.end``
            trainer = self.trainer() if self.trainer else None
            telemetry.event(
                "compile.late", dur=seconds, program=self.last_program,
                hit=self.last_hit,
                step=trainer.global_step + 1 if trainer else -1,
            )


_compiles = _Compiles()


def _jax():
    """``import jax``. The first call is where ``start.imports`` ends
    and ``start.backend`` begins, and registers the compile listeners
    (with telemetry off: neither)."""
    import jax

    if not _compiles.registered and telemetry.active_registry() is not None:
        _compiles.registered = True
        tracing.start_advance("start.backend")
        jax.monitoring.register_event_listener(_compiles.on_event)
        jax.monitoring.register_event_duration_secs_listener(
            _compiles.on_duration
        )
    return jax


def require_backend() -> str:
    """``jax.default_backend()``, refusing the silent CPU fallback.
    The pin is read from JAX's own config: it starts from
    ``JAX_PLATFORMS`` and also sees ``jax.config.update``. The first
    return is where JAX has named the devices: ``start.backend`` ends."""
    jax = _jax()
    backend = jax.default_backend()
    if backend == "cpu" and not _is_cpu_pin(jax.config.jax_platforms):
        raise RuntimeError(NO_ACCELERATOR)
    tracing.start_end("start.backend")
    return backend


def begin_compile_leg(trainer):
    """``Trainer.train`` is about to enter its loop: ``start.compile``
    begins (the first batch's pull, then trace, lowering and backend
    compile or cache load of the step program), and a compile after
    the first completed step is booked to ``trainer``'s step."""
    _compiles.trainer = weakref.ref(trainer)
    if tracing.start_advance("start.compile"):
        _compiles.mark = dict(_compiles.totals)


def first_step_done():
    """The loop has seen its first step complete: ``start.compile``
    ends in retrospect where the newest backend compile ended (labels:
    what the compile counters gained during it; ``hit`` and ``program``
    of that newest compile, the step program's), ``start.first_step``
    runs from there to now, and the start-up legs close."""
    legs = tracing.startup()
    if legs is None or legs.closed:
        return
    if legs.open_name == "start.compile":
        legs.annotate(
            hit=_compiles.last_hit, program=_compiles.last_program,
            **{k: v - _compiles.mark[k] for k, v in _compiles.totals.items()},
        )
        legs.advance("start.first_step", t=_compiles.last_end_t or None)
    tracing.start_done()


def use_interpret() -> bool:
    """Pallas interpret mode: only on a CPU the caller pinned."""
    return require_backend() == "cpu"


def compile_cache_env(env: dict) -> dict:
    """Point a child's environment at the persistent compilation cache.

    ``JAX_COMPILATION_CACHE_DIR`` from outside wins and nothing here
    sets another. The thresholds drop to "cache everything" so a
    restarted worker replays every program instead of recompiling (the
    recompile after a restart is the goodput sink the cache removes)."""
    cache_dir = env.get(CACHE_DIR_ENV) or DEFAULT_CACHE_DIR
    os.makedirs(cache_dir, exist_ok=True)
    env[CACHE_DIR_ENV] = cache_dir
    env.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.0")
    env.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
    return env


def enable_compile_cache() -> str:
    """Switch this process's persistent cache on (entry points that
    were not spawned with :func:`compile_cache_env`'s environment: the
    decode worker, ``benchmark/run.py``). Call before the first
    compile."""
    jax = _jax()
    compile_cache_env(os.environ)
    cache_dir = os.environ[CACHE_DIR_ENV]
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir
