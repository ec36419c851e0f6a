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
"""

from __future__ import annotations

import os

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


def require_backend() -> str:
    """``jax.default_backend()``, refusing the silent CPU fallback.
    The pin is read from JAX's own config: it starts from
    ``JAX_PLATFORMS`` and also sees ``jax.config.update``."""
    import jax

    backend = jax.default_backend()
    if backend == "cpu" and not _is_cpu_pin(jax.config.jax_platforms):
        raise RuntimeError(NO_ACCELERATOR)
    return backend


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
    import jax

    compile_cache_env(os.environ)
    cache_dir = os.environ[CACHE_DIR_ENV]
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir
