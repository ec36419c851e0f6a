"""Persistent XLA compilation cache across worker restarts.

SURVEY hard-parts list: elastic membership changes restart workers with
a new mesh; the recompile must be (mostly) a cache hit or it eats the
goodput the flash checkpoint bought. Reference analogue: the restarted
torch workers reuse NCCL/torch caches; the TPU equivalent is the JAX
persistent compilation cache, whose directory ONE helper
(common/backend.compile_cache_env) decides for every entry point.
"""

import os
import subprocess
import sys

import pytest

from dlrover_tpu.common import backend
from dlrover_tpu.common.backend import compile_cache_env


class TestCacheEnv:
    def test_dir_from_outside_is_used_and_nothing_else(self, tmp_path):
        outside = str(tmp_path / "cc")
        env = compile_cache_env({"JAX_COMPILATION_CACHE_DIR": outside})
        assert env["JAX_COMPILATION_CACHE_DIR"] == outside
        assert env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] == "0.0"
        assert env["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] == "-1"
        assert (tmp_path / "cc").is_dir()

    def test_unset_is_one_fixed_ignored_path_in_the_checkout(
        self, tmp_path, monkeypatch
    ):
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert backend.DEFAULT_CACHE_DIR == os.path.join(repo, ".jax_cache")
        with open(os.path.join(repo, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()
        # (the default redirected: the test must not create the real one)
        monkeypatch.setattr(
            backend, "DEFAULT_CACHE_DIR", str(tmp_path / "fixed")
        )
        env = compile_cache_env({})
        assert env["JAX_COMPILATION_CACHE_DIR"] == str(tmp_path / "fixed")
        assert (tmp_path / "fixed").is_dir()

    def test_user_thresholds_win(self, tmp_path):
        env = compile_cache_env({
            "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cc"),
            "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "2.5",
        })
        assert env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] == "2.5"

    def test_launcher_has_no_cache_knob_of_its_own(self):
        """The directory comes from JAX_COMPILATION_CACHE_DIR or the
        fixed path — tpu-run carries no flag or env name of its own."""
        from dlrover_tpu.agent.training_agent import ElasticLaunchConfig
        from dlrover_tpu.trainer.run import parse_args

        assert not hasattr(ElasticLaunchConfig(), "compilation_cache_dir")
        with pytest.raises(SystemExit):
            parse_args(["--compilation-cache-dir", "/x", "train.py"])


_COMPILE_SCRIPT = r"""
import time
import jax
import jax.numpy as jnp
from jax._src import monitoring

# counter-based proof of cache behavior: the persistent compilation
# cache records these monitoring events on every lookup
_events = {"hits": 0, "misses": 0}

def _on_event(name, **kw):
    if name == "/jax/compilation_cache/cache_hits":
        _events["hits"] += 1
    elif name == "/jax/compilation_cache/cache_misses":
        _events["misses"] += 1

monitoring.register_event_listener(_on_event)

def layer(h, w):
    a = jnp.tanh(h @ w) + h * jax.nn.sigmoid(h @ w.T).mean()
    b = jax.nn.softmax(a @ w, axis=-1) @ h
    c = jnp.where(b > 0, jnp.log1p(jnp.abs(b)), jnp.expm1(b))
    return a + 0.1 * c, None

def step(params, x):
    h, _ = jax.lax.scan(layer, x, params)
    g = jax.grad(lambda p: jax.lax.scan(layer, x, p)[0].sum())(params)
    h2, _ = jax.lax.scan(layer, h.T, params)
    return h.sum() + h2.mean() + sum(
        jnp.sum(v) for v in jax.tree.leaves(g)
    )

params = jnp.ones((8, 256, 256))
x = jnp.ones((256, 256))
t0 = time.perf_counter()
compiled = jax.jit(step).lower(params, x).compile()
print(f"COMPILE_S={time.perf_counter() - t0:.4f}")
print(f"CACHE_HITS={_events['hits']}")
print(f"CACHE_MISSES={_events['misses']}")
"""


class TestRestartRecompileFromCache:
    def test_second_compile_hits_cache(self, tmp_path):
        """Two fresh processes (a simulated worker restart): the second
        must replay the persistent cache.  Asserted on jax's own
        cache-hit/miss monitoring counters plus the cache dir contents
        — a wall-clock ratio here was one of the seed suite's flaky
        assertions (neighbor load on a shared VM dilates the cold/warm
        times independently), so time is only printed, never gated."""
        cache = str(tmp_path / "cc")
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
        env["JAX_COMPILATION_CACHE_DIR"] = cache
        compile_cache_env(env)

        def run_once():
            out = subprocess.run(
                [sys.executable, "-c", _COMPILE_SCRIPT],
                env=env, capture_output=True, text=True, timeout=300,
            )
            assert out.returncode == 0, out.stderr[-2000:]
            stats = {}
            for line in out.stdout.splitlines():
                key, _, value = line.partition("=")
                # only the script's own keys: incidental runtime
                # output containing '=' must not crash the parse
                if key in ("COMPILE_S", "CACHE_HITS", "CACHE_MISSES"):
                    stats[key] = float(value)
            assert "COMPILE_S" in stats, f"no timing: {out.stdout}"
            return stats

        cold = run_once()
        entries = set(os.listdir(cache))
        assert entries, "cache dir empty after first compile"
        assert cold["CACHE_HITS"] == 0
        assert cold["CACHE_MISSES"] >= 1, (
            "cold run never consulted the persistent cache — the env "
            "wiring is broken"
        )
        warm = run_once()
        print(
            f"compile: cold={cold['COMPILE_S']:.3f}s "
            f"warm={warm['COMPILE_S']:.3f}s (informational)"
        )
        assert warm["CACHE_HITS"] >= 1, (
            "second process never hit the persistent cache"
        )
        assert warm["CACHE_MISSES"] == 0, (
            "second process missed the cache and recompiled"
        )
        assert set(os.listdir(cache)) == entries, (
            "second run recompiled (new cache entries) instead of "
            "hitting the cache"
        )


# -------------------------------------------------------------------------
# the runtime's own report of its compiles (jax.monitoring, heard in
# common/backend): counters, start.compile's labels, compile.late
# -------------------------------------------------------------------------


class _Batches:
    """``steps`` batches of 4 rows; from step ``wider_from`` on, of 8."""

    def __init__(self, steps, wider_from=None):
        self.steps, self.wider_from = steps, wider_from

    def __iter__(self):
        import numpy as np

        for step in range(1, self.steps + 1):
            rows = 8 if self.wider_from and step >= self.wider_from else 4
            yield np.ones((rows, 4), np.float32)


@pytest.fixture
def start_legs():
    """A process's start-up legs, armed as at its import (the test
    runner dropped its own: conftest), and a fresh registry."""
    from dlrover_tpu.common import telemetry, tracing

    prev_registry = telemetry.active_registry()
    registry = telemetry.enable("compile-report")
    legs = tracing.Legs(
        "launch", order=tracing.START_LEGS, filler=tracing.START_FILLER
    )
    legs.advance("start.imports")
    prev_legs = tracing.reset_startup(legs)
    yield registry
    tracing.reset_startup(prev_legs)
    telemetry._REGISTRY = prev_registry


def _train(tmp_path, steps, wider_from=None):
    import jax.numpy as jnp

    from dlrover_tpu.trainer.trainer import Trainer, TrainingArgs

    backend.require_backend()
    trainer = Trainer(
        lambda params, batch, rng: jnp.mean((batch @ params["w"]) ** 2),
        lambda rng: {"w": jnp.ones((4, 1))},
        {"w": (None, None)},
        TrainingArgs(
            output_dir=str(tmp_path / "out"), max_steps=steps,
            flash_checkpoint=False, log_steps=0,
        ),
        train_data=_Batches(steps, wider_from),
    )
    trainer.train()
    trainer.close()


class TestCompileReport:
    def test_a_late_compile_names_its_step(self, tmp_path, start_legs):
        _train(tmp_path, steps=6, wider_from=4)
        events = start_legs.snapshot()["events"]
        late = [e for e in events if e["kind"] == "compile.late"]
        # the second batch shape recompiles the step program at step 4
        # (and whatever small program the wider batch brings with it)
        assert late and {e["step"] for e in late} == {4}
        step = [e for e in late if e["program"] == "jit(train_step)"]
        assert len(step) == 1 and step[0]["dur"] > 0
        # the incarnation's first step keeps its own kind, once
        assert [e["step"] for e in events if e["kind"] == "compile"] == [1]

    def test_a_run_of_one_shape_leaves_none(self, tmp_path, start_legs):
        _train(tmp_path, steps=6)
        snap = start_legs.snapshot()
        assert not [e for e in snap["events"] if e["kind"] == "compile.late"]
        spans = {e["name"]: e for e in snap["events"] if e["kind"] == "span"}
        # the legs closed at the first completed step, compile before
        # first_step, with what the counters gained as labels
        compile_leg, first = spans["start.compile"], spans["start.first_step"]
        assert compile_leg["program"] == "jit(train_step)"
        assert compile_leg["hit"] in (0, 1)
        assert compile_leg["backend_s"] > 0 and compile_leg["trace_s"] > 0
        assert first["t"] - first["dur"] == pytest.approx(compile_leg["t"])
        assert spans["launch"]["t"] == pytest.approx(first["t"])
        counters = {c["name"]: c["value"] for c in snap["counters"]}
        assert counters["compile.backend_s"] >= compile_leg["backend_s"]
        assert counters["compile.trace_s"] > 0
        assert counters["compile.lower_s"] > 0
        assert counters["start.compile_s"] == pytest.approx(
            compile_leg["dur"]
        )

    def test_no_legs_no_late_events(self, tmp_path):
        """A process that is no launch (the test runner): a compile
        after a first step is nobody's ``compile.late``."""
        from dlrover_tpu.common import telemetry, tracing

        assert tracing.startup() is None
        prev = telemetry.active_registry()
        registry = telemetry.enable("no-legs")
        try:
            _train(tmp_path, steps=5, wider_from=3)
        finally:
            telemetry._REGISTRY = prev
        kinds = {e["kind"] for e in registry.snapshot()["events"]}
        assert "compile.late" not in kinds and "compile" in kinds
        assert not [e for e in registry.snapshot()["events"]
                    if e.get("name", "").startswith("start.")]
