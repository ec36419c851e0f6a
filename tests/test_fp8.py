"""fp8 training path: e4m3/e5m2 quantized matmuls with per-tensor
scaling (reference Fp8Optimization analogue,
atorch/auto/opt_lib/amp_optimization.py:197).
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest



from dlrover_tpu.models import llama_init, llama_loss_fn
from dlrover_tpu.models.llama import LlamaConfig, llama_logical_axes
from dlrover_tpu.ops.fp8 import (
    Fp8History,
    fp8_autocast,
    fp8_dot,
    fp8_dot_delayed,
    qdot,
    quantize_e4m3,
    quantize_e5m2,
)
from dlrover_tpu.parallel import MeshConfig, Strategy, auto_accelerate


@pytest.fixture(autouse=True)
def _reset_mesh():
    yield
    import dlrover_tpu.parallel.mesh as mesh_mod

    mesh_mod._global_mesh = None


class TestQuantize:
    def test_e4m3_dtype_and_roundtrip(self):
        x = jnp.asarray(np.random.RandomState(0).randn(64, 64), jnp.float32)
        q, scale = quantize_e4m3(x)
        assert q.dtype == jnp.float8_e4m3fn
        back = q.astype(jnp.float32) * scale
        err = np.abs(np.asarray(back - x)) / (np.abs(np.asarray(x)) + 1e-3)
        assert err.mean() < 0.05

    def test_e5m2_dtype(self):
        x = jnp.ones((8, 8)) * 3.0
        q, _ = quantize_e5m2(x)
        assert q.dtype == jnp.float8_e5m2

    def test_scale_tracks_amax(self):
        x = jnp.full((4,), 896.0)  # 2x e4m3 max
        q, scale = quantize_e4m3(x)
        np.testing.assert_allclose(float(scale), 2.0, rtol=1e-6)
        np.testing.assert_allclose(
            np.asarray(q.astype(jnp.float32)) * float(scale), 896.0
        )


class TestFp8Dot:
    def test_close_to_exact(self):
        rs = np.random.RandomState(0)
        a = jnp.asarray(rs.randn(32, 64), jnp.float32)
        b = jnp.asarray(rs.randn(64, 16), jnp.float32)
        got = fp8_dot(a, b)
        want = a @ b
        err = np.linalg.norm(np.asarray(got - want)) / np.linalg.norm(
            np.asarray(want)
        )
        assert err < 0.05, err

    def test_grads_flow_and_match_roughly(self):
        rs = np.random.RandomState(1)
        a = jnp.asarray(rs.randn(16, 32), jnp.float32)
        b = jnp.asarray(rs.randn(32, 8), jnp.float32)

        g8 = jax.grad(lambda a, b: fp8_dot(a, b).sum(), argnums=(0, 1))(
            a, b
        )
        gx = jax.grad(lambda a, b: (a @ b).sum(), argnums=(0, 1))(a, b)
        for got, want in zip(g8, gx):
            err = np.linalg.norm(np.asarray(got - want)) / (
                np.linalg.norm(np.asarray(want)) + 1e-9
            )
            assert err < 0.08, err

    def test_batched_lhs(self):
        rs = np.random.RandomState(2)
        a = jnp.asarray(rs.randn(4, 16, 32), jnp.float32)
        b = jnp.asarray(rs.randn(32, 8), jnp.float32)
        got = fp8_dot(a, b)
        assert got.shape == (4, 16, 8)
        gb = jax.grad(lambda b: fp8_dot(a, b).sum())(b)
        assert gb.shape == b.shape
        want = jax.grad(lambda b: (a @ b).sum())(b)
        err = np.linalg.norm(np.asarray(gb - want)) / np.linalg.norm(
            np.asarray(want)
        )
        assert err < 0.08


class TestQdot:
    def test_passthrough_without_autocast(self):
        a = jnp.ones((4, 8))
        b = jnp.ones((8, 2))
        np.testing.assert_array_equal(np.asarray(qdot(a, b)),
                                      np.asarray(a @ b))

    def test_quantizes_under_autocast(self):
        # random operands: e4m3 rounding must perturb the result
        rs = np.random.RandomState(0)
        a = jnp.asarray(rs.randn(16, 32), jnp.float32)
        b = jnp.asarray(rs.randn(32, 8), jnp.float32)
        with fp8_autocast():
            q = qdot(a, b)
        assert not np.array_equal(np.asarray(q), np.asarray(a @ b))
        # and close (the rounding is bounded)
        err = np.linalg.norm(np.asarray(q - a @ b)) / np.linalg.norm(
            np.asarray(a @ b)
        )
        assert err < 0.05


class TestDelayedScaling:
    def test_history_window(self):
        h = Fp8History.create(window=4)
        h = h.update(jnp.full((2,), 100.0))
        h = h.update(jnp.full((2,), 50.0))
        np.testing.assert_allclose(float(h.scale()), 100.0 / 448.0)

    def test_delayed_dot_converges_to_current(self):
        rs = np.random.RandomState(3)
        a = jnp.asarray(rs.randn(16, 16), jnp.float32)
        b = jnp.asarray(rs.randn(16, 16), jnp.float32)
        ah, bh = Fp8History.create(), Fp8History.create()
        # first call uses the default scale; by the second the history
        # holds the real amaxes
        _, ah, bh = fp8_dot_delayed(a, b, ah, bh)
        out, ah, bh = fp8_dot_delayed(a, b, ah, bh)
        want = a @ b
        err = np.linalg.norm(np.asarray(out - want)) / np.linalg.norm(
            np.asarray(want)
        )
        assert err < 0.05


class TestEndToEndNumerics:
    def _run(self, dtype, steps=12, mesh=None, lr=5e-3, **config_kw):
        cfg = dict(
            vocab_size=64, dim=32, n_layers=2, n_heads=4, n_kv_heads=2,
            mlp_dim=64, max_seq_len=32, attn_impl="reference",
            remat=False, dtype="float32",
        )
        cfg.update(config_kw)
        config = LlamaConfig(**cfg)
        strategy = Strategy(
            mesh=mesh or MeshConfig(data=2, fsdp=4),
            compute_dtype=dtype, remat="none",
        )
        res = auto_accelerate(
            loss_fn=llama_loss_fn(config),
            init_fn=lambda rng: llama_init(config, rng),
            optimizer=optax.adamw(lr),
            param_logical_axes=llama_logical_axes(config),
            strategy=strategy,
        )
        batch = {
            "tokens": jax.random.randint(jax.random.key(1), (8, 33), 0, 64)
        }
        state = res.state
        losses = []
        for i in range(steps):
            state, m = res.train_step(state, batch, jax.random.key(i))
            losses.append(float(m["loss"]))
        return losses

    def test_fp8_composes_with_1f1b_pipeline(self):
        """compute_dtype='fp8' and pipe_schedule='1f1b' together: the
        autocast flag is up while the fused schedule traces, so the
        stage matmuls quantize inside the pipeline's custom VJP."""
        losses = self._run(
            "fp8", steps=8, mesh=MeshConfig(pipe=2, fsdp=4),
            n_layers=4, pipe_microbatches=4, pipe_schedule="1f1b",
        )
        assert np.isfinite(losses[-1])
        assert losses[-1] < losses[0], losses

    def test_fp8_tracks_bf16(self):
        """Strategy.compute_dtype='fp8' must train: loss decreases and
        stays within a few percent of the bf16 run on the same data."""
        l8 = self._run("fp8")
        l16 = self._run("bfloat16")
        assert l8[-1] < l8[0] * 0.9, f"fp8 loss did not drop: {l8}"
        assert abs(l8[-1] - l16[-1]) / l16[-1] < 0.05, (l8[-1], l16[-1])


class TestInt8Dot:
    def test_close_to_exact(self):
        from dlrover_tpu.ops.quantization import int8_dot

        rng = np.random.RandomState(0)
        a = jnp.asarray(rng.randn(64, 128), jnp.float32)
        b = jnp.asarray(rng.randn(128, 96), jnp.float32)
        out = int8_dot(a, b)
        ref = a @ b
        err = float(jnp.max(jnp.abs(out - ref))) / float(
            jnp.max(jnp.abs(ref)))
        assert err < 0.03, err

    def test_grads_are_full_precision(self):
        from dlrover_tpu.ops.quantization import int8_dot

        rng = np.random.RandomState(1)
        a = jnp.asarray(rng.randn(32, 64), jnp.float32)
        b = jnp.asarray(rng.randn(64, 16), jnp.float32)
        g = jax.grad(lambda a, b: jnp.sum(int8_dot(a, b) ** 2), (0, 1))(
            a, b)
        gr = jax.grad(lambda a, b: jnp.sum((a @ b) ** 2), (0, 1))(a, b)
        for x, y in zip(g, gr):
            rel = float(jnp.max(jnp.abs(x - y))) / (
                float(jnp.max(jnp.abs(y))) + 1e-6)
            assert rel < 0.1, rel

    def test_qdot_routes_int8_under_autocast(self):
        from dlrover_tpu.ops.fp8 import qdot, quant_autocast

        rng = np.random.RandomState(2)
        a = jnp.asarray(rng.randn(16, 32), jnp.bfloat16)
        b = jnp.asarray(rng.randn(32, 8), jnp.bfloat16)
        plain = qdot(a, b)
        with quant_autocast("int8"):
            q = qdot(a, b)
        # int8 rounding must change the result (proof the path engaged)
        assert not np.allclose(np.asarray(plain, np.float32),
                               np.asarray(q, np.float32), atol=0)
        rel = float(jnp.max(jnp.abs(
            q.astype(jnp.float32) - plain.astype(jnp.float32))))
        assert rel < 1.0

    def test_int8_tracks_bf16_training(self):
        """Strategy.compute_dtype='int8' loss parity vs bf16 (an earlier
        review: the low-precision knob must not distort training)."""
        helper = TestEndToEndNumerics()
        l8 = helper._run("int8")
        l16 = helper._run("bfloat16")
        assert l8[-1] < l8[0] * 0.9, f"int8 loss did not drop: {l8}"
        assert abs(l8[-1] - l16[-1]) / l16[-1] < 0.05, (l8[-1], l16[-1])


class TestInt8Einsum:
    """int8 quantized einsum — the einsum-form projection path
    (quantization.py int8_einsum; routed by fp8.py qeinsum)."""

    def test_matches_quantized_ground_truth(self):
        from dlrover_tpu.ops.quantization import _per_channel_q, int8_einsum

        rng = np.random.RandomState(0)
        a = jnp.asarray(rng.randn(2, 16, 32), jnp.float32)
        b = jnp.asarray(rng.randn(32, 4, 8), jnp.float32)
        out = np.asarray(int8_einsum("bsd,dhk->bhsk", a, b), np.float64)
        qa, sa = _per_channel_q(a, axis=(2,))
        qb, sb = _per_channel_q(b, axis=(0,))
        # float64 ground truth: the int32-accumulated kernel is MORE
        # exact than an f32 einsum of the dequantized operands
        truth = np.einsum(
            "bsd,dhk->bhsk",
            np.asarray(qa, np.float64) * np.asarray(sa, np.float64),
            np.asarray(qb, np.float64) * np.asarray(sb, np.float64),
        )
        assert np.max(np.abs(out - truth)) < 1e-5

    def test_close_to_exact_and_grads(self):
        from dlrover_tpu.ops.quantization import int8_einsum

        rng = np.random.RandomState(1)
        a = jnp.asarray(rng.randn(2, 16, 32), jnp.float32)
        b = jnp.asarray(rng.randn(32, 4, 8), jnp.float32)
        out = np.asarray(int8_einsum("bsd,dhk->bhsk", a, b), np.float64)
        exact = np.einsum("bsd,dhk->bhsk", np.asarray(a, np.float64),
                          np.asarray(b, np.float64))
        rel = np.max(np.abs(out - exact)) / np.max(np.abs(exact))
        assert rel < 0.1, rel
        # AQT straight-through grads: einsum grads of the DEQUANTIZED
        # operands — close to the unquantized grads at quantization
        # error scale
        g_q = jax.grad(
            lambda a, b: jnp.sum(int8_einsum("bsd,dhk->bhsk", a, b)),
            (0, 1))(a, b)
        g_e = jax.grad(
            lambda a, b: jnp.sum(jnp.einsum("bsd,dhk->bhsk", a, b)),
            (0, 1))(a, b)
        for gq, ge in zip(g_q, g_e):
            rel = float(jnp.max(jnp.abs(gq - ge))) / (
                float(jnp.max(jnp.abs(ge))) + 1e-6)
            assert rel < 0.05, rel

    def test_wo_and_gpt2_specs(self):
        from dlrover_tpu.ops.quantization import int8_einsum

        rng = np.random.RandomState(2)
        o2 = int8_einsum(
            "bhsk,hkd->bsd",
            jnp.asarray(rng.randn(2, 4, 16, 8), jnp.float32),
            jnp.asarray(rng.randn(4, 8, 32), jnp.float32))
        assert o2.shape == (2, 16, 32)
        o3 = int8_einsum(
            "bsd,dthk->tbhsk",
            jnp.asarray(rng.randn(2, 16, 32), jnp.float32),
            jnp.asarray(rng.randn(32, 3, 4, 8), jnp.float32))
        assert o3.shape == (3, 2, 4, 16, 8)

    def test_rejects_non_matmul_specs(self):
        from dlrover_tpu.ops.quantization import int8_einsum

        a = jnp.zeros((2, 16, 32))
        b = jnp.zeros((32, 4, 8))
        for bad in ("bsd,dhk->bhs",      # b's h/k dims half-dropped
                    "bsd,shk->bhk",      # s summed within one operand
                    "bsd,dhk"):          # implicit output
            with pytest.raises(ValueError):
                int8_einsum(bad, a, b)

    def test_qeinsum_routes_by_mode(self):
        from dlrover_tpu.ops.fp8 import qeinsum, quant_autocast

        rng = np.random.RandomState(3)
        a = jnp.asarray(rng.randn(2, 8, 32), jnp.bfloat16)
        b = jnp.asarray(rng.randn(32, 2, 16), jnp.bfloat16)
        plain = qeinsum("bsd,dhk->bhsk", a, b)
        with quant_autocast("int8"):
            q = qeinsum("bsd,dhk->bhsk", a, b)
        assert q.shape == plain.shape
        assert not np.allclose(np.asarray(plain, np.float32),
                               np.asarray(q, np.float32), atol=0)

    def test_flash_einsum_path_stays_active_under_int8(self):
        from dlrover_tpu.models.llama import flash_einsum_path
        from dlrover_tpu.ops.fp8 import quant_autocast

        cfg = LlamaConfig(
            vocab_size=64, dim=64, n_layers=1, n_heads=2, n_kv_heads=2,
            mlp_dim=64, attn_impl="flash")
        assert flash_einsum_path(cfg)
        with quant_autocast("int8"):
            assert flash_einsum_path(cfg), \
                "int8 must keep the einsum-form flash path"
        with quant_autocast("fp8"):
            assert not flash_einsum_path(cfg), \
                "emulated fp8 must yield to the qdot branch"
