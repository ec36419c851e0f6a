"""Pipeline parallelism: GPipe schedule over the ``pipe`` mesh axis.

Checks numerical equivalence with the plain layer scan, gradient flow
through the ppermute schedule, and composition with fsdp/tensor axes —
all on the 8-device virtual CPU mesh.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dlrover_tpu.models import PRESETS, llama_init, llama_loss_fn
from dlrover_tpu.models.llama import (
    LlamaConfig,
    llama_apply,
    llama_logical_axes,
)
from dlrover_tpu.parallel import (
    MeshConfig,
    Strategy,
    auto_accelerate,
    build_mesh,
    set_mesh,
)
from dlrover_tpu.parallel.mesh import _global_mesh  # noqa: F401
from dlrover_tpu.parallel.pipeline import (
    pipeline_apply,
    pipeline_loss_1f1b,
    stage_layer_scan,
)



@pytest.fixture(autouse=True)
def _reset_mesh():
    yield
    import dlrover_tpu.parallel.mesh as mesh_mod

    mesh_mod._global_mesh = None


def _elementwise_stage():
    """stage_fn over stacked [L, D] scale params: h -> h * scale + 1."""

    def layer_fn(h, scale):
        return h * scale + 1.0, jnp.zeros((), jnp.float32)

    return stage_layer_scan(layer_fn, remat=False)


def test_pipeline_matches_scan():
    mesh = build_mesh(MeshConfig(pipe=4, data=2))
    set_mesh(mesh)
    L, B, D = 8, 8, 16
    scales = jnp.linspace(0.5, 1.5, L * D).reshape(L, D)
    x = jnp.arange(B * D, dtype=jnp.float32).reshape(B, D) / (B * D)

    stage_fn = _elementwise_stage()
    with mesh:
        out, aux = jax.jit(
            lambda s, x: pipeline_apply(stage_fn, s, x, n_microbatches=4)
        )(scales, x)

    expected = np.asarray(x)
    for l in range(L):
        expected = expected * np.asarray(scales[l]) + 1.0
    np.testing.assert_allclose(np.asarray(out), expected, rtol=1e-5)
    assert float(aux) == 0.0


def test_pipeline_grad_flows():
    mesh = build_mesh(MeshConfig(pipe=2, data=4))
    set_mesh(mesh)
    L, B, D = 4, 4, 8
    scales = jnp.ones((L, D))
    x = jnp.ones((B, D))
    stage_fn = _elementwise_stage()

    def loss(s):
        out, _ = pipeline_apply(stage_fn, s, x, n_microbatches=2)
        return jnp.sum(out**2)

    def loss_ref(s):
        h = x
        for l in range(L):
            h = h * s[l] + 1.0
        return jnp.sum(h**2)

    with mesh:
        g = jax.jit(jax.grad(loss))(scales)
    g_ref = jax.grad(loss_ref)(scales)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), rtol=1e-4)


def test_llama_pipeline_forward_matches_dense():
    config = LlamaConfig(
        vocab_size=64, dim=32, n_layers=4, n_heads=4, n_kv_heads=2,
        mlp_dim=64, max_seq_len=32, attn_impl="reference", remat=False,
        dtype="float32", pipe_microbatches=4,
    )
    params = llama_init(config, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (8, 16), 0, 64)

    # reference: no mesh
    import dlrover_tpu.parallel.mesh as mesh_mod

    mesh_mod._global_mesh = None
    ref_logits = llama_apply(config, params, tokens)

    mesh = build_mesh(MeshConfig(pipe=2, data=2, fsdp=2))
    set_mesh(mesh)
    with mesh:
        pp_logits = jax.jit(
            lambda p, t: llama_apply(config, p, t)
        )(params, tokens)
    np.testing.assert_allclose(
        np.asarray(pp_logits), np.asarray(ref_logits), atol=2e-4
    )


def test_pipeline_bf16_grad():
    """bf16 boundary arrays crash XLA:CPU without the f32-boundary cast
    in pipeline_apply; this locks the workaround in."""
    mesh = build_mesh(MeshConfig(pipe=2, fsdp=4))
    set_mesh(mesh)
    L, B, D = 4, 8, 16
    scales = jnp.ones((L, D), jnp.bfloat16)
    x = jnp.ones((B, D), jnp.bfloat16)

    def layer_fn(h, scale):
        return h * scale + jnp.asarray(1.0, h.dtype), jnp.zeros(
            (), jnp.float32
        )

    stage_fn = stage_layer_scan(layer_fn, remat=False)

    def loss(s, x):
        out, _ = pipeline_apply(stage_fn, s, x, n_microbatches=2)
        return jnp.sum(out.astype(jnp.float32))

    with mesh:
        gs, gx = jax.jit(jax.grad(loss, argnums=(0, 1)))(scales, x)
    assert np.isfinite(np.asarray(gs, np.float32)).all()
    assert np.isfinite(np.asarray(gx, np.float32)).all()


class Test1F1B:
    """Loss-in-pipeline 1F1B schedule (reference default
    Interleaved1F1B): loss and all grads must match the dense path, and
    in-flight activation storage is bounded by depth by construction
    (ring buffer of 2S-1 slots, independent of M)."""

    def _problem(self, L=8, B=8, D=16):
        rs = np.random.RandomState(0)
        scales = jnp.asarray(rs.randn(L, D).astype(np.float32) * 0.1 + 1)
        head = jnp.asarray(rs.randn(D).astype(np.float32))
        x = jnp.asarray(rs.randn(B, D).astype(np.float32))

        def layer_fn(h, scale):
            return h * scale + 1.0, jnp.mean(h**2).astype(
                jnp.float32
            ) * 0.01

        stage_fn = stage_layer_scan(layer_fn, remat=False)

        def last_fn(lp, h):
            return jnp.mean((h @ lp) ** 2)

        def loss_ref(s, lp, x):
            h, aux = x, 0.0
            for l in range(L):
                aux = aux + jnp.mean(h**2) * 0.01
                h = h * s[l] + 1.0
            return jnp.mean((h @ lp) ** 2) + aux

        return stage_fn, last_fn, loss_ref, scales, head, x

    @pytest.mark.parametrize("pipe,m", [(2, 4), (4, 8), (4, 4)])
    def test_matches_dense(self, pipe, m):
        stage_fn, last_fn, loss_ref, scales, head, x = self._problem()
        mesh = build_mesh(MeshConfig(pipe=pipe, data=8 // pipe))
        set_mesh(mesh)

        def loss_pp(s, lp, x):
            return pipeline_loss_1f1b(
                stage_fn, last_fn, s, lp, x, n_microbatches=m
            )

        with mesh:
            val = jax.jit(loss_pp)(scales, head, x)
            g_s, g_h, g_x = jax.jit(
                jax.grad(loss_pp, argnums=(0, 1, 2))
            )(scales, head, x)
        np.testing.assert_allclose(
            float(val), float(loss_ref(scales, head, x)), rtol=1e-5
        )
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(scales, head, x)
        for got, want in zip((g_s, g_h, g_x), gr):
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), rtol=2e-4, atol=1e-6
            )

    def test_microbatch_extras(self):
        """stage/last extras are microbatched and reach the right
        microbatch (int extras get zero cotangents)."""
        mesh = build_mesh(MeshConfig(pipe=2, data=4))
        set_mesh(mesh)
        L, B, D, M = 4, 8, 8, 4
        scales = jnp.ones((L, D))
        x = jnp.ones((B, D))
        marks = jnp.arange(B, dtype=jnp.int32)  # per-sample marker

        def layer_fn(h, scale, mark):
            return h * scale + mark[:, None].astype(h.dtype), jnp.zeros(
                (), jnp.float32
            )

        stage_fn = stage_layer_scan(layer_fn, remat=False)

        def last_fn(lp, h, mark):
            return jnp.mean(h * mark[:, None].astype(h.dtype))

        def loss_pp(s, x):
            return pipeline_loss_1f1b(
                stage_fn, last_fn, s, jnp.zeros(()), x,
                stage_extras=(marks,), last_extras=(marks,),
                n_microbatches=M,
            )

        def loss_ref(s, x):
            h = x
            for l in range(L):
                h = h * s[l] + marks[:, None].astype(h.dtype)
            # mean-of-microbatch-means == global mean (equal sizes)
            return jnp.mean(h * marks[:, None].astype(h.dtype))

        with mesh:
            val, grad = jax.jit(
                jax.value_and_grad(loss_pp)
            )(scales, x)
        np.testing.assert_allclose(
            float(val), float(loss_ref(scales, x)), rtol=1e-5
        )
        g_ref = jax.grad(loss_ref)(scales, x)
        np.testing.assert_allclose(
            np.asarray(grad), np.asarray(g_ref), rtol=2e-4, atol=1e-6
        )


def test_llama_1f1b_matches_gpipe_loss():
    """The llama training loss through the 1f1b schedule equals the
    gpipe-path loss (all tokens valid -> mean-of-means == global mean)
    and its grads match."""
    base = dict(
        vocab_size=64, dim=32, n_layers=4, n_heads=4, n_kv_heads=2,
        mlp_dim=64, max_seq_len=32, attn_impl="reference", remat=False,
        dtype="float32", pipe_microbatches=4,
    )
    cfg_g = LlamaConfig(**base)
    cfg_f = LlamaConfig(**base, pipe_schedule="1f1b")
    params = llama_init(cfg_g, jax.random.key(0))
    batch = {"tokens": jax.random.randint(jax.random.key(1), (8, 17), 0, 64)}

    mesh = build_mesh(MeshConfig(pipe=2, data=2, fsdp=2))
    set_mesh(mesh)
    with mesh:
        lg, gg = jax.jit(jax.value_and_grad(
            lambda p: llama_loss_fn(cfg_g)(p, batch, None)
        ))(params)
        lf, gf = jax.jit(jax.value_and_grad(
            lambda p: llama_loss_fn(cfg_f)(p, batch, None)
        ))(params)
    np.testing.assert_allclose(float(lf), float(lg), rtol=1e-5)
    for (path, a), (_, b) in zip(
        jax.tree_util.tree_flatten_with_path(gg)[0][:8],
        jax.tree_util.tree_flatten_with_path(gf)[0][:8],
    ):
        np.testing.assert_allclose(
            np.asarray(b), np.asarray(a), rtol=5e-3, atol=1e-5,
            err_msg=str(path),
        )


def test_llama_1f1b_padded_batch_matches_gpipe():
    """With ignore_index padding unevenly spread across microbatches,
    the 1f1b loss must still equal the gpipe/dense objective (global
    valid-token normalization, not mean-of-microbatch-means)."""
    base = dict(
        vocab_size=64, dim=32, n_layers=4, n_heads=4, n_kv_heads=2,
        mlp_dim=64, max_seq_len=32, attn_impl="reference", remat=False,
        dtype="float32", pipe_microbatches=4,
    )
    cfg_g = LlamaConfig(**base)
    cfg_f = LlamaConfig(**base, pipe_schedule="1f1b")
    params = llama_init(cfg_g, jax.random.key(0))
    tokens = np.array(
        jax.random.randint(jax.random.key(1), (8, 17), 0, 64)
    )
    # mask most of the first 4 samples (microbatches 0-1): uneven valid
    tokens[:4, 9:] = -100
    batch = {"tokens": jnp.asarray(tokens)}

    mesh = build_mesh(MeshConfig(pipe=2, data=2, fsdp=2))
    set_mesh(mesh)
    with mesh:
        lg = jax.jit(
            lambda p: llama_loss_fn(cfg_g)(p, batch, None)
        )(params)
        lf = jax.jit(
            lambda p: llama_loss_fn(cfg_f)(p, batch, None)
        )(params)
    np.testing.assert_allclose(float(lf), float(lg), rtol=1e-5)


def test_llama_1f1b_tensor_parallel_matches_dense(
    isolated_ckpt_env, tmp_path
):
    """TP x PP x DP composition (BASELINE config #4): llama 1F1B on a
    pipe=2 x tensor=2 x fsdp=2 mesh matches the dense-mesh loss/grads,
    and the sharded checkpoint engine round-trips the 3D-sharded state.
    Ref: ds_3d_parallel_optimization.py:184.

    ``isolated_ckpt_env``: the engine finds its saver through the
    socket dir and names its shm segment after the job. On the shared
    defaults it adopted the saver factory of whichever agent test
    another xdist worker was running, and lost its lock socket when
    that test tore down — the whole of this test's failures under
    ``-n 6``; the loss and gradient comparisons never moved."""
    base = dict(
        vocab_size=64, dim=32, n_layers=4, n_heads=4, n_kv_heads=2,
        mlp_dim=64, max_seq_len=32, attn_impl="reference", remat=False,
        dtype="float32", pipe_microbatches=4,
    )
    cfg_d = LlamaConfig(**base)
    cfg_f = LlamaConfig(**base, pipe_schedule="1f1b")
    params = llama_init(cfg_d, jax.random.key(0))
    batch = {"tokens": jax.random.randint(jax.random.key(1), (8, 17), 0, 64)}

    dense_mesh = build_mesh(MeshConfig(data=8))
    set_mesh(dense_mesh)
    with dense_mesh:
        ld, gd = jax.jit(jax.value_and_grad(
            lambda p: llama_loss_fn(cfg_d)(p, batch, None)
        ))(params)
        ld, gd = float(ld), jax.device_get(gd)

    mesh = build_mesh(MeshConfig(pipe=2, tensor=2, fsdp=2))
    set_mesh(mesh)
    with mesh:
        lf, gf = jax.jit(jax.value_and_grad(
            lambda p: llama_loss_fn(cfg_f)(p, batch, None)
        ))(params)
        np.testing.assert_allclose(float(lf), ld, rtol=1e-5)
        for (path, a), (_, b) in zip(
            jax.tree_util.tree_flatten_with_path(gd)[0][:8],
            jax.tree_util.tree_flatten_with_path(jax.device_get(gf))[0][:8],
        ):
            np.testing.assert_allclose(
                np.asarray(b), np.asarray(a), rtol=5e-3, atol=1e-5,
                err_msg=str(path),
            )

        # sharded checkpoint round-trip under the 3D mesh
        from dlrover_tpu.trainer.flash_checkpoint.engine import (
            ShardedCheckpointEngine,
        )

        eng = ShardedCheckpointEngine(str(tmp_path / "tp_pp_ckpt"))
        assert eng.save_to_storage(1, {"params": params})
        assert eng.wait_for_shm_save()
        restored, rstep = eng.load(target={"params": params})
        assert rstep == 1
        got = jax.device_get(restored["params"]["layers"]["wq"])
        want = jax.device_get(params["layers"]["wq"])
        np.testing.assert_allclose(np.asarray(got), np.asarray(want))


def test_auto_accelerate_1f1b_train_step():
    config = LlamaConfig(
        vocab_size=64, dim=32, n_layers=4, n_heads=4, n_kv_heads=2,
        mlp_dim=64, max_seq_len=32, attn_impl="reference", remat=False,
        dtype="float32", pipe_microbatches=2, pipe_schedule="1f1b",
    )
    strategy = Strategy(
        mesh=MeshConfig(pipe=2, data=2, fsdp=2),
        compute_dtype=None, remat="none",
    )
    result = auto_accelerate(
        loss_fn=llama_loss_fn(config),
        init_fn=lambda rng: llama_init(config, rng),
        optimizer=optax.adam(1e-3),
        param_logical_axes=llama_logical_axes(config),
        strategy=strategy,
    )
    batch = {"tokens": jax.random.randint(jax.random.key(2), (8, 17), 0, 64)}
    state, metrics = result.train_step(result.state, batch, jax.random.key(3))
    assert np.isfinite(float(metrics["loss"]))
    state, m2 = result.train_step(state, batch, jax.random.key(4))
    assert np.isfinite(float(m2["loss"]))


def test_auto_accelerate_with_pipe_axis():
    config = LlamaConfig(
        vocab_size=64, dim=32, n_layers=4, n_heads=4, n_kv_heads=2,
        mlp_dim=64, max_seq_len=32, attn_impl="reference", remat=False,
        dtype="float32", pipe_microbatches=2,
    )
    strategy = Strategy(
        mesh=MeshConfig(pipe=2, data=2, fsdp=2),
        compute_dtype=None, remat="none",
    )
    result = auto_accelerate(
        loss_fn=llama_loss_fn(config),
        init_fn=lambda rng: llama_init(config, rng),
        optimizer=optax.adam(1e-3),
        param_logical_axes=llama_logical_axes(config),
        strategy=strategy,
    )
    # stacked layer params sharded over pipe
    wq_sharding = result.state.params["layers"]["wq"].sharding
    assert "pipe" in (wq_sharding.spec[0] or ())

    batch = {"tokens": jax.random.randint(jax.random.key(2), (8, 17), 0, 64)}
    state, metrics = result.train_step(result.state, batch, jax.random.key(3))
    assert np.isfinite(float(metrics["loss"]))
    state, m2 = result.train_step(state, batch, jax.random.key(4))
    assert float(m2["loss"]) < float(metrics["loss"]) + 1.0


class TestInterleaved1F1B:
    """Virtual-stage (interleaved) 1F1B — reference default schedule
    (pipeline_parallel_optimization.py:98 Interleaved1F1B)."""

    @pytest.mark.parametrize("S,V,M", [(2, 2, 4), (2, 2, 8), (4, 2, 8)])
    def test_matches_dense_with_layer_order(self, S, V, M):
        from dlrover_tpu.parallel.pipeline import (
            interleaved_layer_order,
            pipeline_loss_1f1b_interleaved,
            stage_layer_scan,
        )

        L, D, B = 8, 16, M * 2
        rng = np.random.RandomState(0)
        Ws = jnp.asarray(rng.randn(L, D, D).astype(np.float32) * 0.3)
        head = jnp.asarray(rng.randn(D).astype(np.float32))
        x = jnp.asarray(rng.randn(B, D).astype(np.float32))
        scale = jnp.ones((B,), jnp.float32)

        def layer_fn(h, lp, sc):
            return jnp.tanh(h @ lp) * sc[:, None], jnp.zeros(
                (), jnp.float32)

        stage_fn = stage_layer_scan(layer_fn, remat=False)

        def last_fn(lp, h, _unused):
            return jnp.mean((h * lp) ** 2)

        order = interleaved_layer_order(L, S, V)

        def loss_dense(Ws_, head_, x_):
            h = x_
            for e in range(L):
                h, _ = layer_fn(h, Ws_[order[e]], jnp.ones(h.shape[0]))
            hm = h.reshape(M, B // M, D)
            ce = 0.0
            for m in range(M):
                ce = ce + last_fn(head_, hm[m], None)
            return ce / M

        def loss_int(Ws_, head_, x_):
            return pipeline_loss_1f1b_interleaved(
                stage_fn, last_fn, Ws_, head_, x_,
                stage_extras=(scale,), last_extras=(scale,),
                n_microbatches=M, virtual_stages=V,
            )

        mesh = build_mesh(MeshConfig(pipe=S, data=8 // S))
        set_mesh(mesh)
        with mesh:
            ld, gd = jax.jit(jax.value_and_grad(
                loss_dense, argnums=(0, 1, 2)))(Ws, head, x)
            li, gi = jax.jit(jax.value_and_grad(
                loss_int, argnums=(0, 1, 2)))(Ws, head, x)
        np.testing.assert_allclose(float(li), float(ld), rtol=1e-5)
        for name, a, b in zip(("Ws", "head", "x"), gi, gd):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-4, atol=1e-6,
                err_msg=name)

    def test_llama_interleaved_matches_dense(self):
        from dlrover_tpu.models.llama import llama_apply
        from dlrover_tpu.parallel.pipeline import interleaved_layer_order
        from dlrover_tpu.ops.cross_entropy import softmax_cross_entropy

        base = dict(
            vocab_size=64, dim=32, n_layers=8, n_heads=4, n_kv_heads=2,
            mlp_dim=64, max_seq_len=32, attn_impl="reference",
            remat=False, dtype="float32", pipe_microbatches=4,
        )
        cfg_i = LlamaConfig(
            **base, pipe_schedule="1f1b", pipe_virtual_stages=2)
        cfg_d = LlamaConfig(**base)
        params = llama_init(cfg_d, jax.random.key(0))
        batch = {"tokens": jax.random.randint(
            jax.random.key(1), (8, 17), 0, 64)}

        # dense reference applies layers in the interleaved order
        order = interleaved_layer_order(8, 2, 2)
        params_perm = dict(params)
        params_perm["layers"] = {
            k: v[order] for k, v in params["layers"].items()
        }
        dense_mesh = build_mesh(MeshConfig(data=8))
        set_mesh(dense_mesh)
        with dense_mesh:
            ld, gd = jax.jit(jax.value_and_grad(
                lambda p: llama_loss_fn(cfg_d)(p, batch, None)
            ))(params_perm)
            ld, gd = float(ld), jax.device_get(gd)

        mesh = build_mesh(MeshConfig(pipe=2, data=2, fsdp=2))
        set_mesh(mesh)
        with mesh:
            li, gi = jax.jit(jax.value_and_grad(
                lambda p: llama_loss_fn(cfg_i)(p, batch, None)
            ))(params)
        np.testing.assert_allclose(float(li), ld, rtol=1e-5)
        # layer grads compare through the inverse permutation
        inv = np.argsort(order)
        gw_dense = gd["layers"]["wq"]
        gw_int = jax.device_get(gi["layers"]["wq"])
        np.testing.assert_allclose(
            np.asarray(gw_int), np.asarray(gw_dense)[inv],
            rtol=5e-3, atol=1e-5)
        np.testing.assert_allclose(
            np.asarray(jax.device_get(gi["lm_head"])),
            np.asarray(gd["lm_head"]), rtol=5e-3, atol=1e-5)

    def test_interleaved_ring_depth_collision_free_both_mailboxes(self):
        """Independent replay oracle: with the returned R, neither the
        saved-input mailbox (inbuf) nor the cotangent mailbox (cotbuf)
        ever overwrites a delivered-but-unconsumed entry, across a sweep
        wider than any empirical spot-check (ADVICE r3: cotbuf was
        previously unvalidated)."""
        from dlrover_tpu.parallel.pipeline import _interleaved_tables

        def replay(tables, T, R, S, V):
            inb = [{v: {} for v in range(V)} for _ in range(S)]
            cot = [{v: {} for v in range(V)} for _ in range(S)]
            for tt in range(T):
                for s in range(S):
                    # tick order mirrors the machine: deliveries land
                    # (step 1), then fwd writes its saved input, then
                    # bwd consumes both mailboxes (step 3)
                    rbm, rbv = tables["rbm"][tt][s], tables["rbv"][tt][s]
                    if rbm >= 0:
                        slot = rbm % R
                        assert cot[s][rbv].get(slot, rbm) == rbm, (
                            "cotbuf collision", S, V, tt, s, slot)
                        cot[s][rbv][slot] = rbm
                    rfm, rfv = tables["rfm"][tt][s], tables["rfv"][tt][s]
                    if rfm >= 0:
                        slot = rfm % R
                        assert inb[s][rfv].get(slot, rfm) == rfm, (
                            "inbuf rf collision", S, V, tt, s, slot)
                        inb[s][rfv][slot] = rfm
                    fm, fv = tables["fm"][tt][s], tables["fv"][tt][s]
                    if fm >= 0:
                        slot = fm % R
                        assert inb[s][fv].get(slot, fm) == fm, (
                            "inbuf fwd collision", S, V, tt, s, slot)
                        inb[s][fv][slot] = fm
                    bm, bv = tables["bm"][tt][s], tables["bv"][tt][s]
                    if bm >= 0:
                        inb[s][bv].pop(bm % R, None)
                        cot[s][bv].pop(bm % R, None)

        for S in (2, 3, 4, 6, 8):
            for V in (2, 3, 4, 6):
                for M in (S, 2 * S, 4 * S, 8 * S):
                    tables, T, R = _interleaved_tables(S, V, M)
                    assert R <= M
                    replay(tables, T, R, S, V)

    def test_interleaved_bubble_smaller_than_plain(self):
        """At (pipe=4, M=8), V=2 chunks cost fewer thin-tick units than
        plain 1F1B (whose ticks do V x the work)."""
        from dlrover_tpu.parallel.pipeline import _interleaved_tables

        _, T_v2, _ = _interleaved_tables(4, 2, 8)
        T_plain = 8 + 2 * (4 - 1)     # M + 2(S-1) fused ticks
        assert T_v2 < T_plain * 2, (T_v2, T_plain * 2)
        # busy fraction (units / tick-slots) strictly improves
        util_v2 = (2 * 8 * 2) / T_v2
        util_plain = (2 * 8) / T_plain
        assert util_v2 > util_plain
