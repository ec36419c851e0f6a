"""Hot-loop MFU levers: decomposed overlapped collectives, the fused
one-pass optimizer step, and per-site int8 selection.

Parity contracts (ISSUE 8):
- ring all-gather / reduce-scatter == ``jax.lax`` collectives on a
  multi-device CPU mesh, forward and backward;
- the overlapped layer scan (off / xla / manual) trains bit-identically
  to the plain scan, and the manual mode's collectives stay decomposed
  (ppermute ring) in the traced step;
- fused fp32 AdamW is BIT-EXACT against the reference per-leaf optax
  chain (clip + adam + weight decay included);
- fused 8-bit Adam tracks the per-leaf ``adam8bit`` within its
  documented quantization tolerance and its state round-trips through
  flash-checkpoint restore;
- the fused step's dispatch count is bounded (no per-leaf tail).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dlrover_tpu.models import (
    PRESETS,
    llama_init,
    llama_logical_axes,
    llama_loss_fn,
)
from dlrover_tpu.ops.collectives import ring_all_gather, ring_reduce_scatter
from dlrover_tpu.ops.fused_optim import fused_adamw
from dlrover_tpu.optimizers import adam8bit
from dlrover_tpu.parallel import (
    MeshConfig,
    Strategy,
    auto_accelerate,
)
from tests.conftest import count_eqns


def _mesh(n):
    from dlrover_tpu.parallel.mesh import build_mesh, set_mesh

    mesh = build_mesh(
        MeshConfig(data=1, fsdp=n), devices=jax.devices()[:n]
    )
    set_mesh(mesh)
    return mesh


# ---------------------------------------------------------------------------
# decomposed collectives vs jax.lax on a multi-device CPU mesh
# ---------------------------------------------------------------------------


class TestRingCollectives:
    @pytest.mark.parametrize("dim", [0, 1])
    def test_ring_all_gather_matches_lax(self, dim):
        from jax.sharding import NamedSharding, PartitionSpec as P

        n = 4
        mesh = _mesh(n)
        sm = jax.shard_map
        x = jnp.asarray(
            np.random.RandomState(0).randn(8, 12).astype(np.float32)
        )
        spec = [None, None]
        spec[dim] = "fsdp"
        xs = jax.device_put(x, NamedSharding(mesh, P(*spec)))

        ring = sm(
            lambda s: ring_all_gather(s, "fsdp", n, dim=dim),
            mesh=mesh, in_specs=P(*spec), out_specs=P(None, None),
            check_vma=False,
        )
        ref = sm(
            lambda s: jax.lax.all_gather(s, "fsdp", axis=dim, tiled=True),
            mesh=mesh, in_specs=P(*spec), out_specs=P(None, None),
            check_vma=False,
        )
        np.testing.assert_array_equal(
            np.asarray(jax.jit(ring)(xs)), np.asarray(jax.jit(ref)(xs))
        )
        np.testing.assert_array_equal(np.asarray(jax.jit(ring)(xs)), x)

    def test_ring_reduce_scatter_matches_psum_scatter(self):
        from jax.sharding import NamedSharding, PartitionSpec as P

        n = 4
        mesh = _mesh(n)
        sm = jax.shard_map
        x = jnp.asarray(
            np.random.RandomState(1).randn(8, 6).astype(np.float32)
        )
        xs = jax.device_put(x, NamedSharding(mesh, P(None, None)))
        ring = sm(
            lambda s: ring_reduce_scatter(s, "fsdp", n, dim=0),
            mesh=mesh, in_specs=P(None, None), out_specs=P("fsdp", None),
            check_vma=False,
        )
        ref = sm(
            lambda s: jax.lax.psum_scatter(
                s, "fsdp", scatter_dimension=0, tiled=True
            ),
            mesh=mesh, in_specs=P(None, None), out_specs=P("fsdp", None),
            check_vma=False,
        )
        np.testing.assert_allclose(
            np.asarray(jax.jit(ring)(xs)), np.asarray(jax.jit(ref)(xs)),
            rtol=1e-6,
        )

    def test_ring_gather_gradient_matches_unsharded(self):
        """AD through the ring gather == the plain sharded-matmul grad
        (the transpose is a decomposed ring reduce-scatter)."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        n = 4
        mesh = _mesh(n)
        sm = jax.shard_map
        rng = np.random.RandomState(2)
        W = jnp.asarray(rng.randn(8, 6).astype(np.float32))
        X = jnp.asarray(rng.randn(8, 8).astype(np.float32))
        Ws = jax.device_put(W, NamedSharding(mesh, P("fsdp", None)))
        Xs = jax.device_put(X, NamedSharding(mesh, P("fsdp", None)))
        gat = sm(
            lambda s: ring_all_gather(s, "fsdp", n, dim=0),
            mesh=mesh, in_specs=P("fsdp", None), out_specs=P(None, None),
            check_vma=False,
        )

        def loss_ring(w, x):
            return jnp.sum(jnp.sin(x @ gat(w)))

        def loss_ref(w, x):
            return jnp.sum(jnp.sin(x @ w))

        with mesh:
            g_ring = jax.jit(jax.grad(loss_ring))(Ws, Xs)
            g_ref = jax.jit(jax.grad(loss_ref))(Ws, Xs)
        np.testing.assert_allclose(
            np.asarray(g_ring), np.asarray(g_ref), atol=1e-6
        )
        # the backward stays decomposed: ppermutes, not one collective
        jaxpr = str(jax.make_jaxpr(jax.grad(loss_ring))(Ws, Xs))
        assert jaxpr.count("ppermute") >= 2 * (n - 1)

    def test_reduce_scatter_rejects_indivisible(self):
        with pytest.raises(ValueError, match="not divisible"):
            _mesh(4)
            sm = jax.shard_map
            from jax.sharding import PartitionSpec as P

            mesh = _mesh(4)
            x = jnp.ones((7, 4))
            sm(
                lambda s: ring_reduce_scatter(s, "fsdp", 4, dim=0),
                mesh=mesh, in_specs=P(None, None),
                out_specs=P("fsdp", None), check_vma=False,
            )(x)


# ---------------------------------------------------------------------------
# overlapped layer scan: off / xla / manual train identically
# ---------------------------------------------------------------------------


_TRAIN_CACHE: dict = {}


def _train(cfg, overlap, tokens, n_steps=2, n_dev=4, remat="minimal"):
    # the "off" baselines repeat across tests — cache per config so the
    # suite pays each auto_accelerate compile once
    key = (overlap, remat, n_steps, n_dev, tokens.shape)
    if key in _TRAIN_CACHE:
        return _TRAIN_CACHE[key]
    strat = Strategy(
        mesh=MeshConfig(data=1, fsdp=n_dev), remat=remat,
        overlap_collectives=overlap, donate=False,
    )
    res = auto_accelerate(
        llama_loss_fn(cfg), lambda rng: llama_init(cfg, rng),
        optax.sgd(1e-2), llama_logical_axes(cfg), strategy=strat,
        devices=jax.devices()[:n_dev],
    )
    s = res.state
    losses = []
    for i in range(n_steps):
        s, m = res.train_step(s, {"tokens": tokens}, jax.random.key(i))
        losses.append(float(m["loss"]))
    flat = np.concatenate(
        [np.asarray(x).ravel() for x in jax.tree.leaves(s.params)]
    )
    _TRAIN_CACHE[key] = (losses, flat)
    return losses, flat


class TestOverlappedScan:
    @pytest.mark.parametrize("mode", ["xla", "manual"])
    def test_overlap_trains_identically(self, mode):
        cfg = PRESETS["tiny"]
        tokens = jnp.asarray(
            np.random.RandomState(0).randint(
                0, cfg.vocab_size, (8, 17)
            )
        )
        l_off, p_off = _train(cfg, "off", tokens)
        l_on, p_on = _train(cfg, mode, tokens)
        assert l_off == l_on
        np.testing.assert_array_equal(p_off, p_on)

    def test_overlap_remat_none_identical_and_checkpoint_free(self):
        """Overlap composes with the remat=none gate: same numbers,
        still no checkpoint primitive in the trace."""
        cfg = PRESETS["tiny"]
        tokens = jnp.asarray(
            np.random.RandomState(1).randint(
                0, cfg.vocab_size, (4, 17)
            )
        )
        l_off, p_off = _train(cfg, "off", tokens, remat="none")
        l_on, p_on = _train(cfg, "xla", tokens, remat="none")
        assert l_off == l_on
        np.testing.assert_array_equal(p_off, p_on)

    def test_manual_mode_traces_decomposed_collectives(self):
        from dlrover_tpu.parallel.overlap import overlap_autocast

        cfg = PRESETS["tiny"]
        mesh = _mesh(4)
        params = llama_init(cfg, jax.random.key(0))
        loss_fn = llama_loss_fn(cfg)
        tokens = jnp.asarray(
            np.random.RandomState(0).randint(
                0, cfg.vocab_size, (8, 17)
            )
        )

        def run(p):
            return loss_fn(p, {"tokens": tokens}, jax.random.key(0))

        with mesh, overlap_autocast("manual"):
            tr = str(jax.make_jaxpr(jax.grad(run))(params))
        assert "ppermute" in tr
        with mesh:
            tr_off = str(jax.make_jaxpr(jax.grad(run))(params))
        assert "ppermute" not in tr_off

    def test_overlap_noop_without_fsdp(self):
        """fsdp=1: the gather resolves to None and the plain scan runs
        (no overlap machinery in the trace)."""
        from dlrover_tpu.parallel.overlap import (
            layer_gather_fn,
            overlap_autocast,
        )

        _mesh(1)
        with overlap_autocast("xla"):
            assert layer_gather_fn({"w": ("embed", "mlp")}) is None

    def test_overlap_mode_validated(self):
        from dlrover_tpu.parallel.overlap import overlap_autocast

        with pytest.raises(ValueError, match="overlap mode"):
            with overlap_autocast("bogus"):
                pass

    def test_strategy_roundtrip_new_fields(self):
        s = Strategy(
            overlap_collectives="manual", quant_sites="mlp",
            fused_optim=True,
        )
        s2 = Strategy.from_json(s.to_json())
        assert s2.overlap_collectives == "manual"
        assert s2.quant_sites == "mlp"
        assert s2.fused_optim is True
        assert "overlap=manual" in s2.describe()


# ---------------------------------------------------------------------------
# fused optimizer: fp32 bit-exact, 8-bit tolerance, bounded dispatch
# ---------------------------------------------------------------------------


def _tree(rng, scale=1.0):
    return {
        "a": jnp.asarray(rng.randn(7, 33).astype(np.float32) * scale),
        "b": {
            "w": jnp.asarray(rng.randn(300).astype(np.float32) * scale),
            "v": jnp.asarray(
                rng.randn(5, 5, 5).astype(np.float32) * scale
            ),
        },
    }


def _assert_trees_equal(a, b, **kw):
    for (pa, la), (_, lb) in zip(
        jax.tree_util.tree_flatten_with_path(a)[0],
        jax.tree_util.tree_flatten_with_path(b)[0],
    ):
        if kw:
            np.testing.assert_allclose(
                np.asarray(la), np.asarray(lb), err_msg=str(pa), **kw
            )
        else:
            np.testing.assert_array_equal(
                np.asarray(la), np.asarray(lb), err_msg=str(pa)
            )


class TestFusedAdam:
    @pytest.mark.parametrize("clip,wd", [
        (None, 0.0), (1.0, 0.0), (0.5, 0.01),
    ])
    def test_fp32_bit_exact_vs_optax_chain(self, clip, wd):
        rng = np.random.RandomState(0)
        params = _tree(rng)
        fused = fused_adamw(1e-3, weight_decay=wd, clip_norm=clip)
        chain = (
            [optax.clip_by_global_norm(clip)] if clip is not None else []
        )
        chain.append(optax.scale_by_adam())
        if wd:
            chain.append(optax.add_decayed_weights(wd))
        chain.append(optax.scale(-1e-3))
        ref = optax.chain(*chain)
        sf, sr = fused.init(params), ref.init(params)
        pf = pr = params
        for step in range(3):
            grads = jax.tree.map(
                lambda p: jnp.asarray(
                    rng.randn(*p.shape).astype(np.float32)
                ),
                params,
            )
            uf, sf = jax.jit(fused.update)(grads, sf, pf)
            ur, sr = jax.jit(ref.update)(grads, sr, pr)
            pf = optax.apply_updates(pf, uf)
            pr = optax.apply_updates(pr, ur)
            _assert_trees_equal(pf, pr)  # BIT-exact, every step

    def test_fp32_schedule_lr(self):
        sched = optax.linear_schedule(1e-2, 1e-3, 10)
        rng = np.random.RandomState(3)
        params = _tree(rng)
        grads = _tree(rng)
        fused = fused_adamw(sched)
        ref = optax.chain(optax.scale_by_adam(),
                          optax.scale_by_learning_rate(sched))
        sf, sr = fused.init(params), ref.init(params)
        pf = pr = params
        for _ in range(3):
            uf, sf = jax.jit(fused.update)(grads, sf, pf)
            ur, sr = jax.jit(ref.update)(grads, sr, pr)
            pf = optax.apply_updates(pf, uf)
            pr = optax.apply_updates(pr, ur)
        _assert_trees_equal(pf, pr, rtol=1e-7, atol=0)

    def test_8bit_tracks_per_leaf_adam8bit(self):
        rng = np.random.RandomState(1)
        params = _tree(rng, scale=0.1)
        fused = fused_adamw(1e-2, bits=8)
        ref = adam8bit(1e-2)
        sf, sr = fused.init(params), ref.init(params)
        pf = pr = params
        for _ in range(8):
            grads = jax.tree.map(
                lambda p: jnp.asarray(
                    rng.randn(*p.shape).astype(np.float32)
                ),
                params,
            )
            uf, sf = jax.jit(fused.update)(grads, sf, pf)
            ur, sr = jax.jit(ref.update)(grads, sr, pr)
            pf = optax.apply_updates(pf, uf)
            pr = optax.apply_updates(pr, ur)
        # identical math, different stochastic-rounding draws + the
        # analytic (vs tabulated) log codebook: trajectories agree
        # within the documented ~11% log-step quantization noise
        # relative to how far the params moved
        a = np.concatenate(
            [np.asarray(x).ravel() for x in jax.tree.leaves(pf)]
        )
        b = np.concatenate(
            [np.asarray(x).ravel() for x in jax.tree.leaves(pr)]
        )
        p0 = np.concatenate(
            [np.asarray(x).ravel() for x in jax.tree.leaves(params)]
        )
        denom = max(float(np.abs(b - p0).max()), 1e-9)
        assert float(np.abs(a - b).max()) / denom < 0.15

    @pytest.mark.parametrize("bits", [32, 8])
    def test_bounded_dispatch_count(self, bits):
        """THE fused-step gate: one pallas dispatch regardless of leaf
        count (the per-leaf 8-bit path scales 2x per leaf)."""
        rng = np.random.RandomState(2)
        few = {f"p{i}": jnp.asarray(
            rng.randn(40).astype(np.float32)) for i in range(2)}
        many = {f"p{i}": jnp.asarray(
            rng.randn(40).astype(np.float32)) for i in range(20)}
        def pallas_calls(opt, tree):
            jaxpr = jax.make_jaxpr(opt.update)(
                tree, opt.init(tree), tree
            )
            return count_eqns(jaxpr.jaxpr, ("pallas_call",))

        fused = fused_adamw(1e-3, bits=bits)
        for tree in (few, many):
            assert pallas_calls(fused, tree) == 1
        n_many = pallas_calls(adam8bit(1e-3), many)
        assert n_many >= len(many)  # the tail the fusion removes

    def test_8bit_state_roundtrips_through_checkpoint_restore(
        self, tmp_path
    ):
        """Save mid-run, restore into a zeroed target, keep stepping:
        the restored trajectory must equal the uninterrupted one (the
        8-bit state is deterministic given count + grads)."""
        from dlrover_tpu.trainer.flash_checkpoint.engine import (
            ReplicatedCheckpointEngine,
        )

        rng = np.random.RandomState(4)
        params = _tree(rng, scale=0.1)
        grads = [_tree(rng) for _ in range(4)]
        fused = fused_adamw(1e-2, bits=8)
        upd = jax.jit(fused.update)

        s = fused.init(params)
        p = params
        for g in grads[:2]:
            u, s = upd(g, s, p)
            p = optax.apply_updates(p, u)

        engine = ReplicatedCheckpointEngine(str(tmp_path / "ckpt"))
        try:
            assert engine.save_to_memory(2, {"opt": s, "params": p})
            target = {
                "opt": jax.tree.map(jnp.zeros_like, s),
                "params": jax.tree.map(jnp.zeros_like, p),
            }
            restored, step = engine.load(target=target)
            assert step == 2
        finally:
            engine.close()
        _assert_trees_equal(restored["opt"], s)

        # uninterrupted vs restored continuation
        p_cont, s_cont = p, s
        p_rest = restored["params"]
        s_rest = jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(s),
            jax.tree_util.tree_leaves(restored["opt"]),
        )
        for g in grads[2:]:
            u, s_cont = upd(g, s_cont, p_cont)
            p_cont = optax.apply_updates(p_cont, u)
            u2, s_rest = upd(g, s_rest, p_rest)
            p_rest = optax.apply_updates(p_rest, u2)
        _assert_trees_equal(p_cont, p_rest)

    def test_fused_in_train_loop_converges(self):
        """End-to-end through auto_accelerate: the fused optimizer is a
        drop-in GradientTransformation."""
        cfg = PRESETS["tiny"]
        strat = Strategy(
            mesh=MeshConfig(data=1, fsdp=1), remat="none",
            fused_optim=True, donate=False,
        )
        res = auto_accelerate(
            llama_loss_fn(cfg), lambda rng: llama_init(cfg, rng),
            fused_adamw(1e-2, bits=8, clip_norm=1.0),
            llama_logical_axes(cfg), strategy=strat,
            devices=jax.devices()[:1],
        )
        tokens = jnp.asarray(
            np.random.RandomState(0).randint(
                0, cfg.vocab_size, (8, 33)
            )
        )
        s = res.state
        losses = []
        for i in range(4):
            s, m = res.train_step(
                s, {"tokens": tokens}, jax.random.key(i)
            )
            losses.append(float(m["loss"]))
        assert all(np.isfinite(losses))
        assert losses[-1] < losses[0]


# ---------------------------------------------------------------------------
# per-site int8 + profiler require-ops gate
# ---------------------------------------------------------------------------


class TestPerSiteQuant:
    def test_site_filter_changes_which_sites_quantize(self):
        from dlrover_tpu.ops.fp8 import quant_autocast

        cfg = dataclasses.replace(PRESETS["tiny"])
        _mesh(1)
        params = llama_init(cfg, jax.random.key(0))
        loss_fn = llama_loss_fn(cfg)
        tokens = jnp.asarray(
            np.random.RandomState(0).randint(
                0, cfg.vocab_size, (4, 17)
            )
        )

        def loss(p):
            return float(jax.jit(
                lambda q: loss_fn(q, {"tokens": tokens}, jax.random.key(0))
            )(p))

        l_bf = loss(params)
        with quant_autocast("int8"):
            l_all = loss(params)
        with quant_autocast("int8", sites="mlp"):
            l_mlp = loss(params)
        with quant_autocast("int8", sites="attn_qkv,attn_out"):
            l_attn = loss(params)
        # distinct quantization subsets -> distinct numerics, and the
        # partial arms sit strictly between bf16 and full int8 effects
        assert len({l_bf, l_all, l_mlp, l_attn}) == 4

    def test_untagged_sites_always_quantize(self):
        from dlrover_tpu.ops.fp8 import qdot, quant_autocast

        a = jnp.asarray(
            np.random.RandomState(0).randn(4, 8).astype(np.float32)
        )
        b = jnp.asarray(
            np.random.RandomState(1).randn(8, 4).astype(np.float32)
        )
        with quant_autocast("int8", sites="mlp"):
            out_untagged = qdot(a, b)          # no site label
            out_off = qdot(a, b, site="attn_qkv")
        assert not np.allclose(np.asarray(out_untagged), np.asarray(a @ b))
        np.testing.assert_array_equal(
            np.asarray(out_off), np.asarray(a @ b)
        )

    def test_parse_quant_sites(self):
        from dlrover_tpu.ops.fp8 import parse_quant_sites

        assert parse_quant_sites("all") is None
        assert parse_quant_sites(None) is None
        assert parse_quant_sites("mlp, attn_out") == frozenset(
            {"mlp", "attn_out"}
        )
