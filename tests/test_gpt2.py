"""Tests for the GPT-2 model family and ElasticPsService — reference
coverage analogue: GPT2AttentionFA swap tests and elastic_ps tests.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest



from dlrover_tpu.master.elastic_ps import ElasticPsService
from dlrover_tpu.models import (
    GPT2_PRESETS,
    GPT2Config,
    gpt2_apply,
    gpt2_init,
    gpt2_logical_axes,
    gpt2_loss_fn,
)
from dlrover_tpu.parallel import MeshConfig, Strategy, auto_accelerate


@pytest.fixture
def tiny():
    return GPT2_PRESETS["tiny"]


class TestGPT2:
    def test_param_count_matches_tree(self, tiny):
        params = gpt2_init(tiny, jax.random.key(0))
        actual = sum(
            int(np.prod(p.shape)) for p in jax.tree.leaves(params)
        )
        assert actual == tiny.param_count()

    def test_logical_axes_match_tree(self, tiny):
        params = gpt2_init(tiny, jax.random.key(0))
        axes = gpt2_logical_axes(tiny)
        p_paths = {
            jax.tree_util.keystr(kp)
            for kp, _ in jax.tree_util.tree_flatten_with_path(params)[0]
        }
        a_paths = {
            jax.tree_util.keystr(kp)
            for kp, _ in jax.tree_util.tree_flatten_with_path(
                axes, is_leaf=lambda x: isinstance(x, tuple)
            )[0]
        }
        assert p_paths == a_paths
        # every axes tuple length matches the param rank
        flat_p = dict(jax.tree_util.tree_flatten_with_path(params)[0])
        for kp, ax in jax.tree_util.tree_flatten_with_path(
            axes, is_leaf=lambda x: isinstance(x, tuple)
        )[0]:
            assert len(ax) == flat_p[kp].ndim, kp

    def test_forward_and_causality(self, tiny):
        params = gpt2_init(tiny, jax.random.key(0))
        tokens = jnp.asarray(
            np.random.RandomState(0).randint(0, tiny.vocab_size, (2, 24))
        )
        logits = gpt2_apply(tiny, params, tokens)
        assert logits.shape == (2, 24, tiny.vocab_size)
        assert logits.dtype == jnp.float32
        assert np.all(np.isfinite(np.asarray(logits)))
        # causality: changing a future token leaves past logits unchanged
        tokens2 = tokens.at[:, 12].set((tokens[:, 12] + 1) % 512)
        logits2 = gpt2_apply(tiny, params, tokens2)
        np.testing.assert_allclose(
            np.asarray(logits[:, :12]), np.asarray(logits2[:, :12]),
            atol=2e-2,
        )
        assert not np.allclose(
            np.asarray(logits[:, 12:]), np.asarray(logits2[:, 12:])
        )

    def test_tied_and_untied_head(self, tiny):
        import dataclasses

        untied = dataclasses.replace(tiny, tie_lm_head=False)
        p_tied = gpt2_init(tiny, jax.random.key(0))
        p_untied = gpt2_init(untied, jax.random.key(0))
        assert "lm_head" not in p_tied
        assert p_untied["lm_head"].shape == (tiny.dim, tiny.vocab_size)
        assert "lm_head" in gpt2_logical_axes(untied)

    @pytest.mark.parametrize("mesh_cfg", [
        MeshConfig(fsdp=8),
        MeshConfig(fsdp=4, tensor=2),
        MeshConfig(data=2, fsdp=2, tensor=2),
    ])
    def test_trains_under_strategies(self, tiny, mesh_cfg):
        strategy = Strategy(mesh=mesh_cfg, remat="none")
        res = auto_accelerate(
            gpt2_loss_fn(tiny), lambda r: gpt2_init(tiny, r),
            optax.adamw(1e-3), gpt2_logical_axes(tiny),
            strategy=strategy,
        )
        toks = jnp.asarray(np.random.RandomState(0).randint(
            0, tiny.vocab_size, (8, 33)
        ))
        state = res.state
        losses = []
        for i in range(3):
            state, m = res.train_step(
                state, {"tokens": toks}, jax.random.key(i)
            )
            losses.append(float(m["loss"]))
        assert all(np.isfinite(losses))
        assert losses[-1] < losses[0]  # memorizing one batch

    def test_pipeline_strategy(self, tiny):
        import dataclasses

        cfg = dataclasses.replace(tiny, pipe_microbatches=2)
        strategy = Strategy(
            mesh=MeshConfig(pipe=2, fsdp=4), remat="none"
        )
        res = auto_accelerate(
            gpt2_loss_fn(cfg), lambda r: gpt2_init(cfg, r),
            optax.adamw(1e-3), gpt2_logical_axes(cfg),
            strategy=strategy,
        )
        toks = jnp.asarray(np.random.RandomState(0).randint(
            0, cfg.vocab_size, (8, 17)
        ))
        _, m = res.train_step(res.state, {"tokens": toks},
                              jax.random.key(0))
        assert np.isfinite(float(m["loss"]))

    def test_1f1b_rejects_overlong_sequences(self, tiny):
        """The 1f1b path must keep gpt2_apply's trace-time guard: a
        too-long batch raises instead of silently clamping positions."""
        import dataclasses

        from dlrover_tpu.models.gpt2 import _gpt2_1f1b_loss

        cfg = dataclasses.replace(
            tiny, pipe_microbatches=2, pipe_schedule="1f1b"
        )
        params = gpt2_init(cfg, jax.random.key(0))
        too_long = jnp.zeros(
            (4, cfg.max_seq_len + 2), jnp.int32
        )
        with pytest.raises(ValueError, match="max_seq_len"):
            _gpt2_1f1b_loss(cfg, params, too_long)

    def test_1f1b_matches_gpipe_loss(self, tiny):
        import dataclasses

        from dlrover_tpu.parallel import build_mesh, set_mesh

        cfg_g = dataclasses.replace(tiny, pipe_microbatches=4)
        cfg_f = dataclasses.replace(
            tiny, pipe_microbatches=4, pipe_schedule="1f1b"
        )
        params = gpt2_init(cfg_g, jax.random.key(0))
        batch = {"tokens": jnp.asarray(np.random.RandomState(1).randint(
            0, cfg_g.vocab_size, (8, 17)
        ))}
        mesh = build_mesh(MeshConfig(pipe=2, fsdp=4))
        set_mesh(mesh)
        try:
            with mesh:
                lg, gg = jax.jit(jax.value_and_grad(
                    lambda p: gpt2_loss_fn(cfg_g)(p, batch, None)
                ))(params)
                lf, gf = jax.jit(jax.value_and_grad(
                    lambda p: gpt2_loss_fn(cfg_f)(p, batch, None)
                ))(params)
        finally:
            import dlrover_tpu.parallel.mesh as mesh_mod

            mesh_mod._global_mesh = None
        np.testing.assert_allclose(float(lf), float(lg), rtol=1e-5)
        # embed grads combine the stage-0 lookup and (tied) last-stage
        # head cotangents — the strongest cross-check of the schedule
        np.testing.assert_allclose(
            np.asarray(gf["embed"]), np.asarray(gg["embed"]),
            rtol=5e-3, atol=3e-4,
        )


class TestElasticPsService:
    def test_version_bump_and_sync(self):
        svc = ElasticPsService()
        assert svc.get_ps_version() == 0
        assert svc.inc_global_cluster_version() == 1
        # worker 0 lags, then catches up
        svc.update_ps_version(0, ElasticPsService.LOCAL, 0)
        assert not svc.all_workers_synced()
        svc.update_ps_version(0, ElasticPsService.LOCAL, 1)
        assert svc.all_workers_synced()
        assert svc.get_ps_version(ElasticPsService.LOCAL, 0) == 1

    def test_restored_version(self):
        svc = ElasticPsService()
        svc.update_ps_version(0, ElasticPsService.RESTORED, 7)
        assert svc.get_ps_version(ElasticPsService.RESTORED) == 7

    def test_rpc_roundtrip(self, local_master):
        """Worker polls/updates PS versions through the master RPC."""
        from dlrover_tpu.agent.master_client import MasterClient
        from dlrover_tpu.common.constants import NodeType

        client = MasterClient(local_master.addr, 0, NodeType.WORKER)
        assert client.get_ps_version() == 0
        local_master.elastic_ps_service.inc_global_cluster_version()
        assert client.get_ps_version() == 1
        assert client.report_ps_version(1, "local")
        assert local_master.elastic_ps_service.all_workers_synced()
        client.close()


def test_flash_einsum_path_matches_reference():
    """The einsum-form flash branch (qkv direct to [B,H,S,Dh]) equals
    the reference-softmax path."""
    import dataclasses

    from dlrover_tpu.models.gpt2 import GPT2Config, gpt2_apply, gpt2_init

    cfg_ref = GPT2Config(
        vocab_size=64, dim=32, n_layers=2, n_heads=4, max_seq_len=32,
        mlp_dim=64, attn_impl="reference", dtype="float32",
    )
    cfg_flash = dataclasses.replace(cfg_ref, attn_impl="flash")
    params = gpt2_init(cfg_ref, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (2, 16), 0, 64)
    ref = gpt2_apply(cfg_ref, params, tokens)
    out = gpt2_apply(cfg_flash, params, tokens)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=3e-4
    )
