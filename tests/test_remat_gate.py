"""The remat="none" trace-time gate.

Strategy.remat="none" must mean NONE: the model's own per-layer
``jax.checkpoint`` (and the qdot residual ``checkpoint_name`` tags the
quant-aware policy would consume) must vanish from the traced step —
before the gate, a leaked checkpoint custom-call charged ~7% of the
remat=none headline step (25.7 ms in a pre-PR-1 chip run).
Intentional non-remat checkpoints — the fused CE's
logits-memory chunking — survive the gate untouched.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from dlrover_tpu.models import PRESETS, llama_init, llama_loss_fn
from dlrover_tpu.ops.fp8 import no_remat_autocast, quant_autocast
from tests.conftest import count_eqns

CHECKPOINT_PRIMS = ("remat2", "checkpoint")
NAME_PRIMS = ("name",)


def _traced_loss(cfg, ctx_factories):
    loss_fn = llama_loss_fn(cfg)
    params = llama_init(cfg, jax.random.key(0))
    tokens = jnp.asarray(
        np.random.RandomState(0).randint(0, cfg.vocab_size, (2, 17))
    )

    def run(p, b):
        return loss_fn(p, b, jax.random.key(0))

    import contextlib

    with contextlib.ExitStack() as stack:
        for f in ctx_factories:
            stack.enter_context(f())
        return jax.make_jaxpr(jax.grad(run))(
            params, {"tokens": tokens}
        ).jaxpr


class TestNoRematGate:
    def _cfg(self, **kw):
        return dataclasses.replace(PRESETS["tiny"], **kw)

    def test_model_checkpoint_stripped_under_gate(self):
        cfg = self._cfg(remat=True, ce_chunks=1)
        before = count_eqns(_traced_loss(cfg, []), CHECKPOINT_PRIMS)
        assert before >= 1  # config.remat=True checkpoints the scan body
        after = count_eqns(
            _traced_loss(cfg, [no_remat_autocast]), CHECKPOINT_PRIMS
        )
        assert after == 0

    def test_qdot_residual_tags_stripped_under_gate(self):
        cfg = self._cfg(remat=True, ce_chunks=1)
        tagged = count_eqns(
            _traced_loss(cfg, [lambda: quant_autocast("int8")]),
            NAME_PRIMS,
        )
        assert tagged >= 1  # qdot_out/qdot_res tags for the save policy
        untagged = count_eqns(
            _traced_loss(
                cfg,
                [lambda: quant_autocast("int8"), no_remat_autocast],
            ),
            NAME_PRIMS,
        )
        assert untagged == 0

    def test_ce_chunk_path_is_checkpoint_free(self):
        """ce_chunks>1 bounds logits memory via a hand-written
        custom_vjp now — NO jax.checkpoint anywhere in the trace (the
        old intentional one lowered to the ``checkpoint.10``
        custom-call charged 25.7 ms/step on the remat=none headline
        arm). Gate off or on, the chunked-CE loss must carry zero
        checkpoint primitives."""
        cfg = self._cfg(remat=False, ce_chunks=2)
        n = count_eqns(
            _traced_loss(cfg, [no_remat_autocast]), CHECKPOINT_PRIMS
        )
        assert n == 0
        # without the gate too: the custom-vjp recompute needs no remat
        n_plain = count_eqns(_traced_loss(cfg, []), CHECKPOINT_PRIMS)
        assert n_plain == 0

    def test_ce_legacy_norm_fn_path_keeps_checkpoint(self):
        """The generic norm_fn closure hook cannot ride the custom VJP
        and stays on the jax.checkpoint scan — pinned so a future
        cleanup doesn't silently blow up its logits memory."""
        import jax.numpy as jnp

        from dlrover_tpu.ops.cross_entropy import (
            fused_linear_cross_entropy,
        )

        h = jnp.ones((2, 8, 16))
        w = jnp.ones((16, 32))
        labels = jnp.zeros((2, 8), jnp.int32)

        def run(hh):
            ls, _ = fused_linear_cross_entropy(
                hh, w, labels, n_chunks=2, norm_fn=lambda t: t * 2.0
            )
            return ls

        jaxpr = jax.make_jaxpr(jax.grad(run))(h).jaxpr
        assert count_eqns(jaxpr, CHECKPOINT_PRIMS) == 1

    def test_strategy_none_sets_gate_in_accelerate(self):
        """End-to-end: auto_accelerate with remat='none' produces a step
        whose compiled loss saw the gate (counted via the model path
        running checkpoint-free)."""
        import optax

        from dlrover_tpu.models import llama_logical_axes
        from dlrover_tpu.parallel import (
            MeshConfig,
            Strategy,
            auto_accelerate,
        )

        cfg = self._cfg(remat=True, ce_chunks=1)
        res = auto_accelerate(
            llama_loss_fn(cfg),
            lambda rng: llama_init(cfg, rng),
            optax.sgd(1e-3),
            llama_logical_axes(cfg),
            strategy=Strategy(
                mesh=MeshConfig(data=1, fsdp=1), remat="none"
            ),
            devices=jax.devices()[:1],
        )
        tokens = jnp.asarray(
            np.random.RandomState(0).randint(0, cfg.vocab_size, (2, 17))
        )
        state, m = res.train_step(
            res.state, {"tokens": tokens}, jax.random.key(0)
        )
        assert np.isfinite(float(m["loss"]))
