"""Generic-model ingestion: a third-party model defined HERE (not a
framework model family) gets fsdp+tensor+pipe acceleration with no
hand-written logical axes (reference capability: ModelContext over any
nn.Module + automatic pipeline graph partition + the HF->TP rewrite
registry, atorch/auto/model_context.py,
pipeline_parallel_optimization.py:56, modules_registry.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest



from dlrover_tpu.parallel import (
    MeshConfig,
    StackedModule,
    Strategy,
    accelerate_module,
    infer_logical_axes,
    stack_layer_params,
)

VOCAB, DIM, LAYERS, FF = 64, 16, 4, 64


def third_party_init(rng):
    """A flax-style model: numbered sibling layer subtrees, HF-ish
    parameter names the adapter has never seen in this repo."""
    ks = jax.random.split(rng, 2 + LAYERS)
    params = {
        "wte": jax.random.normal(ks[0], (VOCAB, DIM)) * 0.02,
        "lm_head": jax.random.normal(ks[1], (DIM, VOCAB)) * 0.02,
    }
    for i in range(LAYERS):
        k1, k2, k3, k4 = jax.random.split(ks[2 + i], 4)
        params[f"block_{i}"] = {
            "q_proj": jax.random.normal(k1, (DIM, DIM)) * 0.05,
            "o_proj": jax.random.normal(k2, (DIM, DIM)) * 0.05,
            "fc1": jax.random.normal(k3, (DIM, FF)) * 0.05,
            "fc2": jax.random.normal(k4, (FF, DIM)) * 0.05,
            "ln": jnp.ones((DIM,)),
        }
    return params


def layer_fn(h, lp):
    dtype = h.dtype
    y = h * lp["ln"].astype(dtype)
    y = jnp.tanh(y @ lp["q_proj"].astype(dtype)) @ lp["o_proj"].astype(
        dtype
    )
    h = h + y
    h = h + jax.nn.gelu(h @ lp["fc1"].astype(dtype)) @ lp["fc2"].astype(
        dtype
    )
    return h


def embed_fn(params, batch):
    return params["wte"].astype(jnp.float32)[batch["tokens"][:, :-1]]


def head_loss_fn(params, h, batch, rng):
    logits = h @ params["lm_head"].astype(h.dtype)
    targets = batch["tokens"][:, 1:]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.mean(
        jnp.take_along_axis(logp, targets[..., None], axis=-1)
    )


def stacked_init(rng):
    params, _ = stack_layer_params(third_party_init(rng), into="layers")
    return params


class TestInferLogicalAxes:
    def test_orientations_from_names_and_shapes(self):
        abstract = jax.eval_shape(stacked_init, jax.random.key(0))
        axes = infer_logical_axes(abstract, vocab_size=VOCAB)
        layers = axes["layers"]
        assert layers["q_proj"] == ("layer", "embed", "mlp")
        assert layers["o_proj"] == ("layer", "mlp", "embed")
        assert layers["fc1"] == ("layer", "embed", "mlp")
        assert layers["fc2"] == ("layer", "mlp", "embed")
        assert layers["ln"] == ("layer", "embed")
        assert axes["wte"] == ("vocab", "embed")
        assert axes["lm_head"] == ("embed", "vocab")

    def test_shape_orientation_without_names(self):
        abstract = {
            "up": jax.ShapeDtypeStruct((32, 128), jnp.float32),
            "downward": jax.ShapeDtypeStruct((128, 32), jnp.float32),
        }
        axes = infer_logical_axes(abstract)
        assert axes["up"] == ("embed", "mlp")
        assert axes["downward"] == ("mlp", "embed")

    def test_vocab_requires_size_or_falls_back(self):
        abstract = jax.eval_shape(stacked_init, jax.random.key(0))
        axes = infer_logical_axes(abstract)  # no vocab_size
        # no silent vocab guess: embeds fall back to embed-only
        assert "vocab" not in (axes["wte"] + axes["lm_head"])


class TestStackLayerParams:
    def test_roundtrip(self):
        params = third_party_init(jax.random.key(0))
        stacked, unstack = stack_layer_params(params)
        assert stacked["layers"]["q_proj"].shape == (LAYERS, DIM, DIM)
        assert "block_0" not in stacked
        back = unstack(stacked)
        for k in params:
            for a, b in zip(
                jax.tree.leaves(params[k]), jax.tree.leaves(back[k])
            ):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_rejects_unstackable(self):
        with pytest.raises(ValueError):
            stack_layer_params({"w": jnp.zeros((2, 2))})


class TestAccelerateThirdPartyModel:
    def _spec(self):
        return StackedModule(
            init_fn=stacked_init,
            embed_fn=embed_fn,
            layer_fn=layer_fn,
            head_loss_fn=head_loss_fn,
            n_microbatches=2,
        )

    def _tokens(self, batch=8):
        return jnp.asarray(
            np.random.RandomState(0).randint(0, VOCAB, (batch, 17))
        )

    def test_fsdp_tensor_pipe_no_handwritten_axes(self):
        strategy = Strategy(
            mesh=MeshConfig(pipe=2, data=1, fsdp=2, tensor=2),
            compute_dtype="float32", remat="none", donate=False,
        )
        res = accelerate_module(
            self._spec(), optax.adam(1e-2), strategy=strategy,
            vocab_size=VOCAB,
        )
        # derived shardings actually use the mesh: fsdp + tensor on the
        # layer weights, layer stack sharded over pipe
        q = res.state.params["layers"]["q_proj"]
        spec_axes = set()
        for part in tuple(q.sharding.spec):
            spec_axes.update(
                (part,) if isinstance(part, str) else (part or ())
            )
        assert "pipe" in spec_axes, q.sharding
        assert {"fsdp", "tensor"} & spec_axes, q.sharding
        state = res.state
        losses = []
        for i in range(4):
            state, metrics = res.train_step(
                state, {"tokens": self._tokens()}, jax.random.key(i)
            )
            losses.append(float(metrics["loss"]))
        assert all(np.isfinite(losses))
        assert losses[-1] < losses[0]

    def test_matches_unsharded_training(self):
        """The derived sharding must not change the math: one dp-only
        step equals one fsdp+tensor+pipe step."""
        tokens = self._tokens()

        def run(mesh_cfg):
            strategy = Strategy(
                mesh=mesh_cfg, compute_dtype="float32", remat="none",
                donate=False,
            )
            res = accelerate_module(
                self._spec(), optax.sgd(0.1), strategy=strategy,
                vocab_size=VOCAB,
            )
            state, m = res.train_step(
                res.state, {"tokens": tokens}, jax.random.key(0)
            )
            return float(m["loss"]), state.params

        loss_dp, p_dp = run(MeshConfig())
        loss_3d, p_3d = run(MeshConfig(pipe=2, data=1, fsdp=2, tensor=2))
        assert abs(loss_dp - loss_3d) < 1e-4
        for a, b in zip(jax.tree.leaves(p_dp), jax.tree.leaves(p_3d)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=2e-4
            )


class TestStackFamilySelection:
    def test_raw_weight_family_not_mistaken_for_layers(self):
        params = {
            "w1": jnp.zeros((4, 4)), "w2": jnp.zeros((4, 4)),
            "w3": jnp.zeros((4, 4)),
            "block_0": {"k": jnp.zeros((4,))},
            "block_1": {"k": jnp.zeros((4,))},
        }
        stacked, _ = stack_layer_params(params)
        assert stacked["layers"]["k"].shape == (2, 4)
        assert "w1" in stacked and "w2" in stacked

    def test_layerish_raw_family_still_stacks(self):
        params = {
            "h_0": jnp.zeros((4, 4)), "h_1": jnp.zeros((4, 4)),
            "head": jnp.zeros((4,)),
        }
        stacked, _ = stack_layer_params(params)
        assert stacked["layers"].shape == (2, 4, 4)

    def test_trailing_h_prefix_not_layerish(self):
        params = {
            "branch_0": jnp.zeros((4, 4)), "branch_1": jnp.zeros((4, 4)),
            "branch_2": jnp.zeros((4, 4)),
            "block_0": {"k": jnp.zeros((4,))},
            "block_1": {"k": jnp.zeros((4,))},
        }
        stacked, _ = stack_layer_params(params)
        assert stacked["layers"]["k"].shape == (2, 4)
        assert "branch_0" in stacked

    def test_into_collision_raises(self):
        params = {
            "layers": {"shared": jnp.zeros((4,))},
            "block_0": {"k": jnp.zeros((4,))},
            "block_1": {"k": jnp.zeros((4,))},
        }
        with pytest.raises(ValueError, match="clobbered"):
            stack_layer_params(params)
        stacked, _ = stack_layer_params(params, into="stack")
        assert "layers" in stacked and "stack" in stacked
