"""reference.py against the program's models at tiny size on the CPU,
both in float32: two independent implementations of the same
mathematics agree to float32 rounding."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import reference

MISTRAL = {
    "hidden_size": 64, "intermediate_size": 160, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_hidden_layers": 3, "vocab_size": 256,
    "sliding_window": 4096, "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
}
GPT2 = {
    "n_embd": 64, "n_head": 4, "n_layer": 3, "n_positions": 96,
    "vocab_size": 300, "layer_norm_epsilon": 1e-5,
}


def _tokens(vocab, seq=48):
    return jnp.asarray(
        np.random.RandomState(5).randint(0, vocab, (seq + 1,)), jnp.int32
    )


def test_mistral_matches_models_llama():
    from dlrover_tpu.models import llama

    config = llama.LlamaConfig(
        vocab_size=256, dim=64, n_layers=3, n_heads=4, n_kv_heads=2,
        mlp_dim=160, max_seq_len=64, dtype="float32",
        attn_impl="reference", remat=False,
    )
    params = llama.llama_init(config, jax.random.key(1))
    tokens = _tokens(256)
    with jax.default_matmul_precision("highest"):
        got = llama.llama_apply(config, params, tokens[None, :-1])[0]
        loss = llama.llama_loss_fn(config)(
            params, {"tokens": tokens[None]}, jax.random.key(0)
        )
    want = reference.mistral_logits(MISTRAL, params, tokens[:-1])
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(
        loss, reference.next_token_loss(want, tokens), rtol=1e-5
    )


def test_mistral_sliding_window_masks_old_keys():
    from dlrover_tpu.models import llama

    config = llama.LlamaConfig(
        vocab_size=256, dim=64, n_layers=3, n_heads=4, n_kv_heads=2,
        mlp_dim=160, max_seq_len=64,
    )
    params = llama.llama_init(config, jax.random.key(1))
    tokens = _tokens(256)
    wide = reference.mistral_logits(MISTRAL, params, tokens[:-1])
    narrow = reference.mistral_logits(
        dict(MISTRAL, sliding_window=8), params, tokens[:-1]
    )
    # the first 8 positions see the same keys either way, later ones do not
    np.testing.assert_allclose(wide[:8], narrow[:8], atol=1e-5)
    assert float(jnp.abs(wide[8:] - narrow[8:]).max()) > 1e-3


def test_gpt2_matches_models_gpt2():
    from dlrover_tpu.models import gpt2

    config = gpt2.GPT2Config(
        vocab_size=300, dim=64, n_layers=3, n_heads=4, mlp_dim=256,
        max_seq_len=96, dtype="float32", attn_impl="reference",
    )
    params = gpt2.gpt2_init(config, jax.random.key(2))
    # biases and norm offsets start at zero: move them so they count
    leaves, treedef = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.key(3), len(leaves))
    params = jax.tree.unflatten(treedef, [
        leaf + 0.05 * jax.random.normal(k, leaf.shape)
        for leaf, k in zip(leaves, keys)
    ])
    tokens = _tokens(300)
    with jax.default_matmul_precision("highest"):
        got = gpt2.gpt2_apply(config, params, tokens[None, :-1])[0]
        loss = gpt2.gpt2_loss_fn(config)(
            params, {"tokens": tokens[None]}, jax.random.key(0)
        )
    want = reference.gpt2_logits(GPT2, params, tokens[:-1])
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(
        loss, reference.next_token_loss(want, tokens), rtol=1e-5
    )


def test_limits_sit_between_the_precisions():
    """bf16 rounding of the logits alone passes, 4 bits of mantissa (an
    8-bit float type's) fail, and so does a loss that is off."""
    shallow = reference.tolerances("gpt2")
    rs = np.random.RandomState(0)
    ref = jnp.asarray(rs.randn(256, 512) * 1.3, jnp.float32)

    def rounded(x, bits):
        scale = 2.0 ** (jnp.floor(jnp.log2(jnp.abs(x))) - bits)
        return jnp.round(x / scale) * scale

    fine = reference.compare(rounded(ref, 8), 10.4, ref, 10.4, shallow)
    coarse = reference.compare(rounded(ref, 3), 10.4, ref, 10.4, shallow)
    assert fine["ok"] and not coarse["ok"]
    off = reference.compare(ref, 10.5, ref, 10.4, shallow)
    assert not off["ok"]
