"""Pallas op tests (interpret mode on the CPU test backend).

Mirrors the reference's op-level unit tests (atorch flash-attn wrappers
are tested against plain attention in atorch/atorch/tests)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.ops.attention import (
    flash_attention,
    flash_attention_bshd,
    mha_reference,
    window_block,
    window_computed_per_kept,
)
from dlrover_tpu.ops.cross_entropy import (
    softmax_cross_entropy,
    vocab_parallel_cross_entropy,
)
from dlrover_tpu.ops.quantization import dequantize_int8, quantize_int8


def _qkv(batch=1, heads=4, kv_heads=2, seq=128, dim=64, seed=0):
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(batch, heads, seq, dim), jnp.float32)
    k = jnp.asarray(rng.randn(batch, kv_heads, seq, dim), jnp.float32)
    v = jnp.asarray(rng.randn(batch, kv_heads, seq, dim), jnp.float32)
    return q, k, v


@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_forward(causal):
    q, k, v = _qkv()
    out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64)
    ref = mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-2)


def test_flash_attention_grads_match_reference():
    q, k, v = _qkv(seq=128)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, block_q=64, block_k=64) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v) ** 2)

    g = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, gr):
        scale = float(jnp.max(jnp.abs(b))) + 1e-6
        assert float(jnp.max(jnp.abs(a - b))) / scale < 5e-2


def test_flash_attention_fused_rope_matches_external():
    """Kernel-fused rope (rope_cos/rope_sin args) must match applying
    rope externally then calling plain attention — forward and all
    gradients, including GQA."""
    B, H, KVH, S, D = 2, 4, 2, 256, 128
    rng = np.random.RandomState(3)
    q = jnp.asarray(rng.randn(B, H, S, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, KVH, S, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, KVH, S, D), jnp.float32)
    half = D // 2
    freqs = np.exp(-np.log(10000.0) * np.arange(half) / half)
    ang = np.arange(S)[:, None] * freqs
    cos1 = jnp.asarray(np.cos(ang), jnp.float32)
    sin1 = jnp.asarray(np.sin(ang), jnp.float32)
    cos_f = jnp.broadcast_to(jnp.concatenate([cos1, cos1], -1), (B, S, D))
    sin_f = jnp.broadcast_to(jnp.concatenate([sin1, sin1], -1), (B, S, D))

    def ext_rope(x):
        c, s = cos1[None, None], sin1[None, None]
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], -1)

    def loss_fused(q, k, v):
        o = flash_attention(q, k, v, causal=True, block_q=128,
                            block_k=128, rope_cos=cos_f, rope_sin=sin_f)
        return jnp.sum(jnp.sin(o))

    def loss_ref(q, k, v):
        return jnp.sum(jnp.sin(
            mha_reference(ext_rope(q), ext_rope(k), v, causal=True)))

    lf, gf = jax.value_and_grad(loss_fused, (0, 1, 2))(q, k, v)
    lr, gr = jax.value_and_grad(loss_ref, (0, 1, 2))(q, k, v)
    np.testing.assert_allclose(float(lf), float(lr), rtol=1e-4)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-4)


def test_flash_attention_gqa_heads():
    q, k, v = _qkv(heads=8, kv_heads=2)
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    ref = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-2)


# latent attention's shape (models/glm_lite.py): 20 heads of 256 over as
# many key/value heads, causal, the backward at half the forward's rows
# as the model runs it; 256 and 1280 positions take the one-pass
# backward (flash_bwd_fused), as the cell's 8192 do, 1200 the dq / dkv
# pair (its last tile of 128 rows is padded)
@pytest.mark.parametrize("seq,block,bwd_block", [(256, 128, 64),
                                                 (1280, 256, 128),
                                                 (1200, 256, 128)])
def test_flash_attention_at_latent_attention_heads(seq, block, bwd_block):
    q, k, v = _qkv(heads=20, kv_heads=20, seq=seq, dim=256, seed=3)
    probe = jnp.asarray(np.random.RandomState(4).randn(*q.shape),
                        jnp.float32)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) * probe)

    kernel = loss(lambda q, k, v: flash_attention(
        q, k, v, block_q=block, block_k=block, bwd_block_q=bwd_block))
    plain = loss(lambda q, k, v: mha_reference(q, k, v))
    got = jax.value_and_grad(kernel, argnums=(0, 1, 2))(q, k, v)
    want = jax.value_and_grad(plain, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("fused", [True, False])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("heads,kv_heads", [(4, 4), (8, 2)])
def test_flash_attention_bshd_forward(causal, heads, kv_heads, fused):
    """The model-native [B,S,H,Dh] kernels match the BHSD reference."""
    q, k, v = _qkv(heads=heads, kv_heads=kv_heads)
    qs, ks, vs = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    out = flash_attention_bshd(qs, ks, vs, causal=causal,
                               block_q=64, block_k=64, fused=fused)
    ref = mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(
        np.asarray(out.transpose(0, 2, 1, 3)), np.asarray(ref), atol=2e-2
    )


@pytest.mark.parametrize("fused", [True, False])
@pytest.mark.parametrize("q_len,kv_len", [(128, 128), (96, 200)])
def test_flash_attention_bshd_grads_match_reference(q_len, kv_len, fused):
    rng = np.random.RandomState(7)
    q = jnp.asarray(rng.randn(2, 8, q_len, 64), jnp.float32)
    k = jnp.asarray(rng.randn(2, 2, kv_len, 64), jnp.float32)
    v = jnp.asarray(rng.randn(2, 2, kv_len, 64), jnp.float32)

    def loss_bshd(q, k, v):
        o = flash_attention_bshd(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), block_q=64, block_k=64, fused=fused)
        return jnp.sum(o ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v) ** 2)

    g = jax.grad(loss_bshd, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, gr):
        scale = float(jnp.max(jnp.abs(b))) + 1e-6
        assert float(jnp.max(jnp.abs(a - b))) / scale < 5e-2


# 96 rows in blocks of 64: the split kernels (delta, dq, dkv) and not
# the one-pass backward, which needs whole blocks
@pytest.mark.parametrize("layout,seq", [
    ("bhsd", 128), ("bhsd", 96), ("bshd", 128), ("bshdf", 128),
])
def test_backward_from_one_column_of_lse_is_bit_identical(layout, seq):
    """What is held from forward to backward is one column of the
    forward kernel's 128-lane row statistic (``_flash``). The backward
    kernels fed the operand rebuilt from that column give the same
    dq, dk, dv bit for bit as fed the kernel's own output: the lanes
    are copies, and every kernel reads column 0."""
    from dlrover_tpu.ops import attention as A

    H, KVH, D = 4, 2, 64
    q, k, v = _qkv(batch=2, heads=H, kv_heads=KVH, seq=seq, dim=D, seed=5)
    do = jnp.asarray(
        np.random.RandomState(6).randn(*q.shape), jnp.float32)
    if layout != "bhsd":
        q, k, v, do = (
            x.transpose(0, 2, 1, 3).reshape(2, seq, -1)
            for x in (q, k, v, do)
        )
    static = (layout, H, KVH, D ** -0.5, True, 64, 64, True)
    o, lse = A._fwd(q, k, v, *static)
    assert lse.shape[-1] == A.STATS_W
    rebuilt = jnp.broadcast_to(lse[..., :1], lse.shape)
    np.testing.assert_array_equal(np.asarray(lse), np.asarray(rebuilt))
    own = A._bwd(*static, (q, k, v, o, lse), do)
    again = A._bwd(*static, (q, k, v, o, rebuilt), do)
    for a, b in zip(own, again):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    if layout == "bhsd":
        # and through the public entry, whose residual is that column
        _, vjp = jax.vjp(
            lambda q, k, v: A.flash_attention(
                q, k, v, block_q=64, block_k=64),
            q, k, v,
        )
        for a, b in zip(own, vjp(do)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_softmax_cross_entropy_matches_optax():
    rng = np.random.RandomState(0)
    logits = jnp.asarray(rng.randn(4, 16, 64), jnp.float32)
    labels = jnp.asarray(rng.randint(0, 64, (4, 16)))
    loss, valid = softmax_cross_entropy(logits, labels)
    import optax

    ref = optax.softmax_cross_entropy_with_integer_labels(logits, labels)
    np.testing.assert_allclose(np.asarray(loss), np.asarray(ref), rtol=1e-5)
    assert bool(valid.all())


def test_softmax_cross_entropy_ignore_index():
    logits = jnp.zeros((2, 3, 8))
    labels = jnp.asarray([[0, -100, 2], [-100, 1, 3]])
    loss, valid = softmax_cross_entropy(logits, labels)
    assert int(valid.sum()) == 4
    assert float(loss[0, 1]) == 0.0


def test_vocab_parallel_cross_entropy():
    from jax.sharding import Mesh, PartitionSpec as P
    shard_map = jax.shard_map

    rng = np.random.RandomState(1)
    vocab, n_shard = 64, 4
    logits = jnp.asarray(rng.randn(8, vocab), jnp.float32)
    labels = jnp.asarray(rng.randint(0, vocab, (8,)))
    devices = np.array(jax.devices()[:n_shard])
    mesh = Mesh(devices, ("tensor",))
    f = shard_map(
        lambda lg, lb: vocab_parallel_cross_entropy(lg, lb)[0],
        mesh=mesh,
        in_specs=(P(None, "tensor"), P(None)),
        out_specs=P(None),
    )
    loss = f(logits, labels)
    ref, _ = softmax_cross_entropy(logits, labels)
    np.testing.assert_allclose(np.asarray(loss), np.asarray(ref), rtol=1e-4)


def test_quantize_roundtrip():
    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randn(1000) * 3, jnp.float32)
    q, scales, shape = quantize_int8(x, stochastic=False)
    out = dequantize_int8(q, scales, shape)
    assert out.shape == x.shape
    # error bounded by scale/2 per block
    max_scale = float(scales.max())
    assert float(jnp.max(jnp.abs(out - x))) <= max_scale * 0.51


@pytest.mark.parametrize(
    "shape",
    [(1000,), (1024, 256), (1000, 300)],
    ids=["one-tile", "two-whole-tiles", "ragged-last-tile"],
)
def test_quantize_row_tiles_match_one_block(shape, monkeypatch):
    """The kernels walk a grid of row tiles (one whole-array VMEM block
    is refused by the TPU compiler at real leaf sizes): whatever the
    tiling — a single short tile, whole tiles, a ragged last one — the
    result is bit for bit what one block over the whole array gives,
    and that is the plain blockwise absmax quantization."""
    from dlrover_tpu.ops import quantization

    rng = np.random.RandomState(5)
    x = jnp.asarray((rng.randn(*shape) * 3).astype(np.float32))
    q, scales, orig = quantize_int8(x, stochastic=False)
    out = dequantize_int8(q, scales, orig)
    monkeypatch.setattr(quantization, "TILE_ROWS", 1 << 30)
    q1, scales1, _ = quantize_int8(x, stochastic=False)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(q1))
    np.testing.assert_array_equal(np.asarray(scales), np.asarray(scales1))
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(dequantize_int8(q1, scales1, orig))
    )
    flat = np.asarray(x).reshape(-1)
    blocks = np.zeros((q.shape[0], quantization.BLOCK), np.float32)
    blocks.reshape(-1)[: flat.size] = flat
    absmax = np.abs(blocks).max(axis=-1, keepdims=True)
    np.testing.assert_allclose(
        np.asarray(scales), np.where(absmax == 0.0, 1.0, absmax / 127.0),
        rtol=1e-6,
    )
    assert float(jnp.max(jnp.abs(out - x))) <= float(scales.max()) * 0.51


def test_quantize_stochastic_unbiased():
    x = jnp.full((4096,), 0.35, jnp.float32)
    q, scales, shape = quantize_int8(x, seed=3, stochastic=True)
    out = dequantize_int8(q, scales, shape)
    # stochastic rounding preserves the mean
    assert abs(float(out.mean()) - 0.35) < 5e-3


def test_flash_attention_unequal_lengths_end_aligned_causal():
    """Decode-style q_len < kv_len: causality must be end-aligned."""
    rng = np.random.RandomState(3)
    q = jnp.asarray(rng.randn(1, 2, 16, 64), jnp.float32)
    k = jnp.asarray(rng.randn(1, 2, 128, 64), jnp.float32)
    v = jnp.asarray(rng.randn(1, 2, 128, 64), jnp.float32)
    out = flash_attention(q, k, v, causal=True, block_q=16, block_k=64)
    ref = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-2)


@pytest.mark.parametrize("q_len,kv_len", [(100, 100), (96, 200)])
def test_flash_attention_non_block_multiple_lengths(q_len, kv_len):
    """Padded tail rows/cols must not pollute the softmax."""
    rng = np.random.RandomState(4)
    q = jnp.asarray(rng.randn(1, 2, q_len, 64), jnp.float32)
    k = jnp.asarray(rng.randn(1, 2, kv_len, 64), jnp.float32)
    v = jnp.asarray(rng.randn(1, 2, kv_len, 64), jnp.float32)
    for causal in (True, False):
        out = flash_attention(
            q, k, v, causal=causal, block_q=64, block_k=64
        )
        ref = mha_reference(q, k, v, causal=causal)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-2
        )
    g = jax.grad(
        lambda *a: jnp.sum(
            flash_attention(*a, causal=True, block_q=64, block_k=64) ** 2
        ),
        argnums=(0, 1, 2),
    )(q, k, v)
    gr = jax.grad(
        lambda *a: jnp.sum(mha_reference(*a, causal=True) ** 2),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b in zip(g, gr):
        scale = float(jnp.max(jnp.abs(b))) + 1e-6
        assert float(jnp.max(jnp.abs(a - b))) / scale < 5e-2


def _masked_reference(q, k, v, window=None, prefix=None):
    """Dense reference for the causal mask family: visibility =
    (causal & in-window) | in-prefix, end-aligned for q_len != kv_len
    (matches _causal_mask's documented semantics)."""
    B, H, Sq, D = q.shape
    KVH = k.shape[1]
    Skv = k.shape[2]
    rep = H // KVH
    kk = jnp.repeat(k, rep, axis=1)
    vv = jnp.repeat(v, rep, axis=1)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, kk) * (D ** -0.5)
    rows = jnp.arange(Sq)[:, None]
    cols = jnp.arange(Skv)[None, :]
    offset = Skv - Sq
    vis = cols <= offset + rows
    if window is not None:
        vis &= cols > offset + rows - window
    if prefix is not None:
        vis |= cols < prefix
    scores = jnp.where(vis[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    # fully-masked rows (possible only in pathological configs) -> 0
    probs = jnp.where(jnp.isnan(probs), 0.0, probs)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, vv)


class TestWindowPrefixMasks:
    """Sliding-window / prefix-LM mask coverage (tile-liveness, mask and
    p-zero math across _tile_meta_impl/_mask_needed/_needs_p_zero and
    the kernels)."""

    CASES = [
        # (q_len, kv_len, window, prefix, configured block) — aligned,
        # unaligned, cross-lengths, window=1 (hazard path), composition;
        # the kernels take window_block's tiles, which at a configured
        # 64 are 64 and at 1024 with a window of 256 the window's own
        (128, 128, 64, None, 64),
        (128, 128, 1, None, 64),
        (100, 100, 48, None, 64),
        (96, 200, 64, None, 64),
        (128, 128, None, 32, 64),
        (100, 100, None, 17, 64),
        (96, 200, None, 40, 64),
        (128, 128, 48, 32, 64),
        (100, 100, 33, 17, 64),
        (96, 200, 48, 40, 64),
        (128, 128, 1, 1, 64),
        (1024, 1024, 256, None, 1024),
    ]

    # the first four entries name a case, as before the fifth
    @pytest.mark.parametrize(
        "q_len,kv_len,window,prefix,block", CASES,
        ids=["-".join(map(str, c[:4])) for c in CASES],
    )
    def test_forward_matches_dense_mask(self, q_len, kv_len, window,
                                        prefix, block):
        rng = np.random.RandomState(7)
        q = jnp.asarray(rng.randn(1, 4, q_len, 64), jnp.float32)
        k = jnp.asarray(rng.randn(1, 2, kv_len, 64), jnp.float32)
        v = jnp.asarray(rng.randn(1, 2, kv_len, 64), jnp.float32)
        block = window_block(block, window)
        out = flash_attention(
            q, k, v, causal=True, block_q=block, block_k=block,
            window=window, prefix_len=prefix,
        )
        ref = _masked_reference(q, k, v, window=window, prefix=prefix)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-2
        )

    GRAD_CASES = [
        (128, 128, 64, None, 64),
        (128, 128, 1, None, 64),
        (100, 100, 48, None, 64),
        (96, 200, 64, None, 64),
        (128, 128, None, 32, 64),
        (100, 100, 33, 17, 64),
        (1024, 1024, 256, None, 1024),
    ]

    @pytest.mark.parametrize(
        "q_len,kv_len,window,prefix,block", GRAD_CASES,
        ids=["-".join(map(str, c[:4])) for c in GRAD_CASES],
    )
    def test_grads_match_dense_mask(self, q_len, kv_len, window, prefix,
                                    block):
        rng = np.random.RandomState(8)
        q = jnp.asarray(rng.randn(1, 2, q_len, 64), jnp.float32)
        k = jnp.asarray(rng.randn(1, 2, kv_len, 64), jnp.float32)
        v = jnp.asarray(rng.randn(1, 2, kv_len, 64), jnp.float32)
        block = window_block(block, window)

        g = jax.grad(
            lambda *a: jnp.sum(flash_attention(
                *a, causal=True, block_q=block, block_k=block,
                window=window, prefix_len=prefix,
            ) ** 2),
            argnums=(0, 1, 2),
        )(q, k, v)
        gr = jax.grad(
            lambda *a: jnp.sum(
                _masked_reference(*a, window=window, prefix=prefix) ** 2
            ),
            argnums=(0, 1, 2),
        )(q, k, v)
        for a, b in zip(g, gr):
            # floor the scale: at window=1 the true dq/dk are exactly 0
            # (softmax over one element) and only float-cancellation
            # residue remains — a pure relative metric degenerates
            scale = max(float(jnp.max(jnp.abs(b))), 1e-3)
            assert float(jnp.max(jnp.abs(a - b))) / scale < 5e-2

    def test_bshd_window_matches(self):
        rng = np.random.RandomState(9)
        q = jnp.asarray(rng.randn(1, 128, 4, 64), jnp.float32)
        k = jnp.asarray(rng.randn(1, 128, 2, 64), jnp.float32)
        v = jnp.asarray(rng.randn(1, 128, 2, 64), jnp.float32)
        out = flash_attention_bshd(
            q, k, v, causal=True, block_q=64, block_k=64,
            window=48, prefix_len=16,
        )
        ref = _masked_reference(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), window=48, prefix=16,
        ).transpose(0, 2, 1, 3)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-2
        )


@pytest.mark.parametrize("window,block,want", [
    (512, 1024, 512),      # laguna-xs.2's window layers
    (4096, 1024, 1024),    # a window as wide as the block keeps it
    (48, 1024, 128),       # never below 128
    (300, 1024, 512),      # 384 does not divide 1024
    (None, 1024, 1024),    # no window: the configured block
    (48, 64, 64),          # never above the configured block
])
def test_window_block(window, block, want):
    assert window_block(block, window) == want


@pytest.mark.parametrize("block,low,high", [
    (1024, 3.87, 3.871),   # the configured tiles: the band's 15 tiles
    (window_block(1024, 512), 1.99, 2.01),
])
def test_window_computed_per_kept_at_laguna_shape(block, low, high):
    """laguna-xs.2's window layers: 8192 positions, a window of 512."""
    assert low <= window_computed_per_kept(8192, 512, block, block) <= high


# Each cell's attention backward as the kernels see it: positions, head
# size, a tile's rows and columns, rope inside the kernels; and whether
# the one-pass kernel's count fits the scoped VMEM a kernel gets unasked
ONEPASS_CELLS = {
    "mistral-7b": ((2048, 128, 1024, 1024, True), True),
    "gpt2-xl": ((1024, 64, 512, 512, False), True),
    "granite-4.0-h-micro": ((8192, 64, 1024, 1024, False), False),
    "zaya1-8b": ((8192, 128, 1024, 1024, False), False),
    "olmo-hybrid-7b": ((8192, 128, 1024, 1024, False), False),
    "laguna-xs.2-full": ((8192, 128, 1024, 1024, False), False),
    "laguna-xs.2-window": ((8192, 128, 512, 512, False), True),
    "glm-4.7-flash": ((8192, 256, 512, 1024, False), False),
}


@pytest.mark.parametrize("cell", sorted(ONEPASS_CELLS))
def test_onepass_vmem_at_the_cells(cell):
    """Every cell's backward takes the one-pass kernel; where its count
    fits the default the compiler params are the parent's, exactly
    (mistral-7b and gpt2-xl keep their step programs), and elsewhere
    the call asks for more than the count and at most the ceiling."""
    from dlrover_tpu.ops import attention as A

    shape, fits_default = ONEPASS_CELLS[cell]
    vmem = A._onepass_vmem_bytes(*shape[:4], jnp.bfloat16, shape[4])
    assert vmem <= A._VMEM_CEILING
    params = A._onepass_params(vmem)
    assert (vmem <= A._VMEM_DEFAULT) == fits_default
    if fits_default:
        assert params == A._compiler_params(
            ("parallel", "parallel", "arbitrary"))
    else:
        assert vmem < params.vmem_limit_bytes <= A._VMEM_CEILING


def _bwd_path(q_len, head_dim, block_q, block_k, heads=2, kv_heads=1,
              dtype=jnp.bfloat16):
    """The labels and value of the ``model.attn.bwd_vmem_bytes`` gauge
    the backward sets as it is traced (nothing runs)."""
    from dlrover_tpu.common import telemetry
    from dlrover_tpu.ops import attention as A

    q = jax.ShapeDtypeStruct((1, heads, q_len, head_dim), dtype)
    kv = jax.ShapeDtypeStruct((1, kv_heads, q_len, head_dim), dtype)
    lse = jax.ShapeDtypeStruct((1, heads, q_len, A.STATS_W), jnp.float32)
    telemetry.enable("test")
    try:
        jax.eval_shape(
            lambda q, k, v, lse: A._bwd(
                "bhsd", heads, kv_heads, head_dim ** -0.5, True, block_q,
                block_k, True, (q, k, v, q, lse), q),
            q, kv, kv, lse)
        gauges = [g for g in telemetry.snapshot()["gauges"]
                  if g["name"] == "model.attn.bwd_vmem_bytes"]
    finally:
        telemetry.install_from_env()
    assert len(gauges) == 1
    return gauges[0]["labels"]["path"], gauges[0]["value"]


@pytest.mark.parametrize("q_len,head_dim,block_q,block_k,path", [
    (8192, 256, 512, 1024, "onepass"),      # glm-4.7-flash's
    (16384, 256, 512, 1024, "onepass"),
    (32768, 256, 512, 1024, "split"),       # the count past the ceiling
    (65536, 128, 1024, 1024, "split"),
    (1200, 256, 128, 256, "split"),         # a padded last tile
])
def test_backward_path_follows_the_count(q_len, head_dim, block_q, block_k,
                                         path):
    """The backward takes the one-pass kernel exactly where its count
    lies under the ceiling (and the tiles divide the lengths), and the
    gauge carries the path and the count."""
    from dlrover_tpu.ops import attention as A

    vmem = A._onepass_vmem_bytes(q_len, head_dim, block_q, block_k,
                                 jnp.bfloat16, False)
    assert _bwd_path(q_len, head_dim, block_q, block_k) == (path, vmem)
    if q_len % block_q == 0:
        assert (vmem <= A._VMEM_CEILING) == (path == "onepass")


# shapes whose q_len x head size passed the 2048 x 128 the one-pass
# backward was held to before it counted its VMEM: laguna-xs.2's window
# of 512 in its tiles, zaya1-8b's four query heads a key/value head
@pytest.mark.parametrize("window,block", [(512, 512), (None, 512)])
def test_onepass_backward_past_2048_by_128(window, block):
    q, k, v = _qkv(heads=8, kv_heads=2, seq=2560, dim=128, seed=11)
    probe = jnp.asarray(np.random.RandomState(12).randn(*q.shape),
                        jnp.float32)
    assert _bwd_path(2560, 128, block, block, heads=8, kv_heads=2,
                     dtype=jnp.float32)[0] == "onepass"
    got = jax.value_and_grad(
        lambda q, k, v: jnp.sum(probe * flash_attention(
            q, k, v, block_q=block, block_k=block, window=window)),
        argnums=(0, 1, 2))(q, k, v)
    want = jax.value_and_grad(
        lambda q, k, v: jnp.sum(
            probe * _masked_reference(q, k, v, window=window)),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)
