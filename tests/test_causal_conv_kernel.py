"""The causal convolution's kernel pair (ops/causal_conv.py, interpret
mode) against the plain form it replaces, ``silu(causal_conv1d(...))``
differentiated by JAX, and the rule that picks between them
(ops/ssd.py:causal_conv_silu)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.common import telemetry
from dlrover_tpu.ops import causal_conv, ssd
from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh

TAPS = 4


def _plain(x, weight, bias, first=0):
    x = x[..., first:first + weight.shape[1]]
    return jax.nn.silu(ssd.causal_conv1d(x, weight, bias)).astype(x.dtype)


def _operands(batch, seq, channels, dtype, seed=0, width=None):
    """x (``width`` channels, the convolution's or more), weight, bias
    and the output's cotangent; the taps at the model's initial scale
    (uniform in +-1/2)."""
    keys = jax.random.split(jax.random.key(seed), 4)
    x = jax.random.normal(keys[0], (batch, seq, width or channels), dtype)
    weight = jax.random.uniform(keys[1], (TAPS, channels), dtype, -0.5, 0.5)
    bias = (0.1 * jax.random.normal(keys[2], (channels,))).astype(dtype)
    dy = jax.random.normal(keys[3], (batch, seq, channels), dtype)
    return x, weight, bias, dy


def _output_and_gradients(fn, x, weight, bias, dy):
    """y, and dx, dw, dbias of ``sum(y * dy)``: through the activation."""
    def loss(x, weight, bias):
        y = fn(x, weight, bias)
        return jnp.sum(y.astype(jnp.float32) * dy.astype(jnp.float32)), y

    (_, y), grads = jax.jit(
        jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)
    )(x, weight, bias)
    return dict(zip(("y", "dx", "dw", "dbias"), (y, *grads)))


def _assert_close(got, want, dtype, what):
    assert got.dtype == want.dtype == dtype, what
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    if dtype == jnp.float32:
        limit = 1e-5 * np.maximum(np.abs(want), 1.0)
    else:
        # one rounding: neighbours in bf16 (8 bits of significand)
        limit = 2.0 ** -7 * np.maximum(np.abs(want), 2.0 ** -20)
    worst = np.max(np.abs(got - want) / limit)
    assert worst <= 1.0, f"{what}: {worst:.3g} of its limit"


# batch, sequence, channels, the first of them in x, x's channels,
# (channels, positions) a block: 1, 2 and 3 sequence blocks, a
# convolution over all of x and over the middle of it
SHAPES = [(1, 128, 16, 0, 16, (16, 128)), (2, 256, 48, 32, 96, (16, 128)),
          (1, 384, 32, 0, 32, (32, 128)), (2, 384, 32, 16, 80, (16, 128))]


@pytest.fixture(scope="module")
def results():
    """Both forms' outputs and gradients, computed once a shape and
    dtype and asserted a part at a time."""
    cache = {}

    def get(dtype, shape):
        key = (jnp.dtype(dtype).name, shape)
        if key not in cache:
            batch, seq, channels, first, width, blocks = shape
            operands = _operands(batch, seq, channels, dtype, width=width)

            def kernel(x, weight, bias):
                return causal_conv.causal_conv_silu_kernel(
                    x, weight, bias, first=first, fwd_blocks=blocks,
                    bwd_blocks=blocks)

            def plain(x, weight, bias):
                return _plain(x, weight, bias, first)

            cache[key] = (_output_and_gradients(kernel, *operands),
                          _output_and_gradients(plain, *operands))
        return cache[key]

    return get


@pytest.mark.parametrize("what", ["y", "dx", "dw", "dbias"])
@pytest.mark.parametrize("shape", SHAPES,
                         ids=lambda s: "x".join(map(str, s[:5])))
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_kernel_is_the_plain_form(results, dtype, shape, what):
    got, want = results(dtype, shape)
    _assert_close(got[what], want[what], dtype, what)


def _by_hand(x, weight, bias):
    """Position by position, in numpy: rows before the start are zero."""
    pre = np.zeros_like(x) + bias
    for t in range(x.shape[1]):
        for k in range(TAPS):
            if t - (TAPS - 1) + k >= 0:
                pre[:, t] += weight[k] * x[:, t - (TAPS - 1) + k]
    return pre / (1.0 + np.exp(-pre))


def _kernel(blocks):
    def kernel(x, weight, bias):
        return causal_conv.causal_conv_silu_kernel(
            x, weight, bias, fwd_blocks=blocks, bwd_blocks=blocks)
    return kernel


def test_first_positions_read_zeros_and_a_block_reads_the_one_before():
    x, weight, bias, _ = _operands(2, 384, 16, jnp.float32, seed=1)
    got = _kernel((16, 128))(x, weight, bias)
    want = _by_hand(*(np.asarray(a) for a in (x, weight, bias)))
    # the sequence's first positions, each block's first, and the rest
    for rows in (slice(0, 3), slice(128, 131), slice(256, 259), slice(None)):
        np.testing.assert_allclose(got[:, rows], want[:, rows], atol=1e-5)


def test_more_taps_than_four():
    keys = jax.random.split(jax.random.key(2), 3)
    x = jax.random.normal(keys[0], (1, 256, 16))
    weight = jax.random.normal(keys[1], (9, 16))
    bias = jax.random.normal(keys[2], (16,))
    dy = jnp.ones_like(x)
    got = _output_and_gradients(_kernel((16, 128)), x, weight, bias, dy)
    want = _output_and_gradients(_plain, x, weight, bias, dy)
    for what in got:
        _assert_close(got[what], want[what], jnp.float32, what)
    with pytest.raises(ValueError, match="128 taps"):
        causal_conv.causal_conv_silu_kernel(
            x, jnp.zeros((causal_conv.MAX_TAPS + 1, 16)), bias)


def _no_columns(ref, block):
    """The fault: a halo that always reads zero."""
    return jnp.zeros(ref.shape[1:], jnp.float32)


@pytest.mark.parametrize("halo,wrong,right", [
    ("_cols_before", ("y", "dx", "dw"), ()),
    # the positions after a block feed the gradient of x alone
    ("_cols_after", ("dx",), ("y", "dw", "dbias")),
])
def test_planted_zeroed_halo_fails(monkeypatch, halo, wrong, right):
    operands = _operands(1, 256, 16, jnp.float32, seed=3)
    want = _output_and_gradients(_plain, *operands)
    monkeypatch.setattr(causal_conv, halo, _no_columns)
    got = _output_and_gradients(_kernel((16, 128)), *operands)
    for what in wrong:
        with pytest.raises(AssertionError):
            _assert_close(got[what], want[what], jnp.float32, what)
    for what in right:
        _assert_close(got[what], want[what], jnp.float32, what)
    # only where a block reads its neighbour: the first block's output
    # needs no halo
    _assert_close(got["y"][:, :128], want["y"][:, :128], jnp.float32, "y")


def test_blocks_must_tile_the_input():
    x, weight, bias, _ = _operands(1, 256, 64, jnp.float32, width=96)
    for blocks in ((24, 128), (48, 128), (16, 192), (16, 64)):
        with pytest.raises(ValueError, match="do not tile"):
            _kernel(blocks)(x[..., :64], weight, bias)
    # a block of channels must also divide the first channel
    with pytest.raises(ValueError, match="do not tile"):
        causal_conv.causal_conv_silu_kernel(
            x, weight[:, :32], bias[:32], first=16, fwd_blocks=(32, 128))
    with pytest.raises(ValueError, match="are not tiled"):
        causal_conv.causal_conv_silu_kernel(x, weight, bias, first=48)
    assert causal_conv._largest_block(8192, 128, 2048) == 2048
    assert causal_conv._largest_block(4352, 16, 64, also=4096) == 64
    assert causal_conv._largest_block(4352, 16, 256, also=4096 + 64) == 64
    assert causal_conv._largest_block(48, 16, 64, also=32) == 16
    assert causal_conv._largest_block(8192 + 128, 128, 2048) == 1664


# ------------------------------------------------------------- dispatch


def _impl_traced(fn, *operands):
    """Which form ``fn`` traced, by the gauge and by the jaxpr."""
    telemetry.enable("test")
    try:
        jaxpr = str(jax.make_jaxpr(fn)(*operands))
        impls = [g["labels"]["impl"]
                 for g in telemetry.snapshot()["gauges"]
                 if g["name"] == "model.conv.impl"]
    finally:
        telemetry.install_from_env()
    assert len(impls) == 1, impls
    assert ("pallas_call" in jaxpr) == (impls[0] == "kernel")
    return impls[0]


@pytest.mark.parametrize("shape,first,impl", [
    ((2, 128, 16), 0, "kernel"), ((1, 256, 96), 32, "kernel"),
    # toy widths, a width that is not whole sublane tiles, a sequence
    # that is not whole lane tiles, a first channel inside a tile
    ((2, 128, 3), 0, "plain"), ((2, 128, 200), 0, "plain"),
    ((2, 96, 16), 0, "plain"), ((1, 128, 96), 8, "plain"),
])
def test_dispatch_reads_the_shape(shape, first, impl):
    x, weight, bias, _ = _operands(*shape, jnp.float32)
    weight, bias = weight[:, first:], bias[first:]
    conv = functools.partial(ssd.causal_conv_silu, first=first)
    assert _impl_traced(conv, x, weight, bias) == impl
    np.testing.assert_allclose(
        conv(x, weight, bias), _plain(x, weight, bias, first), atol=1e-5)


@pytest.mark.parametrize("mesh,impl", [
    ({"data": 2, "fsdp": 2, "tensor": 2}, "kernel"),
    ({"fsdp": 4, "tensor": 2}, "kernel"),
    # a halo across sequence shards is not built
    ({"data": 2, "seq": 4}, "plain"),
    # the batch does not divide over the batch axes
    ({"data": 8}, "plain"),
])
def test_dispatch_reads_the_mesh(mesh, impl):
    """On a mesh that splits the batch the kernels are mapped over its
    batch axes, and the taps' gradients are summed over them."""
    operands = _operands(4, 128, 16, jnp.float32, seed=4, width=48)
    conv = functools.partial(ssd.causal_conv_silu, first=16)
    want = _output_and_gradients(
        functools.partial(_plain, first=16), *operands)
    with build_mesh(MeshConfig(**mesh)):
        assert _impl_traced(conv, *operands[:3]) == impl
        got = _output_and_gradients(conv, *operands)
    for what in got:
        _assert_close(got[what], want[what], jnp.float32, what)
