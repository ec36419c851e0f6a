"""The chunked scan's kernel pair (ops/ssd_kernel.py, interpret mode)
against the plain form it replaces (ops/ssd.py:ssd_scan_plain
differentiated by JAX), the rule that picks between them
(ops/ssd.py:ssd_scan), and where the pair lands in a hybrid's step."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax._src.interpreters import partial_eval as pe

from dlrover_tpu.common import telemetry
from dlrover_tpu.models import granite_hybrid as gh
from dlrover_tpu.ops import ssd, ssd_kernel
from dlrover_tpu.parallel import MeshConfig, Strategy
from dlrover_tpu.parallel.accelerate import auto_accelerate
from dlrover_tpu.parallel.mesh import build_mesh

CHUNK, HEAD, STATE = 128, 16, 128
PARTS = ("y", "dx", "ddt", "da", "db", "dc", "dd")


def _operands(batch, seq, heads, groups, dtype, seed=0, head=HEAD,
              state=STATE):
    """x, dt, a, b, c, d and the output's cotangent; step sizes and
    decays in the model's initial range (dt log-uniform in [0.001,
    0.1], A = -(1..heads))."""
    keys = jax.random.split(jax.random.key(seed), 6)
    x = jax.random.normal(keys[0], (batch, seq, heads, head), dtype)
    dt = jnp.exp(jax.random.uniform(
        keys[1], (batch, seq, heads), minval=np.log(1e-3), maxval=np.log(0.1)))
    a = -jnp.arange(1, heads + 1, dtype=jnp.float32)
    b = (0.5 * jax.random.normal(keys[2], (batch, seq, groups, state))
         ).astype(dtype)
    c = (0.5 * jax.random.normal(keys[3], (batch, seq, groups, state))
         ).astype(dtype)
    d = 1.0 + 0.1 * jax.random.normal(keys[4], (heads,))
    dy = jax.random.normal(keys[5], (batch, seq, heads, head), dtype)
    return (x, dt, a, b, c, d), dy


def _plain(*operands, chunk=CHUNK):
    return ssd.ssd_scan_plain(*operands, chunk)


def _kernel(*operands, chunk=CHUNK):
    return ssd_kernel.ssd_scan_kernel(*operands, chunk)


def _output_and_gradients(fn, operands, dy):
    """y and the six gradients of ``sum(y * dy)``."""
    def loss(*operands):
        y = fn(*operands)
        return jnp.sum(y.astype(jnp.float32) * dy.astype(jnp.float32)), y

    (_, y), grads = jax.jit(jax.value_and_grad(
        loss, argnums=tuple(range(6)), has_aux=True))(*operands)
    return dict(zip(PARTS, (y, *grads)))


def _assert_close(got, want, dtype, what):
    """``y`` element by element (the forward pass rounds where the
    plain form does); a gradient by its relative rms distance: the
    kernel's backward sums the same products in another order, and in
    bf16 rounds them at other places than JAX's transpose does (against
    float32 both are equally far: PERF.md, PR 33)."""
    assert got.dtype == want.dtype and got.shape == want.shape, what
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    if what == "y":
        ulp = 1e-5 if dtype == jnp.float32 else 2.0 ** -7
        worst = np.max(
            np.abs(got - want) / (ulp * np.maximum(np.abs(want), 1.0)))
    else:
        limit = 3e-5 if dtype == jnp.float32 else 2e-2
        worst = np.sqrt(np.mean((got - want) ** 2)) / (
            limit * np.sqrt(np.mean(want ** 2)))
    assert worst <= 1.0, f"{what}: {worst:.3g} of its limit"


# batch, sequence, heads, groups: one block of 8 heads over 3 chunks;
# two groups, a block each, two batch rows; three blocks a group; a
# block of 16 heads a group
SHAPES = [(1, 384, 8, 1), (2, 384, 16, 2), (1, 256, 24, 1), (2, 256, 32, 2)]


@pytest.fixture(scope="module")
def results():
    """Both forms' outputs and gradients, computed once a shape and
    dtype and asserted a part at a time."""
    cache = {}

    def get(dtype, shape):
        key = (jnp.dtype(dtype).name, shape)
        if key not in cache:
            operands, dy = _operands(*shape, dtype)
            cache[key] = (_output_and_gradients(_kernel, operands, dy),
                          _output_and_gradients(_plain, operands, dy))
        return cache[key]

    return get


@pytest.mark.parametrize("what", PARTS)
@pytest.mark.parametrize("shape", SHAPES,
                         ids=lambda s: "x".join(map(str, s)))
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_kernel_is_the_plain_form(results, dtype, shape, what):
    got, want = results(dtype, shape)
    _assert_close(got[what], want[what], dtype, what)


@pytest.mark.parametrize("heads,groups,head,chunk,dtype,block", [
    # the cell: 16 heads a grid step; in float32 their block is too
    # large; three blocks of 8 a group; 8 heads a group
    (64, 1, 64, 256, jnp.bfloat16, 16), (64, 1, 64, 256, jnp.float32, 8),
    (24, 1, 16, 128, jnp.float32, 8), (16, 2, 16, 128, jnp.float32, 8),
    (64, 2, 16, 128, jnp.float32, 16),
])
def test_heads_a_step(heads, groups, head, chunk, dtype, block):
    x = jax.ShapeDtypeStruct((1, chunk, heads, head), dtype)
    assert ssd_kernel._head_block(x, groups, chunk) == block


def _recurrence(x, dt, a, b, c, d):
    """Position by position, in numpy (one group)."""
    batch, seq, heads, head = x.shape
    state = np.zeros((batch, heads, head, b.shape[-1]))
    y = np.zeros(x.shape)
    for t in range(seq):
        decay = np.exp(dt[:, t] * a)[:, :, None, None]
        state = decay * state + (dt[:, t, :, None] * x[:, t])[..., None] \
            * b[:, t, 0][:, None, None, :]
        y[:, t] = np.einsum("bhpn,bn->bhp", state, c[:, t, 0]) \
            + d[:, None] * x[:, t]
    return y


def test_kernel_is_the_recurrence():
    operands, _ = _operands(1, 384, 8, 1, jnp.float32, seed=1)
    want = _recurrence(*(np.asarray(o, np.float64) for o in operands))
    np.testing.assert_allclose(_kernel(*operands), want, atol=2e-4)


def _nothing_carried(scratch, rows):
    """The fault: a state that never crosses a chunk's edge."""
    return jnp.zeros((rows.size, scratch.shape[1]), jnp.float32)


@pytest.mark.parametrize("carry,wrong,right", [
    # the forward's state: every output after the first chunk, and the
    # gradients of what it meets there (C, the decay)
    ("_entering", ("y", "ddt", "da", "dc"), ("dx", "db", "dd")),
    # the backward's: the outputs stand, the gradients of everything
    # that feeds a later chunk's state fall
    ("_entering_gradient", ("dx", "ddt", "da", "db"), ("y", "dc", "dd")),
])
def test_planted_zeroed_carry_fails(monkeypatch, carry, wrong, right):
    operands, dy = _operands(1, 384, 8, 1, jnp.float32, seed=3)
    want = _output_and_gradients(_plain, operands, dy)
    monkeypatch.setattr(ssd_kernel, carry, _nothing_carried)
    got = _output_and_gradients(_kernel, operands, dy)
    for what in wrong:
        with pytest.raises(AssertionError):
            _assert_close(got[what], want[what], jnp.float32, what)
    for what in right:
        _assert_close(got[what], want[what], jnp.float32, what)
    # only across a chunk's edge: the first chunk's outputs enter no
    # state, and nothing of the last chunk's leaves it
    _assert_close(got["y"][:, :CHUNK], want["y"][:, :CHUNK], jnp.float32, "y")
    _assert_close(got["dx"][:, -CHUNK:], want["dx"][:, -CHUNK:],
                  jnp.float32, "y")


# seq, chunk, heads, groups, head, state
REFUSED = [
    (256, 64, 8, 1, 16, 128),     # a chunk that is not whole lane tiles
    (320, 128, 8, 1, 16, 128),    # a sequence that is not whole chunks
    (256, 128, 8, 1, 8, 128),     # a head inside a sublane tile
    (256, 128, 8, 1, 16, 64),     # states that half-fill the lanes
    (256, 128, 4, 1, 16, 128),    # four heads a group: toy widths
    (256, 128, 16, 4, 16, 128),   # the same, by the groups
    (512, 256, 8, 1, 128, 128),   # 8 heads of a chunk overfill a block
]


@pytest.mark.parametrize("shape", REFUSED, ids=lambda s: "x".join(map(str, s)))
def test_shapes_the_rule_refuses_raise_at_the_entry(shape):
    seq, chunk, heads, groups, head, state = shape
    assert not ssd_kernel.kernel_takes(*shape)
    operands, _ = _operands(1, seq, heads, groups, jnp.float32, head=head,
                            state=state)
    with pytest.raises(ValueError, match="not tiled by the kernels"):
        ssd_kernel.ssd_scan_kernel(*operands, chunk)


def test_the_cell_is_taken():
    # granite-4.0-h-micro: 8192 positions in chunks of 256, 64 heads of
    # 64 in one group, 128 states
    assert ssd_kernel.kernel_takes(8192, 256, 64, 1, 64, 128)


# ------------------------------------------------------------- dispatch


def _impl_traced(fn, *operands):
    """Which form ``fn`` traced, by the gauge and by the jaxpr."""
    telemetry.enable("test")
    try:
        jaxpr = str(jax.make_jaxpr(fn)(*operands))
        impls = [g["labels"]["impl"]
                 for g in telemetry.snapshot()["gauges"]
                 if g["name"] == "model.ssd.impl"]
    finally:
        telemetry.install_from_env()
    assert len(impls) == 1, impls
    assert ("pallas_call" in jaxpr) == (impls[0] == "kernel")
    return impls[0]


@pytest.mark.parametrize("shape,impl", [
    ((256, 128, 8, 1, 16, 128), "kernel"),
    ((256, 128, 16, 2, 16, 128), "kernel"),
    # the CPU tests' toy widths, and each thing the rule reads
    ((64, 8, 4, 1, 16, 8), "plain"),
] + [(shape, "plain") for shape in REFUSED if shape[0] % shape[1] == 0])
def test_dispatch_reads_the_shape(shape, impl):
    seq, chunk, heads, groups, head, state = shape
    operands, _ = _operands(1, seq, heads, groups, jnp.float32, head=head,
                            state=state)
    scan = functools.partial(ssd.ssd_scan, chunk=chunk)
    assert _impl_traced(scan, *operands) == impl
    np.testing.assert_allclose(
        scan(*operands), _plain(*operands, chunk=chunk), atol=1e-5)


@pytest.mark.parametrize("mesh,impl", [
    ({"data": 2, "fsdp": 2, "tensor": 2}, "kernel"),
    ({"fsdp": 4, "tensor": 2}, "kernel"),
    # a state handed across sequence shards is not built
    ({"data": 2, "seq": 4}, "plain"),
    # the batch does not divide over the batch axes
    ({"data": 8}, "plain"),
])
def test_dispatch_reads_the_mesh(mesh, impl):
    """On a mesh that splits the batch the kernels are mapped over its
    batch axes, and the gradients of ``a`` and ``d`` are summed over
    them."""
    operands, dy = _operands(4, 256, 8, 1, jnp.float32, seed=4)
    scan = functools.partial(ssd.ssd_scan, chunk=CHUNK)
    want = _output_and_gradients(_plain, operands, dy)
    with build_mesh(MeshConfig(**mesh)):
        assert _impl_traced(scan, *operands) == impl
        got = _output_and_gradients(scan, operands, dy)
    for what in PARTS:
        _assert_close(got[what], want[what], jnp.float32, what)


# ------------------------------------------------- in a hybrid's step


def _subjaxprs(val):
    if hasattr(val, "jaxpr"):
        yield val.jaxpr
    elif hasattr(val, "eqns"):
        yield val
    elif isinstance(val, (tuple, list)):
        for v in val:
            yield from _subjaxprs(v)


def _outside_kernels(jaxpr):
    """Every equation at any depth, a ``pallas_call`` as one equation:
    what is inside a kernel lives in fast memory."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for val in eqn.params.values():
            for sub in _subjaxprs(val):
                yield from _outside_kernels(sub)


@functools.lru_cache(maxsize=None)
def _step_equations(chunk: int):
    """The equations of the dead-code-eliminated train step of a toy
    hybrid whose Mamba layers keep their input alone (LAYER_INPUT);
    with ``chunk`` 128 its scan is one the kernels tile."""
    config = gh.GraniteHybridConfig(
        vocab_size=64, dim=32, layer_types=("mamba", "mamba", "attention"),
        n_heads=2, n_kv_heads=1, mlp_dim=64, mamba_heads=8,
        mamba_head_dim=16, mamba_state=128, mamba_chunk=chunk,
        dtype="float32", attn_block_q=128, attn_block_k=128,
    )
    accel = auto_accelerate(
        gh.granite_hybrid_loss_fn(config),
        lambda rng: gh.granite_hybrid_init(config, rng),
        optax.sgd(1e-2), gh.granite_hybrid_logical_axes(config),
        strategy=Strategy(mesh=MeshConfig(data=1, fsdp=1)),
        devices=jax.devices()[:1],
    )
    tokens = np.random.RandomState(0).randint(0, 64, (1, 257))
    closed = jax.make_jaxpr(accel.train_step)(
        accel.state, {"tokens": jnp.asarray(tokens, jnp.int32)},
        jax.random.key(0))
    live, _ = pe.dce_jaxpr(closed.jaxpr, [True] * len(closed.jaxpr.outvars))
    return list(_outside_kernels(live))


def _decays_in_memory(equations, chunk):
    """``exp`` equations whose result is [..., chunk, chunk]: the masked
    decay as an array."""
    return [
        eqn for eqn in equations if eqn.primitive.name == "exp"
        and eqn.outvars[0].aval.shape[-2:] == (chunk, chunk)
    ]


def test_the_pair_is_in_a_hybrids_step_and_no_decay_outside_it():
    equations = _step_equations(128)
    names = [eqn.params["name"] for eqn in equations
             if eqn.primitive.name == "pallas_call"]
    # a run of two layers is one scan body: the first forward pass, the
    # recomputation that LAYER_INPUT differentiates, its backward
    assert names.count("ssd_scan_fwd") == 2, names
    assert names.count("ssd_scan_bwd") == 1, names
    assert _decays_in_memory(equations, 128) == []


def test_the_plain_form_holds_the_decay_in_memory():
    """The same step with a chunk the kernels do not tile: the check
    above sees what it looks for."""
    equations = _step_equations(64)
    names = [eqn.params["name"] for eqn in equations
             if eqn.primitive.name == "pallas_call"]
    assert not any(name.startswith("ssd_scan") for name in names), names
    assert len(_decays_in_memory(equations, 64)) >= 2
