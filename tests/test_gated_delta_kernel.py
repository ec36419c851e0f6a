"""The gated delta rule's kernel pair (ops/gated_delta_kernel.py,
interpret mode) against the recurrence it computes
(ops/gated_delta.py:gated_delta_rule_plain differentiated by JAX), the
rule that picks a form (ops/gated_delta.py:gated_delta_rule), and where
the pair lands in an Olmo hybrid's step."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax._src.interpreters import partial_eval as pe

from dlrover_tpu.common import telemetry
from dlrover_tpu.models import olmo_hybrid as oh
from dlrover_tpu.ops import gated_delta as gd
from dlrover_tpu.ops import gated_delta_kernel as gk
from dlrover_tpu.parallel import MeshConfig, Strategy
from dlrover_tpu.parallel.accelerate import auto_accelerate
from dlrover_tpu.parallel.mesh import build_mesh
from tests.conftest import _subjaxprs

DK, DV = 96, 192                # olmo-hybrid-7b's head sizes
PARTS = ("o", "dq", "dk", "dv", "dg", "dbeta")


def _operands(seq, batch=1, heads=3, dk=DK, dv=DV, seed=0, repeat=False,
              decays=(1e-4, 6.0), beta=(0.0, 2.0)):
    """Unit keys, scaled queries, ``beta`` over ``beta`` with every
    seventh position at 2, per-step log-decays ``-g`` log-uniform over
    ``decays`` (e^-6 a step: the state all but forgotten; 0.9999: kept
    over many chunks), and the output's cotangent. ``repeat`` makes
    every key of a head the same vector: with ``beta`` at 2 every
    transition is the same reflection, the case in which the powers of
    ``A`` explode."""
    rs = np.random.RandomState(seed)
    q = rs.randn(batch, seq, heads, dk)
    k = rs.randn(batch, 1 if repeat else seq, heads, dk)
    k = np.broadcast_to(k / np.linalg.norm(k, axis=-1, keepdims=True),
                        (batch, seq, heads, dk))
    g = -np.exp(rs.uniform(*np.log(decays), (batch, seq, heads)))
    b = rs.uniform(*beta, (batch, seq, heads))
    b[:, ::7] = 2.0
    operands = tuple(jnp.asarray(x, jnp.float32) for x in (
        q / np.linalg.norm(q, axis=-1, keepdims=True) * dk ** -0.5,
        k, rs.randn(batch, seq, heads, dv), g, b))
    return operands, jnp.asarray(rs.randn(batch, seq, heads, dv), jnp.float32)


# sequence (chunks of 128) and what _operands is told
CASES = {
    # one block of three heads over two chunks
    "3-heads": (256, {}),
    # a block of five, three chunks, two batch rows
    "5-heads-2-rows": (384, dict(heads=5, batch=2, seed=1)),
    # two blocks of nine
    "18-heads": (256, dict(heads=18, seed=9)),
    # seventeen heads: a head a grid step
    "17-heads": (128, dict(heads=17, seed=2)),
    # one key a head and beta in [1.9, 2]: the Neumann product's powers
    # reach 1e20 here
    "reflections": (256, dict(repeat=True, beta=(1.9, 2.0), seed=3)),
    # the state kept over every chunk, and forgotten inside one
    "slow-decays": (384, dict(decays=(1e-5, 1e-3), seed=4)),
    "fast-decays": (256, dict(decays=(1.0, 6.0), seed=5)),
    # other head sizes the blocks tile
    "heads-of-64-and-80": (256, dict(heads=2, dk=64, dv=80, seed=6)),
}


def _kernel(*operands):
    return gk.gated_delta_rule_kernel(*operands)


def _output_and_gradients(fn, operands, do):
    """``o`` and the five gradients of ``sum(o * do)``."""
    def loss(*operands):
        o = fn(*operands)
        return jnp.sum(o.astype(jnp.float32) * do), o

    (_, o), grads = jax.jit(jax.value_and_grad(
        loss, argnums=tuple(range(5)), has_aux=True))(*operands)
    return dict(zip(PARTS, (o, *grads)))


def _distance(got, want):
    """Relative rms."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.sqrt(np.mean((got - want) ** 2))
                 / np.sqrt(np.mean(want ** 2)))


@pytest.fixture(scope="module")
def results():
    """The kernels' outputs and gradients in float32 and with bf16
    operands, and the recurrence's in float32, once a case."""
    cache = {}

    def get(case):
        if case not in cache:
            seq, told = CASES[case]
            operands, do = _operands(seq, **told)
            q, k, v, g, beta = operands
            bf16 = jnp.bfloat16
            low = (q.astype(bf16), k.astype(bf16), v.astype(bf16), g, beta)
            cache[case] = {
                "plain": _output_and_gradients(
                    gd.gated_delta_rule_plain, operands, do),
                "float32": _output_and_gradients(_kernel, operands, do),
                "bfloat16": _output_and_gradients(_kernel, low, do),
            }
        return cache[case]

    return get


# float32: two roundings of one function (read: 1e-6 to 4e-6 of a
# part's largest element). bf16 operands against the float32
# recurrence: the band tests/test_gated_delta.py holds the chunked form
# to on ``o`` (0.015; the kernels read 0.003-0.006 where that form reads
# 0.0047), and twice it on the gradients, which pass through the rounded
# operands twice (read 0.004-0.012)
@pytest.mark.parametrize("what", PARTS)
@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_is_the_recurrence_in_float32(results, case, what):
    got, want = results(case)["float32"][what], results(case)["plain"][what]
    assert got.dtype == want.dtype and got.shape == want.shape
    assert bool(jnp.all(jnp.isfinite(got)))
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(got - want))) < 2e-5 * scale


@pytest.mark.parametrize("what", PARTS)
@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_in_bf16_stays_near_the_recurrence(results, case, what):
    got, want = results(case)["bfloat16"][what], results(case)["plain"][what]
    assert got.dtype == (jnp.float32 if what in ("dg", "dbeta")
                         else jnp.bfloat16)
    assert bool(jnp.all(jnp.isfinite(got.astype(jnp.float32))))
    assert _distance(got, want) < (0.015 if what == "o" else 0.03)


def _nothing_carried(scratch, rows):
    """The fault: a state that never crosses a chunk's edge."""
    return jnp.zeros((rows.size, scratch.shape[1]), jnp.float32)


@pytest.mark.parametrize("carry,wrong,right", [
    # the forward's state: every output after the first chunk, and the
    # gradients of what it meets there (q, the decay)
    ("_entering", ("o", "dq", "dg"), ()),
    # the backward's: the outputs stand, the gradients of everything
    # that feeds a later chunk's state fall
    ("_entering_gradient", ("dk", "dv", "dg", "dbeta"), ("o",)),
])
def test_planted_zeroed_carry_fails(monkeypatch, carry, wrong, right):
    operands, do = _operands(256, decays=(1e-4, 1e-2), seed=7)
    want = _output_and_gradients(gd.gated_delta_rule_plain, operands, do)
    monkeypatch.setattr(gk, carry, _nothing_carried)
    got = _output_and_gradients(_kernel, operands, do)
    for what in wrong:
        assert _distance(got[what], want[what]) > 0.05, what
    for what in right:
        assert _distance(got[what], want[what]) < 1e-5, what
    # only across a chunk's edge: the first chunk's outputs enter no
    # state, and nothing of the last chunk's leaves it
    assert _distance(got["o"][:, :gk.CHUNK], want["o"][:, :gk.CHUNK]) < 1e-5
    assert _distance(got["dv"][:, -gk.CHUNK:],
                     want["dv"][:, -gk.CHUNK:]) < 1e-5


def test_planted_neumann_product_fails_on_reflections(monkeypatch):
    """The inverse as the product ``(I - A)(I + A^2)(I + A^4)...``:
    the same matrix on paper, and on one key a head with ``beta`` near
    2 its powers cancel from 1e20: the case ``reflections`` sees it."""
    def neumann(inverse, a):
        del inverse
        eye = jnp.eye(gk.CHUNK, dtype=jnp.float32)
        product, power = eye - a, a @ a
        for _ in range(6):
            product, power = product @ (eye + power), power @ power
        return product

    seq, told = CASES["reflections"]
    operands, _ = _operands(seq, **told)
    want = gd.gated_delta_rule_plain(*operands)
    assert _distance(_kernel(*operands), want) < 1e-5
    monkeypatch.setattr(gk, "_doubled", neumann)
    got = _kernel(*operands)
    assert not (_distance(got, want) < 1e-2)        # or not finite


# seq, heads, dk, dv
REFUSED = [
    (320, 3, 96, 192),      # a sequence that is not whole chunks of 128
    (256, 3, 88, 192),      # keys inside a sublane tile
    (256, 3, 96, 200),      # values inside a sublane tile
    (256, 4, 16, 32),       # toy widths: keys under half a lane tile
    (256, 2, 8, 16),
]


@pytest.mark.parametrize("shape", REFUSED, ids=lambda s: "x".join(map(str, s)))
def test_shapes_the_rule_refuses_raise_at_the_kernels(shape):
    seq, heads, dk, dv = shape
    assert not gk.kernel_takes(*shape)
    operands, _ = _operands(seq, heads=heads, dk=dk, dv=dv)
    with pytest.raises(ValueError, match="not tiled by the kernels"):
        gk.gated_delta_rule_kernel(*operands)


def test_the_cell_is_taken():
    # olmo-hybrid-7b.steady, a chip: 8192 positions, 30 heads of 96 / 192
    assert gk.kernel_takes(8192, 30, 96, 192)
    assert gk._head_block(30) == 15


@pytest.mark.parametrize("heads,block", [
    (30, 15), (32, 8), (7, 7), (17, 1), (12, 12), (64, 8)])
def test_heads_a_step(heads, block):
    assert gk._head_block(heads) == block


# ------------------------------------------------------------- dispatch


def _impl_traced(fn, *operands):
    """Which form ``fn`` traced, by the gauge and by the jaxpr."""
    telemetry.enable("test")
    try:
        jaxpr = str(jax.make_jaxpr(fn)(*operands))
        impls = [g["labels"]["impl"]
                 for g in telemetry.snapshot()["gauges"]
                 if g["name"] == "model.gdn.impl"]
    finally:
        telemetry.install_from_env()
    assert len(impls) == 1, impls
    assert ("pallas_call" in jaxpr) == (impls[0] == "kernel")
    assert ("scan" in jaxpr and "cumsum" not in jaxpr) == (
        impls[0] == "plain")
    return impls[0]


@pytest.mark.parametrize("shape,chunk,impl", [
    ((256, 3, 96, 192), 64, "kernel"),
    # the kernels' chunk is their own: the plain form's need not divide
    # the sequence
    ((128, 2, 64, 64), 48, "kernel"),
    # the CPU tests' toy widths
    ((256, 4, 16, 32), 32, "chunked"),
    ((64, 2, 8, 16), 16, "chunked"),
    # head sizes inside a sublane tile
    ((256, 3, 88, 192), 64, "chunked"),
    # a ragged tail: not whole chunks of either kind
    ((320, 3, 96, 192), 128, "plain"),
    ((48, 2, 8, 16), 32, "plain"),
], ids=lambda v: "x".join(map(str, v)) if isinstance(v, tuple) else str(v))
def test_dispatch_reads_the_shape(shape, chunk, impl):
    seq, heads, dk, dv = shape
    operands, _ = _operands(seq, heads=heads, dk=dk, dv=dv)
    rule = functools.partial(gd.gated_delta_rule, chunk=chunk)
    assert _impl_traced(rule, *operands) == impl
    np.testing.assert_allclose(
        rule(*operands), gd.gated_delta_rule_plain(*operands), atol=2e-5)


@pytest.mark.parametrize("mesh,impl", [
    ({"data": 2, "fsdp": 2, "tensor": 2}, "kernel"),
    ({"fsdp": 4, "tensor": 2}, "kernel"),
    # a state handed across sequence shards is not built
    ({"data": 2, "seq": 4}, "plain"),
    # the batch does not divide over the batch axes
    ({"data": 8}, "chunked"),
])
def test_dispatch_reads_the_mesh(mesh, impl):
    """On a mesh that splits the batch the kernels are mapped over its
    batch axes: ``pallas_call`` does not partition itself."""
    operands, do = _operands(128, batch=4, heads=2, dk=64, dv=64, seed=8)
    rule = functools.partial(gd.gated_delta_rule, chunk=64)
    want = _output_and_gradients(gd.gated_delta_rule_plain, operands, do)
    with build_mesh(MeshConfig(**mesh)):
        assert _impl_traced(rule, *operands) == impl
        got = _output_and_gradients(rule, operands, do)
    for what in PARTS:
        assert _distance(got[what], want[what]) < 1e-5, what


# ------------------------------------------- in an Olmo hybrid's step


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
def _step_equations(dk: int):
    """The equations of the dead-code-eliminated train step of a toy
    Olmo hybrid (two linear-attention layers, one full-attention), every
    layer keeping its input alone; with keys of 64 channels its rule is
    one the kernels tile."""
    config = oh.OlmoHybridConfig(
        vocab_size=64, dim=32, n_heads=2, n_kv_heads=2, mlp_dim=64,
        layer_types=("linear_attention", "linear_attention",
                     "full_attention"),
        linear_heads=2, linear_key_head_dim=dk, linear_value_head_dim=dk,
        linear_chunk=64, dtype="float32", attn_block_q=128,
        attn_block_k=128,
    )
    accel = auto_accelerate(
        oh.olmo_hybrid_loss_fn(config),
        lambda rng: oh.olmo_hybrid_init(config, rng),
        optax.sgd(1e-2), oh.olmo_hybrid_logical_axes(config),
        strategy=Strategy(mesh=MeshConfig(data=1, fsdp=1)),
        devices=jax.devices()[:1],
    )
    tokens = np.random.RandomState(0).randint(0, 64, (1, 129))
    closed = jax.make_jaxpr(accel.train_step)(
        accel.state, {"tokens": jnp.asarray(tokens, jnp.int32)},
        jax.random.key(0))
    live, _ = pe.dce_jaxpr(closed.jaxpr, [True] * len(closed.jaxpr.outvars))
    return list(_outside_kernels(live))


def _chunk_squares_in_memory(equations, chunk):
    """Float32 results shaped [batch, chunks, heads, chunk, chunk] (or
    with more in front) outside any kernel: the masked decay, ``K K^T``,
    the inverse's doublings as arrays."""
    return [
        eqn for eqn in equations for out in eqn.outvars
        if len(getattr(out.aval, "shape", ())) >= 5
        and out.aval.shape[-2:] == (chunk, chunk)
        and out.aval.dtype == jnp.float32
    ]


def test_the_pair_is_in_a_hybrids_step_and_no_square_outside_it():
    equations = _step_equations(64)
    names = [eqn.params["name"] for eqn in equations
             if eqn.primitive.name == "pallas_call"]
    # a run of two layers is one scan body: the first forward pass, the
    # recomputation that the layer's kept input is differentiated from,
    # its backward
    assert names.count("gdn_chunk_fwd") == 2, names
    assert names.count("gdn_chunk_bwd") == 1, names
    assert _chunk_squares_in_memory(equations, 64) == []
    assert _chunk_squares_in_memory(equations, gk.CHUNK) == []


def test_the_chunked_form_holds_the_squares_in_memory():
    """The same step with keys the kernels do not tile: the check above
    sees what it looks for."""
    equations = _step_equations(16)
    names = [eqn.params["name"] for eqn in equations
             if eqn.primitive.name == "pallas_call"]
    assert not any(name.startswith("gdn_chunk") for name in names), names
    assert len(_chunk_squares_in_memory(equations, 64)) >= 6
