"""The chunked loss's per-chip form (``ops/cross_entropy.py``, PR 40)
on the CPU's virtual devices: where the head's matrix is sharded over
``fsdp`` the loop runs on each chip over its own rows, the matrix is
gathered once a pass and the chunks' gradient reduce-scattered once.
Held to the plain form on one device: loss, ``dh``, ``dw`` and the norm
scale's gradient, with ignored labels and a sequence the chunks pad.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec

from dlrover_tpu.common import telemetry
from dlrover_tpu.ops.cross_entropy import fused_linear_cross_entropy
from dlrover_tpu.parallel import mesh as mesh_lib
from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
from tests.conftest import _subjaxprs

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
B, S, D, V, CHUNKS = 4, 13, 32, 64, 4   # 13 positions: 3 padded


@pytest.fixture
def no_mesh():
    before = mesh_lib._global_mesh
    mesh_lib._global_mesh = None
    try:
        yield
    finally:
        mesh_lib._global_mesh = before


def _operands(seed=0):
    k = jax.random.split(jax.random.key(seed), 4)
    h = jax.random.normal(k[0], (B, S, D), jnp.bfloat16)
    w = (jax.random.normal(k[1], (D, V)) * 0.3).astype(jnp.bfloat16)
    scale = 1 + 0.2 * jax.random.normal(k[2], (D,))
    labels = jax.random.randint(k[3], (B, S), 0, V)
    # ignored labels: a whole row's start, and one in the last chunk
    labels = labels.at[0, :5].set(-100).at[2, 11].set(-100)
    return h, w, scale, labels


def _loss_and_grads(h, w, scale, labels, use_norm):
    def loss(h, w, scale):
        total, valid = fused_linear_cross_entropy(
            h, w, labels, n_chunks=CHUNKS,
            norm_scale=scale if use_norm else None)
        return total / valid

    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))(h, w, scale)


def _traced_impls(fn):
    telemetry.enable("test")
    try:
        fn()
        return {g["labels"]["impl"] for g in telemetry.snapshot()["gauges"]
                if g["name"] == "model.head.impl"}
    finally:
        telemetry.install_from_env()


@pytest.mark.parametrize("use_norm", [True, False])
@pytest.mark.parametrize("mesh", [{"fsdp": 4}, {"data": 2, "fsdp": 2}])
def test_per_chip_form_is_the_plain_form(no_mesh, mesh, use_norm):
    """Same chunks, same dtypes at every rounding point; what differs is
    where the chips' partial sums of ``dw`` meet: after the eighth
    chunk, not after each. ``dw`` reads up to 1.1% of its largest entry
    apart over three seeds, as far as either form lies from the same
    loss in float32 (0.33-1.04% and 0.52-0.67%): a few bf16 roundings of
    the accumulator, met in another order."""
    h, w, scale, labels = _operands()
    plain = {}
    assert _traced_impls(lambda: plain.update(
        out=_loss_and_grads(h, w, scale, labels, use_norm))) == {"plain"}
    m = build_mesh(MeshConfig(**mesh), devices=jax.devices()[:4])
    mesh_lib.set_mesh(m)
    w_shard = jax.device_put(w, NamedSharding(m, PartitionSpec("fsdp")))
    per_chip = {}
    assert _traced_impls(lambda: per_chip.update(
        out=_loss_and_grads(h, w_shard, scale, labels, use_norm))) == {
            "per_chip"}
    (want, want_grads), (got, got_grads) = plain["out"], per_chip["out"]
    np.testing.assert_allclose(float(got), float(want), rtol=1e-6)
    assert got_grads[1].sharding.spec == PartitionSpec("fsdp")
    names = ("dh", "dw", "dscale")
    for name, a, b in zip(names, got_grads, want_grads):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert a.shape == b.shape, name
        top = np.abs(b).max()
        assert top > 0 or (name == "dscale" and not use_norm), name
        assert np.abs(a - b).max() <= 2e-2 * top, name


@pytest.mark.parametrize("mesh,impl", [
    ({"fsdp": 4}, "per_chip"),
    ({"data": 2, "fsdp": 2}, "per_chip"),
    ({"data": 4}, "plain"),                   # nothing sharded over fsdp
    ({"fsdp": 2, "tensor": 2}, "plain"),      # the vocabulary split too
    ({"fsdp": 2, "seq": 2}, "plain"),
    ({"fsdp": 8}, "plain"),                   # 4 rows over 8 chips
])
def test_form_follows_the_mesh(no_mesh, mesh, impl):
    h, w, scale, labels = _operands()
    m = build_mesh(MeshConfig(**mesh), devices=jax.devices()[:8][
        :int(np.prod(list(mesh.values())))])
    mesh_lib.set_mesh(m)
    assert _traced_impls(lambda: jax.make_jaxpr(
        lambda *a: _loss_and_grads(*a, labels, True))(h, w, scale)) == {impl}


def _collectives(jaxpr, in_loop=False, found=None):
    """``(primitive, output shape, inside a scan or while)`` of every
    all_gather, reduce_scatter and optimization_barrier in ``jaxpr``,
    sub-jaxprs included."""
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in ("all_gather", "reduce_scatter",
                                  "optimization_barrier"):
            found.append((eqn.primitive.name,
                          tuple(eqn.outvars[0].aval.shape), in_loop))
        loop = in_loop or eqn.primitive.name in ("scan", "while")
        for val in eqn.params.values():
            for sub in _subjaxprs(val):
                _collectives(sub, loop, found)
    return found


def test_step_gathers_the_matrix_once_a_pass(no_mesh):
    """The toy Olmo-Hybrid's whole training step under ``fsdp=4``: one
    gather of the head's matrix in each pass and one reduce-scatter of
    its gradient, none inside a loop; and the forward loop's logits
    rounded once before anything reads them (on the chip the compiler
    had given the label's pick an unrounded copy: PERF.md, PR 40)."""
    from dlrover_tpu.models import olmo_hybrid as oh
    from dlrover_tpu.parallel import Strategy, auto_accelerate

    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    try:
        import families
        import lookup

        family = families.build(lookup.data("configs", "toy-olmo-hybrid"))
    finally:
        sys.path.remove(os.path.join(ROOT, "benchmark"))
    config = family.model_config
    accel = auto_accelerate(
        oh.olmo_hybrid_loss_fn(config),
        lambda rng: oh.olmo_hybrid_init(config, rng),
        optax.sgd(0.01), oh.olmo_hybrid_logical_axes(config),
        strategy=Strategy(mesh=MeshConfig(fsdp=4), donate=False),
        devices=jax.devices()[:4], seed=3,
    )
    tokens = jnp.zeros((4, 129), jnp.int32)
    traced = {}
    assert _traced_impls(lambda: traced.update(jaxpr=jax.make_jaxpr(
        accel.train_step)(accel.state, {"tokens": tokens},
                          jax.random.key(0)))) == {"per_chip"}
    head = accel.state.params["lm_head"].shape
    found = _collectives(traced["jaxpr"].jaxpr)
    chunk = (1, (tokens.shape[1] - 1) // config.ce_chunks, head[1])
    assert sorted(found) == [
        ("all_gather", head, False),
        ("all_gather", head, False),
        ("optimization_barrier", chunk, True),
        ("reduce_scatter", (head[0] // 4, head[1]), False),
    ], found



@pytest.mark.parametrize("manual", ["data", "fsdp"])
def test_a_manual_axis_keeps_the_plain_head(no_mesh, manual):
    """Inside a map manual over any axis the head keeps the plain form;
    the layers' overlapped gathers (``overlap.gather_mesh``) decline
    only where ``fsdp`` itself is manual, as before PR 40."""
    from dlrover_tpu.parallel.overlap import gather_mesh

    h, w, scale, labels = _operands()
    m = build_mesh(MeshConfig(data=2, fsdp=2), devices=jax.devices()[:4])
    mesh_lib.set_mesh(m)
    seen = {}

    def local(h, labels):
        seen["gather_mesh"] = gather_mesh()
        total, _ = fused_linear_cross_entropy(
            h, w, labels, n_chunks=CHUNKS, norm_scale=scale)
        return total[None]

    rows = PartitionSpec(manual)
    mapped = jax.shard_map(local, mesh=m, in_specs=(rows, rows),
                           out_specs=rows, axis_names={manual},
                           check_vma=False)
    assert _traced_impls(
        lambda: jax.make_jaxpr(mapped)(h, labels)) == {"plain"}
    assert (seen["gather_mesh"] is m) == (manual != "fsdp")


def test_the_plain_form_pins_its_logits_where_asked(no_mesh):
    """``pinned`` puts one barrier on each chunk's logits in the plain
    form's forward and changes no number; without it there is none."""
    h, w, scale, labels = _operands(seed=7)

    def loss(pinned):
        def fn(h, w, scale):
            total, valid = fused_linear_cross_entropy(
                h, w, labels, n_chunks=CHUNKS, norm_scale=scale,
                pinned=pinned)
            return total / valid
        return fn

    def count(jaxpr):
        n = 0
        for e in jaxpr.eqns:
            n += e.primitive.name == "optimization_barrier"
            for j in _subjaxprs(list(e.params.values())):
                n += count(j)
        return n

    def barriers(fn):
        return count(jax.make_jaxpr(jax.value_and_grad(
            fn, argnums=(0, 1, 2)))(h, w, scale).jaxpr)

    assert barriers(loss(True)) >= 1 and barriers(loss(False)) == 0
    got = jax.value_and_grad(loss(True), argnums=(0, 1, 2))(h, w, scale)
    want = jax.value_and_grad(loss(False), argnums=(0, 1, 2))(h, w, scale)
    for g, x in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(x))
