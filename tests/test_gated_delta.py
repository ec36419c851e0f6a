"""The gated delta rule (ops/gated_delta.py) on the CPU at tiny sizes:
the recurrence against the equation written out in numpy, the chunked
form against the recurrence (values and all five gradients), the
triangular inverse against numpy's, and the entry's choice of form."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.ops import gated_delta as gd


def _operands(seq, batch=2, heads=3, dk=8, dv=16, seed=0, repeat=False):
    """Unit keys, scaled queries, ``beta`` over all of (0, 2) and
    per-step decays ``exp(g)`` log-uniform from e^-6 (the state all but
    forgotten in a step) to 0.9999 (kept over many chunks). ``repeat``
    makes every key of a head the same vector, the case in which the
    powers of ``A`` explode."""
    rs = np.random.RandomState(seed)
    q = rs.randn(batch, seq, heads, dk)
    k = rs.randn(batch, 1 if repeat else seq, heads, dk)
    k = np.broadcast_to(k / np.linalg.norm(k, axis=-1, keepdims=True),
                        (batch, seq, heads, dk))
    g = -np.exp(rs.uniform(np.log(1e-4), np.log(6.0), (batch, seq, heads)))
    beta = rs.uniform(0.0, 2.0, (batch, seq, heads))
    beta[:, ::7] = 2.0
    return tuple(jnp.asarray(x, jnp.float32) for x in (
        q / np.linalg.norm(q, axis=-1, keepdims=True) * dk ** -0.5,
        k, rs.randn(batch, seq, heads, dv), g, beta))


def test_the_recurrence_is_the_equation():
    """Both ways the rule is written, in numpy float64, one head."""
    q, k, v, g, beta = (np.asarray(x, np.float64)
                        for x in _operands(12, batch=1, heads=1))
    dk, dv = k.shape[-1], v.shape[-1]
    state, other, want = np.zeros((dk, dv)), np.zeros((dk, dv)), []
    for t in range(12):
        qt, kt, vt = q[0, t, 0], k[0, t, 0], v[0, t, 0]
        decay, bt = np.exp(g[0, t, 0]), beta[0, t, 0]
        state = decay * state + bt * np.outer(
            kt, vt - decay * state.T @ kt)
        other = decay * (np.eye(dk) - bt * np.outer(kt, kt)) @ other \
            + bt * np.outer(kt, vt)
        np.testing.assert_allclose(state, other, atol=1e-12)
        want.append(state.T @ qt)
    got = gd.gated_delta_rule_plain(
        *(jnp.asarray(x, jnp.float32) for x in (q, k, v, g, beta)))
    np.testing.assert_allclose(got[0, :, 0], np.stack(want), atol=2e-6)


@pytest.mark.parametrize("size", [1, 2, 16, 64])
@pytest.mark.parametrize("hard", [False, True], ids=["random", "reflections"])
def test_unit_lower_inverse(size, hard):
    """Against numpy's inverse in float64. ``reflections``: beta = 2 and
    one key for every position, ``A = 2 x strict_lower(ones)``: the
    inverse's entries are +-2 and the powers of ``A`` explode; read 0
    to 5e-7 of the inverse's largest entry, where the Neumann product
    of the reflections at 64 positions reads 1.5e+20 (float32)."""
    rs = np.random.RandomState(size)
    a = np.tril(np.full((2, size, size), 2.0) if hard
                else rs.randn(2, size, size), -1)
    want = np.linalg.inv(np.eye(size) + a)
    got = gd.unit_lower_inverse(jnp.asarray(a, jnp.float32))
    assert got.shape == a.shape
    assert np.max(np.abs(got - want)) <= 1e-5 * np.max(np.abs(want))


def test_inverse_wants_a_power_of_two():
    with pytest.raises(ValueError, match="48 positions is no power of two"):
        gd.unit_lower_inverse(jnp.zeros((48, 48)))


@pytest.mark.parametrize("chunk,seq,repeat", [
    (16, 128, False), (64, 256, False), (32, 32, False), (16, 64, True),
], ids=["8-chunks", "4-chunks-of-64", "1-chunk", "repeated-keys"])
def test_chunked_form_is_the_recurrence(chunk, seq, repeat):
    """Outputs and the gradient of every operand in float32 on the CPU:
    two roundings of one function (read: outputs 3e-7 to 2e-6 of the
    largest, gradients 3e-7 to 2.3e-6 of a gradient's largest). The state
    is carried over up to 8 chunks at decays up to 0.9999 a step."""
    operands = _operands(seq, repeat=repeat)
    weight = jnp.asarray(
        np.random.RandomState(9).randn(*operands[2].shape), jnp.float32)

    def chunked(*ops):
        return gd.gated_delta_rule_chunked(*ops, chunk)

    got, want = chunked(*operands), gd.gated_delta_rule_plain(*operands)
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5 * scale

    def grads(fn):
        return jax.grad(
            lambda *ops: jnp.sum(fn(*ops) * weight), argnums=range(5)
        )(*operands)

    for name, got_g, want_g in zip(
            ("q", "k", "v", "g", "beta"), grads(chunked),
            grads(gd.gated_delta_rule_plain)):
        assert bool(jnp.all(jnp.isfinite(got_g))), name
        assert float(jnp.max(jnp.abs(got_g - want_g))) \
            < 1e-4 * float(jnp.max(jnp.abs(want_g))), name


def test_chunked_form_in_bf16_stays_near_the_recurrence():
    """bf16 operands, float32 decays, inverse, state and accumulation:
    the distance is bf16's rounding of the operands and of T, W and V'
    (read 0.0047-0.0048 relative rms over 8 chunks, three seeds), not a loss of the
    state from chunk to chunk."""
    q, k, v, g, beta = _operands(512, dk=16, dv=32)
    want = gd.gated_delta_rule_plain(q, k, v, g, beta)
    bf16 = jnp.bfloat16
    got = gd.gated_delta_rule_chunked(
        q.astype(bf16), k.astype(bf16), v.astype(bf16), g, beta, 64)
    assert got.dtype == bf16
    rel = float(jnp.sqrt(jnp.mean((got.astype(jnp.float32) - want) ** 2))
                / jnp.sqrt(jnp.mean(want ** 2)))
    assert rel < 0.015


def _traced_impl(operands, chunk):
    from dlrover_tpu.common import telemetry

    telemetry.enable("test")
    try:
        jaxpr = jax.make_jaxpr(
            lambda *ops: gd.gated_delta_rule(*ops, chunk))(*operands)
        impls = {g["labels"]["impl"] for g in telemetry.snapshot()["gauges"]
                 if g["name"] == "model.gdn.impl"}
    finally:
        telemetry.install_from_env()
    return impls, str(jaxpr)


def test_entry_takes_whole_chunks_chunked_and_the_rest_plain():
    """The form follows from the shapes alone, and the gauge names it;
    either gives the recurrence's output."""
    whole, ragged = _operands(64), _operands(48)
    assert _traced_impl(whole, 16)[0] == {"chunked"}
    assert _traced_impl(ragged, 32)[0] == {"plain"}
    np.testing.assert_allclose(
        gd.gated_delta_rule(*ragged, 32),
        gd.gated_delta_rule_plain(*ragged), atol=1e-6)
    np.testing.assert_allclose(
        gd.gated_delta_rule(*whole, 16),
        gd.gated_delta_rule_plain(*whole), atol=1e-5)


def test_entry_takes_a_sequence_sharded_mesh_plain():
    """No state is handed across sequence shards: under a ``seq`` mesh
    axis the recurrence itself runs, whatever the shapes."""
    from dlrover_tpu.parallel import mesh as mesh_lib

    before = mesh_lib._global_mesh
    mesh_lib.set_mesh(mesh_lib.build_mesh(
        mesh_lib.MeshConfig(data=4, seq=2)))
    try:
        assert _traced_impl(_operands(64), 16)[0] == {"plain"}
    finally:
        mesh_lib._global_mesh = before
