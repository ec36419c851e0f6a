"""The routed-expert op without capacity (parallel/moe.routed_experts)
on the CPU at tiny sizes: held to a plain loop over the experts, in
its outputs and in every operand's gradient; the shares of the chips
that divide a layer add up to the whole layer; no token is lost when
nine in ten go to one expert; rows sharded over data and fsdp give
what one device gives; an ``expert`` mesh axis is refused.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.parallel import mesh as mesh_lib
from dlrover_tpu.parallel import moe

E, D, M = 8, 16, 24


def _operands(seed=0, batch=2, seq=40, skew=None):
    rs = np.random.RandomState(seed)
    h = jnp.asarray(rs.randn(batch, seq, D), jnp.float32)
    if skew is None:
        choice = rs.randint(0, E, (batch, seq))
    else:
        # nine tokens in ten to expert ``skew``
        choice = np.where(rs.rand(batch, seq) < 0.9, skew,
                          rs.randint(0, E, (batch, seq)))
    weight = jnp.asarray(rs.rand(batch, seq), jnp.float32)
    experts = {
        "w_in": jnp.asarray(rs.randn(E, D, 2 * M) * D ** -0.5, jnp.float32),
        "w_out": jnp.asarray(rs.randn(E, M, D) * M ** -0.5, jnp.float32),
    }
    return h, jnp.asarray(choice, jnp.int32), weight, experts


def _share(experts, held):
    first, count = held
    return {k: v[first:first + count] for k, v in experts.items()}


def _plain(h, choice, weight, experts, held):
    """Every held expert over all rows, a mask selecting."""
    first, count = held
    y = jnp.zeros_like(h)
    for e in range(first, first + count):
        w_in, w_out = experts["w_in"][e], experts["w_out"][e]
        out = (jax.nn.silu(h @ w_in[:, :M]) * (h @ w_in[:, M:])) @ w_out
        y = y + jnp.where((choice == e)[..., None], out, 0)
    return y * weight[..., None]


def _routed(h, choice, weight, experts, held):
    return moe.routed_experts(h, choice, weight, _share(experts, held), held)


@pytest.mark.parametrize("held", [(0, E), (0, E // 2), (E // 2, E // 2),
                                  (3, 2)])
def test_routed_experts_is_the_plain_loop(held):
    """Outputs, and the gradient of the rows, the weights and the
    experts, whatever part of the model's experts is held. float32 on
    the CPU: read 2e-7 of the largest."""
    h, choice, weight, experts = _operands()
    got = _routed(h, choice, weight, experts, held)
    want = _plain(h, choice, weight, experts, held)
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5 * scale
    probe = jnp.asarray(np.random.RandomState(5).randn(*h.shape), jnp.float32)

    def grads(fn):
        return jax.grad(
            lambda h, w, ex: jnp.sum(fn(h, choice, w, ex, held) * probe),
            argnums=(0, 1, 2))(h, weight, experts)

    got_g, want_g = grads(_routed), grads(_plain)
    # the plain loop differentiates the whole stack; the op its share
    first, count = held
    want_g = (want_g[0], want_g[1], _share(want_g[2], held))
    got_g = (got_g[0], got_g[1], _share(got_g[2], held))
    for g, w in zip(jax.tree.leaves(got_g), jax.tree.leaves(want_g)):
        assert bool(jnp.all(jnp.isfinite(g)))
        assert float(jnp.max(jnp.abs(g - w))) \
            < 1e-5 * float(jnp.max(jnp.abs(w)))


def test_the_shares_add_up_to_the_whole_layer():
    """Two chips share a layer, experts 0-3 here and 4-7 there: their
    parts of the result add up to what the uncut layer gives, and a
    token's row is zero on the chip that does not hold its expert."""
    h, choice, weight, experts = _operands(seed=1)
    low = _routed(h, choice, weight, experts, (0, E // 2))
    high = _routed(h, choice, weight, experts, (E // 2, E // 2))
    whole = _plain(h, choice, weight, experts, (0, E))
    np.testing.assert_allclose(low + high, whole, atol=1e-5)
    there = np.asarray(choice) >= E // 2
    assert not np.asarray(low)[there].any()
    assert not np.asarray(high)[~there].any()
    assert np.asarray(low)[~there].any(axis=-1).all()


@pytest.mark.parametrize("crowded", [0, E - 1])
def test_no_token_is_lost_under_imbalance(crowded):
    """Nine tokens in ten choose one expert: a capacity of 1.25 x the
    mean would drop six in seven of them; here every one is computed."""
    h, choice, weight, experts = _operands(seed=2, seq=200, skew=crowded)
    load = np.bincount(np.asarray(choice).ravel(), minlength=E)
    assert load[crowded] > 0.85 * choice.size
    got = _routed(h, choice, weight, experts, (0, E))
    want = _plain(h, choice, weight, experts, (0, E))
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert np.asarray(got).any(axis=-1).all()


def test_an_empty_share_gives_zeros_and_finite_gradients():
    """No token chooses an expert that is held here."""
    h, _, weight, experts = _operands(seed=3)
    choice = jnp.full(h.shape[:2], E - 1, jnp.int32)
    held = (0, 2)

    def loss(h, w, ex):
        return jnp.sum(_routed(h, choice, w, ex, held) ** 2)

    assert float(loss(h, weight, experts)) == 0.0
    for g in jax.tree.leaves(jax.grad(loss, (0, 1, 2))(h, weight, experts)):
        assert not np.asarray(g).any()


def test_bf16_stays_near_the_plain_loop():
    """bf16 rows and weights, float32 accumulation: bf16's rounding of
    three matmuls and an activation (read 0.006 relative rms)."""
    h, choice, weight, experts = _operands(seed=4)
    want = _plain(h, choice, weight, experts, (0, E))
    low = jax.tree.map(lambda x: x.astype(jnp.bfloat16), experts)
    got = moe.routed_experts(h.astype(jnp.bfloat16), choice, weight, low,
                             (0, E))
    assert got.dtype == jnp.bfloat16
    rel = float(jnp.sqrt(jnp.mean((got.astype(jnp.float32) - want) ** 2))
                / jnp.sqrt(jnp.mean(want ** 2)))
    assert rel < 0.015


@pytest.fixture
def _mesh():
    before = mesh_lib._global_mesh
    yield mesh_lib
    mesh_lib._global_mesh = before


@pytest.mark.parametrize("axes", [{"data": 8}, {"data": 2, "fsdp": 4}])
def test_rows_sharded_over_data_and_fsdp(_mesh, axes):
    """Each device sorts and computes its own rows: the result and the
    gradients are those of one device."""
    h, choice, weight, experts = _operands(seed=6, batch=8, seq=16)
    held = (0, E // 2)

    def loss(h, w, ex):
        return jnp.sum(jnp.sin(_routed(h, choice, w, ex, held)))

    want = jax.value_and_grad(loss, (0, 1, 2))(h, weight, experts)
    mesh = _mesh.build_mesh(_mesh.MeshConfig(**axes))
    _mesh.set_mesh(mesh)
    with mesh:
        got = jax.jit(jax.value_and_grad(loss, (0, 1, 2)))(h, weight, experts)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(g, w, atol=2e-5)


def test_an_expert_mesh_axis_is_refused(_mesh):
    h, choice, weight, experts = _operands()
    _mesh.set_mesh(_mesh.build_mesh(_mesh.MeshConfig(expert=2, data=4)))
    with pytest.raises(NotImplementedError, match="exchange of tokens"):
        _routed(h, choice, weight, experts, (0, E))


def test_held_has_to_match_the_weights():
    h, choice, weight, experts = _operands()
    with pytest.raises(ValueError, match="names 3 experts"):
        moe.routed_experts(h, choice, weight, experts, (0, 3))


def test_under_layer_input_recomputation():
    """Inside ``stage_layer_scan`` with ``LAYER_INPUT`` (the layer's
    forward pass recomputed from its input in the backward pass) the
    gradients are those of the plain scan."""
    from dlrover_tpu.parallel import pipeline

    h, choice, weight, experts = _operands(seed=7)
    layers = 3
    stacked = jax.tree.map(
        lambda x: jnp.stack([x * (1 + 0.1 * i) for i in range(layers)]),
        experts)

    def layer(x, p):
        score = jnp.cos(x[..., :E] + jnp.arange(E))
        pick = jnp.argmax(score, -1).astype(jnp.int32)
        gate = jnp.take_along_axis(jax.nn.softmax(score), pick[..., None],
                                   -1)[..., 0]
        return x + moe.routed_experts(x, pick, gate, p, (0, E)), \
            jnp.zeros((), jnp.float32)

    def loss(policy, remat):
        stage = pipeline.stage_layer_scan(layer, remat=remat, policy=policy)
        return lambda p, x: jnp.sum(stage(p, x)[0] ** 2)

    want = jax.grad(loss(None, False), (0, 1))(stacked, h)
    got = jax.grad(loss(pipeline.LAYER_INPUT, True), (0, 1))(stacked, h)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(g, w, rtol=2e-5, atol=1e-5)


def _pairs(seed=8, batch=2, seq=24, k=2):
    """A token's k distinct experts of E and its k weights."""
    rs = np.random.RandomState(seed)
    h, _, _, experts = _operands(seed, batch, seq)
    choice = np.stack([rs.permutation(E)[:k]
                       for _ in range(batch * seq)]).reshape(batch, seq, k)
    weight = jnp.asarray(rs.rand(batch, seq, k), jnp.float32)
    return h, jnp.asarray(choice, jnp.int32), weight, experts


def _per_token(h, choice, weight, experts, held):
    """Each of a token's k pairs in turn, over the held experts: the
    expert's output times the pair's weight where the pair chose it."""
    first, count = held
    y = jnp.zeros_like(h)
    for j in range(choice.shape[-1]):
        for e in range(first, first + count):
            w_in, w_out = experts["w_in"][e], experts["w_out"][e]
            out = (jax.nn.silu(h @ w_in[:, :M]) * (h @ w_in[:, M:])) @ w_out
            y = y + jnp.where(choice[..., j] == e, weight[..., j], 0)[
                ..., None] * out
    return y


@pytest.mark.parametrize("k", [1, 2, 8])
@pytest.mark.parametrize("held", [(0, E), (2, 3)])
def test_top_k_is_the_per_token_loop(k, held):
    """A token's k pairs sorted with every other token's, grouped by
    held expert and summed back into its row: the per-token loop over
    its held experts, in the output and in the gradient of the rows,
    the weights and the experts; pairs of absent experts add 0."""
    h, choice, weight, experts = _pairs(seed=8 + k, k=k)
    if held != (0, E):
        assert (np.asarray(choice) < held[0]).any()     # absent pairs
    got = _routed(h, choice, weight, experts, held)
    want = _per_token(h, choice, weight, experts, held)
    np.testing.assert_allclose(got, want, atol=1e-5 * float(
        jnp.max(jnp.abs(want))))
    probe = jnp.asarray(np.random.RandomState(5).randn(*h.shape), jnp.float32)

    def grads(fn):
        return jax.grad(
            lambda h, w, ex: jnp.sum(fn(h, choice, w, ex, held) * probe),
            argnums=(0, 1, 2))(h, weight, experts)

    got_g, want_g = grads(_routed), grads(_per_token)
    got_g = (got_g[0], got_g[1], _share(got_g[2], held))
    want_g = (want_g[0], want_g[1], _share(want_g[2], held))
    for g, w in zip(jax.tree.leaves(got_g), jax.tree.leaves(want_g)):
        assert bool(jnp.all(jnp.isfinite(g)))
        np.testing.assert_allclose(
            g, w, atol=1e-5 * max(float(jnp.max(jnp.abs(w))), 1e-6))


@pytest.mark.parametrize("k", [2, 8])
def test_what_the_matmuls_leave_unvisited_reaches_no_gradient(
        k, monkeypatch):
    """On the chip the grouped matmuls leave the rows of absent pairs as
    their buffer held them, NaN among it. Planted here as NaN, it must
    reach neither the output nor any gradient."""
    grouped = jax.lax.ragged_dot

    def leaves_nan(lhs, rhs, group_sizes, **kwargs):
        out = grouped(lhs, rhs, group_sizes, **kwargs)
        visited = jnp.arange(out.shape[0]) < jnp.sum(group_sizes)
        return jnp.where(visited[:, None], out, jnp.nan)

    monkeypatch.setattr(jax.lax, "ragged_dot", leaves_nan)
    h, choice, weight, experts = _pairs(seed=11, k=k)
    held = (2, 3)
    out, grads = jax.value_and_grad(
        lambda h, w, ex: jnp.sum(_routed(h, choice, w, ex, held)),
        argnums=(0, 1, 2))(h, weight, experts)
    assert bool(jnp.isfinite(out))
    for g in jax.tree.leaves(grads):
        assert bool(jnp.all(jnp.isfinite(g)))


def test_top_1_as_pairs_is_top_1():
    """[B, S, 1] choices are the [B, S] form's, bit for bit."""
    h, choice, weight, experts = _operands(seed=9)
    held = (0, E // 2)
    flat = _routed(h, choice, weight, experts, held)
    pairs = _routed(h, choice[..., None], weight[..., None], experts, held)
    assert np.array_equal(np.asarray(flat), np.asarray(pairs))


def test_top_k_shares_add_up_to_the_whole_layer():
    """Four chips share a layer, two experts each, 4 pairs a token: the
    four parts add up to the layer with every expert held."""
    h, choice, weight, experts = _pairs(seed=10, k=4)
    parts = [_routed(h, choice, weight, experts, (first, 2))
             for first in range(0, E, 2)]
    whole = _per_token(h, choice, weight, experts, (0, E))
    np.testing.assert_allclose(sum(parts), whole, atol=2e-5)


def test_routed_counts_count_pairs():
    """``routed_counts``: pairs routed, pairs held here, and the largest
    and the mean load of a held expert in pairs."""
    choice = jnp.asarray([[[0, 3], [3, 5]], [[1, 3], [7, 6]]], jnp.int32)
    counts = np.asarray(moe.routed_counts(choice, (2, 4)))
    # held experts 2-5: loads 0, 3, 0, 1
    np.testing.assert_array_equal(counts, [8, 4, 3, 1])


def _laguna_route_before(config, y, router):
    """``models/laguna.route`` as it was written before the router moved
    into ``moe.route``."""
    with jax.named_scope("router"):
        scores = jnp.matmul(y, router.astype(y.dtype),
                            precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)
        probs = jax.nn.softmax(scores, axis=-1)
        top, choice = jax.lax.top_k(probs, config.top_k)
        weight = top / jnp.sum(top, -1, keepdims=True) * config.routed_scale
    return choice.astype(jnp.int32), weight


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_laguna_routes_through_moe_route_as_it_did(dtype):
    """Laguna's softmax router, now ``moe.route(scoring="softmax")``,
    gives its former formula's choices and weights bit for bit at the
    toy's widths (64 wide, 16 experts, 4 a token)."""
    from dlrover_tpu.models import laguna

    config = laguna.LagunaConfig(
        layer_types=("full_attention",) * 2, mlp_layer_types=("sparse",) * 2,
        heads=(8, 8), n_kv_heads=2, n_experts=16, held_experts=4, top_k=4,
        dim=64, head_dim=16, routed_scale=2.5)
    rs = np.random.RandomState(11)
    y = jnp.asarray(rs.randn(2, 128, 64), dtype)
    router = jnp.asarray(rs.randn(64, 16) * 0.125, jnp.float32)
    for got, want in zip(
        jax.jit(lambda y, r: laguna.route(config, y, r))(y, router),
        jax.jit(lambda y, r: _laguna_route_before(config, y, r))(y, router),
    ):
        assert got.dtype == want.dtype
        assert np.array_equal(np.asarray(got), np.asarray(want))


def test_route_refuses_what_it_does_not_score():
    y, router = jnp.zeros((2, 8)), jnp.zeros((8, 4))
    with pytest.raises(ValueError, match="softmax or sigmoid"):
        moe.route(y, router, scoring="relu", top_k=2, scale=1.0)
