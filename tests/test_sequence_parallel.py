"""Sequence parallelism: ring attention and Ulysses vs single-device MHA."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dlrover_tpu.ops.attention import mha_reference
from dlrover_tpu.parallel.sequence import (
    ring_attention,
    sequence_sharded_attention,
    ulysses_attention,
)

shard_map = jax.shard_map


def seq_mesh(n=4):
    return Mesh(np.array(jax.devices()[:n]).reshape(n), ("seq",))


def make_qkv(b=2, h=4, s=32, d=16, kv_heads=None, seed=0, dtype=jnp.float32):
    kv_heads = kv_heads or h
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, h, s, d), dtype)
    k = jax.random.normal(ks[1], (b, kv_heads, s, d), dtype)
    v = jax.random.normal(ks[2], (b, kv_heads, s, d), dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("kv_heads", [4, 2])
def test_ring_attention_matches_reference(causal, kv_heads):
    mesh = seq_mesh(4)
    q, k, v = make_qkv(kv_heads=kv_heads)
    ref = mha_reference(q, k, v, causal=causal)

    fn = shard_map(
        functools.partial(ring_attention, axis_name="seq", axis_size=4,
                          causal=causal),
        mesh=mesh,
        in_specs=(P(None, None, "seq", None),) * 3,
        out_specs=P(None, None, "seq", None),
        check_vma=False,
    )
    sharding = NamedSharding(mesh, P(None, None, "seq", None))
    out = jax.jit(fn)(*(jax.device_put(x, sharding) for x in (q, k, v)))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ring_attention_grads_match():
    mesh = seq_mesh(4)
    q, k, v = make_qkv(b=1, h=2, s=16, d=8)

    def ref_loss(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=True) ** 2)

    fn = shard_map(
        functools.partial(ring_attention, axis_name="seq", axis_size=4),
        mesh=mesh,
        in_specs=(P(None, None, "seq", None),) * 3,
        out_specs=P(None, None, "seq", None),
        check_vma=False,
    )

    def ring_loss(q, k, v):
        return jnp.sum(fn(q, k, v) ** 2)

    g_ref = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    with mesh:
        g_ring = jax.jit(jax.grad(ring_loss, argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("causal", [True, False])
def test_ulysses_matches_reference(causal):
    mesh = seq_mesh(4)
    q, k, v = make_qkv(h=8, kv_heads=4, s=64)
    ref = mha_reference(q, k, v, causal=causal)
    fn = shard_map(
        functools.partial(ulysses_attention, axis_name="seq", axis_size=4,
                          causal=causal, interpret=True),
        mesh=mesh,
        in_specs=(P(None, None, "seq", None),) * 3,
        out_specs=P(None, None, "seq", None),
        check_vma=False,
    )
    with mesh:
        out = jax.jit(fn)(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ulysses_rejects_indivisible_heads():
    mesh = seq_mesh(4)
    q, k, v = make_qkv(h=6, s=32)
    fn = shard_map(
        functools.partial(ulysses_attention, axis_name="seq", axis_size=4),
        mesh=mesh,
        in_specs=(P(None, None, "seq", None),) * 3,
        out_specs=P(None, None, "seq", None),
        check_vma=False,
    )
    with pytest.raises(ValueError, match="divisible"):
        with mesh:
            jax.jit(fn)(q, k, v)


def test_llama_forward_with_seq_axis():
    """Llama logits under seq=4 ring attention == single-device logits."""
    import dlrover_tpu.parallel.mesh as mesh_mod
    from dlrover_tpu.models.llama import (
        LlamaConfig, llama_apply, llama_init,
    )
    from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh, set_mesh

    config = LlamaConfig(
        vocab_size=64, dim=32, n_layers=2, n_heads=4, n_kv_heads=2,
        mlp_dim=64, max_seq_len=32, dtype="float32", attn_impl="reference",
    )
    params = llama_init(config, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 64)

    mesh_mod._global_mesh = None
    ref = llama_apply(config, params, tokens)

    mesh = build_mesh(MeshConfig(data=2, seq=4))
    set_mesh(mesh)
    try:
        with mesh:
            out = jax.jit(lambda p, t: llama_apply(config, p, t))(
                params, tokens)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)
    finally:
        mesh_mod._global_mesh = None


def test_sequence_sharded_attention_wrapper():
    import dlrover_tpu.parallel.mesh as mesh_mod
    from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh, set_mesh

    mesh = build_mesh(MeshConfig(data=2, seq=4, tensor=1))
    set_mesh(mesh)
    try:
        q, k, v = make_qkv(b=4, h=4, s=32)
        ref = mha_reference(q, k, v, causal=True)
        out = sequence_sharded_attention(q, k, v, mesh=mesh, impl="ring")
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
    finally:
        mesh_mod._global_mesh = None


def test_ring_kernel_path_is_taken(monkeypatch):
    """Causal rings must route through the Pallas block kernels
    (an earlier review), not the einsum fallback."""
    import dlrover_tpu.ops.attention as attn_mod
    import dlrover_tpu.parallel.sequence as seq_mod

    calls = {"n": 0}
    real = attn_mod.ring_fwd_block

    def counting(*args, **kwargs):
        calls["n"] += 1
        return real(*args, **kwargs)

    monkeypatch.setattr(attn_mod, "ring_fwd_block", counting)
    mesh = seq_mesh(4)
    q, k, v = make_qkv()
    fn = shard_map(
        functools.partial(seq_mod.ring_attention, axis_name="seq",
                          axis_size=4),
        mesh=mesh,
        in_specs=(P(None, None, "seq", None),) * 3,
        out_specs=P(None, None, "seq", None),
        check_vma=False,
    )
    sharding = NamedSharding(mesh, P(None, None, "seq", None))
    out = jax.jit(fn)(*(jax.device_put(x, sharding) for x in (q, k, v)))
    jax.block_until_ready(out)
    assert calls["n"] > 0
    ref = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ring_kernel_grads_match_gqa():
    """Kernel-ring gradients (custom VJP: second ring pass through the
    dq/dkv kernels with GLOBAL lse/delta) vs dense reference, with
    grouped kv heads."""
    mesh = seq_mesh(4)
    q, k, v = make_qkv(b=1, h=4, s=32, d=16, kv_heads=2, seed=3)

    def ref_loss(q, k, v):
        return jnp.sum(jnp.sin(mha_reference(q, k, v, causal=True)))

    fn = shard_map(
        functools.partial(ring_attention, axis_name="seq", axis_size=4),
        mesh=mesh,
        in_specs=(P(None, None, "seq", None),) * 3,
        out_specs=P(None, None, "seq", None),
        check_vma=False,
    )

    def ring_loss(q, k, v):
        return jnp.sum(jnp.sin(fn(q, k, v)))

    g_ref = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    with mesh:
        g_ring = jax.jit(jax.grad(ring_loss, argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)
