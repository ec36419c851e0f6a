"""Slotted KV-cache decode engine: the device half of continuous
batching.

Equivalent capability: the reference's inference backend serves many
concurrent users through vLLM's paged KV cache. TPU redesign: paging
through an allocator of 4 KB blocks is a pointer-chasing workload a
static-shape compiler hates, so the pool is **slotted** instead — a
fixed device-resident cache of ``S`` slots (the batch dimension), each
slot an independent ring buffer of ``C`` positions with its OWN
position row (the tiered-embedding slot-map idiom from PR 1: fixed
device residency, host-side slot map deciding who lives where). The
two jitted programs are:

- :func:`slot_prefill` — write ONE admitted sequence's prompt K/V into
  one slot. Prompts are padded to power-of-two **length buckets**
  (masked positions, the real length is a traced scalar), so the jit
  cache holds one trace per bucket, never one per prompt length.
- :func:`slot_decode` — ONE decode step for the whole pool, whatever
  mix of live slots exists: per-slot absolute positions, per-slot
  ring-buffer write indices, per-slot temperature, sampling in-jit.
  Dead slots compute garbage nobody reads (their ``pos`` rows mark
  everything invalid and admission fully resets the row), which is
  exactly what makes **mid-step admission and eviction free**: the
  host flips its slot map; the compiled program never changes shape.

GQA is native like the training decode path (the cache stores KVH
heads, queries expand on read); the numerics are checked against the
non-cached full-attention forward in tests/test_serving.py.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from dlrover_tpu.common.backend import require_backend
from dlrover_tpu.common.log import get_logger
from dlrover_tpu.models.llama import (
    LlamaConfig,
    _rms_norm,
    _rope,
)

# shared with the PPO decode backend, where they are defined: the ONE
# prompt-bucketing policy and the ONE decode-shape MoE mixture, so the
# two decode paths' jit-cache shapes and MoE numerics cannot drift
from dlrover_tpu.rl.generation import (  # noqa: F401 - re-exported
    MIN_PROMPT_BUCKET as MIN_BUCKET,
    bucket_len,
    moe_mixture,
)

logger = get_logger(__name__)


class SlotKVCache(NamedTuple):
    """``k``/``v`` are [L, S, C, KVH, hd]; ``pos`` is [S, C] — each
    slot's ring carries its OWN absolute positions (-1 = invalid), so
    sequences of different lengths coexist in one decode step."""

    k: jnp.ndarray
    v: jnp.ndarray
    pos: jnp.ndarray  # [S, C] int32


def init_slot_cache(
    config: LlamaConfig, slots: int, capacity: int, dtype=None
) -> SlotKVCache:
    dtype = dtype or jnp.dtype(config.dtype)
    shape = (
        config.n_layers, slots, capacity, config.n_kv_heads,
        config.head_dim,
    )
    return SlotKVCache(
        k=jnp.zeros(shape, dtype),
        v=jnp.zeros(shape, dtype),
        pos=jnp.full((slots, capacity), -1, jnp.int32),
    )


def _sample(logits, rng, temperature):
    """Greedy when temperature <= 0, else categorical at the given
    per-row temperature. logits [N, V], temperature [N] -> (tok [N],
    logprob [N])."""
    safe_t = jnp.where(temperature > 0, temperature, 1.0)
    drawn = jax.random.categorical(rng, logits / safe_t[:, None])
    greedy = jnp.argmax(logits, axis=-1)
    tok = jnp.where(temperature > 0, drawn, greedy)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return tok, jnp.take_along_axis(logp, tok[:, None], axis=-1)[:, 0]


def _mlp(config: LlamaConfig, p, y, dtype):
    if config.is_moe:
        return moe_mixture(config, p, y, dtype)
    gate = jax.nn.silu(y @ p["w_gate"].astype(dtype))
    up = y @ p["w_up"].astype(dtype)
    return (gate * up) @ p["w_down"].astype(dtype)


# ------------------------------------------------------------------ prefill


def slot_prefill(
    config: LlamaConfig, params, cache: SlotKVCache, tokens, length,
    slot, rng, temperature,
):
    """Admit one sequence: run the prompt forward, write its K/V into
    ``slot``'s ring, fully reset that slot's position row, and sample
    the first output token.

    ``tokens`` is [Pb] (one bucket-padded prompt), ``length``/``slot``
    are traced scalars — one trace per bucket Pb, never per prompt
    length. Positions past ``length`` are marked -1 so pads can never
    be attended; the first-token logits are read at ``length - 1``.
    Returns (cache, token, logprob).
    """
    dtype = jnp.dtype(config.dtype)
    (Pb,) = tokens.shape
    C = cache.pos.shape[1]
    h, kvh, hd = config.n_heads, config.n_kv_heads, config.head_dim
    rep = h // kvh

    idx = jnp.arange(Pb, dtype=jnp.int32)
    positions = jnp.where(idx < length, idx, -1)[None, :]  # [1, Pb]
    x = params["embed"].astype(dtype)[tokens][None, :, :]  # [1, Pb, D]

    # self-attention over the prompt only: a freshly admitted slot owns
    # no other context, so prefill never reads the pool cache — it just
    # computes K/V once and scatters them in afterwards
    q_pos = positions[0]
    valid = (q_pos[None, :] >= 0) & (q_pos[None, :] <= q_pos[:, None])

    def layer(carry, p):
        hdn = carry
        y = _rms_norm(hdn, p["attn_norm"], config.norm_eps)
        q = (y @ p["wq"].astype(dtype)).reshape(1, Pb, h, hd)
        k = (y @ p["wk"].astype(dtype)).reshape(1, Pb, kvh, hd)
        v = (y @ p["wv"].astype(dtype)).reshape(1, Pb, kvh, hd)
        q = _rope(q, positions, config.rope_theta)
        k = _rope(k, positions, config.rope_theta)
        kr = jnp.repeat(k, rep, axis=2)
        vr = jnp.repeat(v, rep, axis=2)
        scores = jnp.einsum("bshd,bchd->bhsc", q, kr) / jnp.sqrt(
            jnp.asarray(hd, jnp.float32)
        ).astype(q.dtype)
        scores = jnp.where(
            valid[None, None, :, :], scores,
            jnp.asarray(-1e30, scores.dtype),
        )
        probs = jax.nn.softmax(
            scores.astype(jnp.float32), axis=-1
        ).astype(q.dtype)
        attn = jnp.einsum("bhsc,bchd->bshd", probs, vr).reshape(
            1, Pb, h * hd
        )
        hdn = hdn + attn @ p["wo"].astype(dtype)
        y = _rms_norm(hdn, p["mlp_norm"], config.norm_eps)
        hdn = hdn + _mlp(config, p, y, dtype)
        return hdn, (k[0], v[0])

    hidden, (ks, vs) = jax.lax.scan(layer, x, params["layers"])
    # ks/vs: [L, Pb, KVH, hd] -> slot's ring indices 0..Pb-1 (bucket
    # <= C is enforced host-side, so the prompt never wraps at admit)
    new_k = cache.k.at[:, slot, :Pb].set(ks)
    new_v = cache.v.at[:, slot, :Pb].set(vs)
    # FULL row reset: whatever a previous occupant left at higher ring
    # indices becomes invalid the moment this admission lands
    row = jnp.arange(C, dtype=jnp.int32)
    new_row = jnp.where(row < length, row, -1)
    new_pos = cache.pos.at[slot].set(new_row)

    last = jnp.clip(length - 1, 0, Pb - 1)
    logits = _rms_norm(
        hidden[0, last][None, :], params["final_norm"], config.norm_eps
    )
    logits = (
        logits @ params["lm_head"].astype(logits.dtype)
    ).astype(jnp.float32)
    tok, logp = _sample(logits, rng, temperature[None])
    return SlotKVCache(new_k, new_v, new_pos), tok[0], logp[0]


# ------------------------------------------------------------------- decode


def slot_decode(
    config: LlamaConfig, params, cache: SlotKVCache, tokens,
    positions, live, rng, temperature,
):
    """One token for every slot of the pool. ``tokens``/``positions``/
    ``live``/``temperature`` are [S]; each live slot consumes its token
    at its OWN absolute position and writes K/V at ``position % C`` of
    its own ring. Dead slots compute garbage nobody reads: their writes
    land at ring index 0 with ``pos = -1`` (still invalid), and
    admission resets the whole row anyway. Returns (cache, next_tokens
    [S], logprobs [S])."""
    dtype = jnp.dtype(config.dtype)
    S = tokens.shape[0]
    C = cache.pos.shape[1]
    h, kvh, hd = config.n_heads, config.n_kv_heads, config.head_dim
    rep = h // kvh

    safe_pos = jnp.where(live, positions, 0)
    write_idx = safe_pos % C
    rows = jnp.arange(S)
    pos2 = safe_pos[:, None]  # [S, 1]
    x = params["embed"].astype(dtype)[tokens][:, None, :]  # [S, 1, D]

    def layer(carry, xs):
        hdn = carry
        p, ck, cv = xs
        y = _rms_norm(hdn, p["attn_norm"], config.norm_eps)
        q = (y @ p["wq"].astype(dtype)).reshape(S, 1, h, hd)
        k = (y @ p["wk"].astype(dtype)).reshape(S, 1, kvh, hd)
        v = (y @ p["wv"].astype(dtype)).reshape(S, 1, kvh, hd)
        q = _rope(q, pos2, config.rope_theta)
        k = _rope(k, pos2, config.rope_theta)
        ck = ck.at[rows, write_idx].set(k[:, 0])
        cv = cv.at[rows, write_idx].set(v[:, 0])
        kr = jnp.repeat(ck, rep, axis=2)  # [S, C, H, hd]
        vr = jnp.repeat(cv, rep, axis=2)
        scores = jnp.einsum("shd,schd->shc", q[:, 0], kr) / jnp.sqrt(
            jnp.asarray(hd, jnp.float32)
        ).astype(q.dtype)
        # a slot attends its own ring only: written, and causally
        # visible from ITS position (this very step's write included)
        new_row_pos = cache.pos.at[rows, write_idx].set(
            jnp.where(live, positions, -1)
        )
        valid = (new_row_pos >= 0) & (new_row_pos <= safe_pos[:, None])
        scores = jnp.where(
            valid[:, None, :], scores, jnp.asarray(-1e30, scores.dtype)
        )
        probs = jax.nn.softmax(
            scores.astype(jnp.float32), axis=-1
        ).astype(q.dtype)
        attn = jnp.einsum("shc,schd->shd", probs, vr).reshape(
            S, 1, h * hd
        )
        hdn = hdn + attn @ p["wo"].astype(dtype)
        y = _rms_norm(hdn, p["mlp_norm"], config.norm_eps)
        hdn = hdn + _mlp(config, p, y, dtype)
        return hdn, (ck, cv)

    hidden, (new_k, new_v) = jax.lax.scan(
        layer, x, (params["layers"], cache.k, cache.v)
    )
    new_pos = cache.pos.at[rows, write_idx].set(
        jnp.where(live, positions, -1)
    )
    logits = _rms_norm(
        hidden[:, 0, :], params["final_norm"], config.norm_eps
    )
    logits = (
        logits @ params["lm_head"].astype(logits.dtype)
    ).astype(jnp.float32)
    tok, logp = _sample(logits, rng, temperature)
    return SlotKVCache(new_k, new_v, new_pos), tok, logp


# ------------------------------------------------------------------- engine


class DecodeEngine:
    """Host handle over the jitted slot programs: owns the device
    cache, hands the scheduler ``admit``/``step``. The jit caches are
    bounded by construction — ``admit`` traces once per prompt bucket
    (power-of-two lengths up to the ring capacity), ``step`` exactly
    once (the pool's shape never changes)."""

    def __init__(
        self,
        config: LlamaConfig,
        params,
        slots: int = 8,
        capacity: int = 128,
        min_bucket: int = MIN_BUCKET,
    ):
        # a decode pool that lost the chip must not serve from JAX's
        # silent CPU fallback
        require_backend()
        self.config = config
        self.params = params
        self.slots = int(slots)
        self.capacity = int(capacity)
        self.min_bucket = int(min_bucket)
        self.cache = init_slot_cache(config, self.slots, self.capacity)
        self._prefill = jax.jit(partial(slot_prefill, config))
        self._decode = jax.jit(partial(slot_decode, config))

    def bucket_for(self, n: int) -> int:
        return bucket_len(n, self.capacity, self.min_bucket)

    def admit(self, slot: int, prompt, rng, temperature: float):
        """Prefill ``prompt`` (a 1-D int sequence) into ``slot`` and
        sample its first token. Prompts longer than the ring keep their
        last ``capacity`` tokens (the sliding-window contract). Returns
        (token, logprob, prompt_len_used)."""
        toks = jnp.asarray(prompt, jnp.int32).reshape(-1)
        if toks.shape[0] > self.capacity:
            toks = toks[-self.capacity:]
        n = int(toks.shape[0])
        bucket = self.bucket_for(n)
        padded = jnp.zeros((bucket,), jnp.int32).at[:n].set(toks)
        self.cache, tok, logp = self._prefill(
            self.params, self.cache, padded, n, slot, rng,
            jnp.asarray(temperature, jnp.float32),
        )
        return int(tok), float(logp), n

    def step(self, tokens, positions, live, rng, temperature):
        """One decode step over the whole pool (arrays of length
        ``slots``). Returns (next_tokens, logprobs) as host lists."""
        self.cache, tok, logp = self._decode(
            self.params, self.cache,
            jnp.asarray(tokens, jnp.int32),
            jnp.asarray(positions, jnp.int32),
            jnp.asarray(live, bool),
            rng,
            jnp.asarray(temperature, jnp.float32),
        )
        return np.asarray(tok), np.asarray(logp)

    def warmup(self, buckets=None):
        """Compile the decode step and the given prompt buckets (all
        power-of-two buckets up to capacity when None) ahead of
        traffic, so the first admission's lease never expires inside a
        multi-second XLA compile."""
        if buckets is None:
            buckets = []
            b = self.min_bucket
            while b < self.capacity:
                buckets.append(b)
                b <<= 1
            buckets.append(self.capacity)
        for b in sorted({self.bucket_for(int(n)) for n in buckets}):
            padded = jnp.zeros((b,), jnp.int32)
            # functional call: the returned cache is dropped, so
            # warmup never perturbs pool state
            _cache, _t, _l = self._prefill(
                self.params, self.cache, padded, 1, 0,
                jax.random.key(0), jnp.asarray(0.0, jnp.float32),
            )
        self._decode(
            self.params, self.cache,
            jnp.zeros((self.slots,), jnp.int32),
            jnp.zeros((self.slots,), jnp.int32),
            jnp.zeros((self.slots,), bool),
            jax.random.key(0),
            jnp.zeros((self.slots,), jnp.float32),
        )

    def prefill_traces(self) -> int:
        """Compiled prefill variants (== distinct buckets seen); the
        bounded-jit-cache assertion tests read this."""
        return self._prefill._cache_size()

    def decode_traces(self) -> int:
        return self._decode._cache_size()
