"""Laguna decoder (``model_type`` ``laguna``): sliding-window and full
attention layers with a sigmoid gate on each head's output, top-k
routed experts beside a shared expert, leading dense layers. Written
from the published ``config.json``; what it leaves open is listed as
``assumed`` in the benchmark's configuration file and below.

A layer, with ``x^ = RMSNorm(x)`` (no linear map has a bias)::

    a_h = softmax_{0 <= t-s < W}(q_h k_g(h)^T / sqrt(d)) v_g(h)    H heads
    x   = x + W_o concat_h(sigmoid(x^ W_gate)_h * a_h)
    y^  = RMSNorm(x)
    p   = softmax(y^ W_r) over all E experts;  T = top_k(p)
    w_e = c p_e / sum_T p          c: moe_routed_scaling_factor
    x   = x + sum_{e in T} w_e FFN_e(y^) + FFN_shared(y^)
    FFN(z) = (silu(z W_g) * z W_u) W_d

A ``sliding_attention`` layer has the window ``W`` and rotary embedding
on every channel of q and k (the default form); a ``full_attention``
layer has no window and YaRN rotary embedding on the first channels of
each head, its cos and sin times YaRN's attention factor. Query heads
``h`` read key/value head ``g(h) = h // (H / KV)``; the two kinds have
their own head counts. A ``dense`` layer has one SwiGLU ``FFN`` in
place of the experts. ``x_0 = E[tokens]``, ``logits = RMSNorm(x_L)
W_head`` (head untied).

The experts run through ``parallel/moe.routed_experts``, told which of
the model's experts this chip holds (``held_first``, ``held_experts``):
the router scores all ``n_experts``, and a pair whose expert lives
elsewhere adds nothing. No pair is dropped.

Layers are of three kinds, ``<mlp>_<attention>``: ``dense_full``,
``moe_window`` and ``moe_full``; like layers that follow one another
form a run, stacked on axis 0 and scanned by ``pipeline.stage_run_scan``
(a leading dense layer is a run of one). The carry is ``(x, counts)``;
the counts (token-expert pairs routed, pairs held, the largest and the
mean load of a held expert, summed over layers) leave the step with the
loss (``laguna_loss_fn(...).with_aux``). The rotary tables are made
once a step for each kind of attention and handed to every layer.

Assumed, where the published file is silent: the gate is one scalar a
head; softmax scoring with the top-k weights normalised over the k; the
shared expert added ungated; no q/k norm; the rotated channels of a
full layer are each head's first ``d x partial_rotary_factor``,
rotate-half pairing inside them. Every matrix is normal(0,
``init_range``), every norm's scale 1.

Precision: bf16 matmuls with float32 accumulation; the router's scores,
softmax, choice and weights, the rotary embedding and the gate in
float32.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from dlrover_tpu.common import telemetry
from dlrover_tpu.models.llama import (
    _rms_norm,
    _seq_axis_active,
    bhsd_flash_attention,
    flash_blocks,
)
from dlrover_tpu.ops.attention import mha_reference, window_computed_per_kept
from dlrover_tpu.ops.cross_entropy import fused_linear_cross_entropy
from dlrover_tpu.ops.fp8 import qdot, qeinsum
from dlrover_tpu.parallel.moe import COUNTS, routed_counts, routed_experts
from dlrover_tpu.parallel.moe import route as moe_route
from dlrover_tpu.parallel.sharding import shard_logical

# bf16 operands, float32 products and sums: one pass on the chip, where
# a product of two bf16 numbers is exact in float32 (XLA:CPU takes no
# bf16 x bf16 -> float32 dot at the default precision)
_EXACT = jax.lax.Precision.HIGHEST

# a layer's kind from its published attention and feed-forward types
_ATTENTION = {"full_attention": "full", "sliding_attention": "window"}
_MLP = {"dense": "dense", "sparse": "moe"}


@dataclasses.dataclass(frozen=True)
class LagunaConfig:
    vocab_size: int = 100352
    dim: int = 2048
    # one entry a layer: layer_types, mlp_layer_types and
    # num_attention_heads_per_layer of the published file
    layer_types: Tuple[str, ...] = (
        ("full_attention",) + ("sliding_attention",) * 3) * 10
    mlp_layer_types: Tuple[str, ...] = ("dense",) + ("sparse",) * 39
    heads: Tuple[int, ...] = (48, 64, 64, 64) * 10
    n_kv_heads: int = 8
    head_dim: int = 128
    window: int = 512                    # sliding_window
    mlp_dim: int = 8192                  # intermediate_size, dense layers
    n_experts: int = 256                 # the router's outputs
    held_first: int = 0                  # the experts this chip holds:
    held_experts: int = 256              # held_first .. + held_experts - 1
    top_k: int = 8
    expert_dim: int = 512                # moe_intermediate_size
    shared_dim: int = 512                # shared_expert_intermediate_size
    routed_scale: float = 2.5            # moe_routed_scaling_factor
    # full_attention: YaRN on part of each head
    full_rope_theta: float = 500000.0
    full_rotary_factor: float = 0.5
    yarn_factor: float = 64.0
    yarn_original: int = 4096
    yarn_beta_fast: float = 64.0
    yarn_beta_slow: float = 1.0
    yarn_attention_factor: float = 1.4158883083359672
    # sliding_attention: the default form on the whole head
    window_rope_theta: float = 10000.0
    window_rotary_factor: float = 1.0
    norm_eps: float = 1e-6
    init_range: float = 0.02
    dtype: str = "bfloat16"
    remat: bool = True                   # a layer keeps its input
    ce_chunks: int = 8                   # the head, a chunk of rows a time
    # attention dispatch shared with the llama family ("flash": the
    # kernels; anything else the plain form)
    attn_impl: str = "flash"
    attn_block_q: int = 1024
    attn_block_k: int = 1024
    # what else that dispatcher reads off a config; no field: nothing
    # sets another value
    attn_bwd_block_q = 0
    attn_bwd_block_k = 0

    def __post_init__(self):
        for name in ("layer_types", "mlp_layer_types", "heads"):
            object.__setattr__(self, name, tuple(getattr(self, name)))
        n = len(self.layer_types)
        if not n or len(self.mlp_layer_types) != n or len(self.heads) != n:
            raise ValueError(
                f"{n} layer_types, {len(self.mlp_layer_types)} "
                f"mlp_layer_types and {len(self.heads)} head counts: one "
                "of each a layer"
            )
        unknown = sorted(set(self.layer_types) - set(_ATTENTION)) + sorted(
            set(self.mlp_layer_types) - set(_MLP))
        if unknown:
            raise ValueError(f"unknown layer types {unknown}")
        for kind in set(self.kinds()):
            counts = {h for h, k in zip(self.heads, self.kinds()) if k == kind}
            if len(counts) > 1:
                raise ValueError(
                    f"layers of kind {kind} have {sorted(counts)} heads: "
                    "one count a kind (a run's weights are one stack)"
                )
        for h in self.heads:
            if h % self.n_kv_heads:
                raise ValueError(
                    f"{h} query heads do not divide into "
                    f"{self.n_kv_heads} key/value heads"
                )
        if self.held_first < 0 or self.held_experts < 1 or \
                self.held_first + self.held_experts > self.n_experts:
            raise ValueError(
                f"experts {self.held_first}..{self.held_first}+"
                f"{self.held_experts} are not among {self.n_experts}"
            )
        if not 1 <= self.top_k <= self.n_experts:
            raise ValueError(f"top_k {self.top_k} of {self.n_experts}")
        for factor in (self.full_rotary_factor, self.window_rotary_factor):
            rotary = self.head_dim * factor
            if rotary != int(rotary) or int(rotary) % 2 or rotary <= 0:
                raise ValueError(
                    f"rotary factor {factor} of a head of {self.head_dim} "
                    "is no even number of channels"
                )

    def kinds(self) -> tuple:
        """A layer's kind, ``<mlp>_<attention>``, one a layer."""
        return tuple(
            f"{_MLP[m]}_{_ATTENTION[a]}"
            for a, m in zip(self.layer_types, self.mlp_layer_types))

    def kind_heads(self, kind: str) -> int:
        return self.heads[self.kinds().index(kind)]

    @property
    def held(self) -> tuple:
        return (self.held_first, self.held_experts)

    def runs(self):
        """[(name, kind, layers)] of the runs of like layers, in order;
        the name keys the run's stacked parameters and sorts as the
        stack does."""
        from dlrover_tpu.parallel.pipeline import layer_runs

        return [
            (f"{i:02d}_{kind}", kind, count)
            for i, (kind, count) in enumerate(layer_runs(self.kinds()))
        ]

    def param_counts(self) -> dict:
        """Parameters by part, the labels of the ``model.params`` gauge:
        a layer's attention norm with its attention, its second norm
        with its dense MLP or its shared expert, the final norm with
        the head."""
        d, hd, kv = self.dim, self.head_dim, self.n_kv_heads
        counts = dict.fromkeys(
            ("attention", "gate", "router", "shared_expert", "experts",
             "dense_mlp"), 0)
        for kind, heads in zip(self.kinds(), self.heads):
            counts["attention"] += 2 * d * (heads + kv) * hd + d
            counts["gate"] += d * heads
            if kind.startswith("dense"):
                counts["dense_mlp"] += 3 * d * self.mlp_dim + d
            else:
                counts["router"] += d * self.n_experts
                counts["shared_expert"] += 3 * d * self.shared_dim + d
                counts["experts"] += \
                    self.held_experts * 3 * d * self.expert_dim
        counts["embedding"] = self.vocab_size * d
        counts["head"] = self.vocab_size * d + d
        return counts

    def param_count(self) -> int:
        return sum(self.param_counts().values())


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------


def _run_init(config: LagunaConfig, kind: str, rng, layers: int) -> dict:
    d, hd, kv = config.dim, config.head_dim, config.n_kv_heads
    heads = config.kind_heads(kind)
    keys = iter(jax.random.split(rng, 10))

    def normal(*shape):
        return jax.random.normal(next(keys), (layers, *shape)) \
            * config.init_range

    p = {
        "attn_norm": jnp.ones((layers, d)),
        "wq": normal(d, heads * hd),
        "wk": normal(d, kv * hd),
        "wv": normal(d, kv * hd),
        "wo": normal(heads * hd, d),
        "w_gate": normal(d, heads),
        "mlp_norm": jnp.ones((layers, d)),
    }
    if kind.startswith("dense"):
        # [gate | up]
        p["w_in"] = normal(d, 2 * config.mlp_dim)
        p["w_out"] = normal(config.mlp_dim, d)
        return p
    m, held = config.expert_dim, config.held_experts
    p["router"] = normal(d, config.n_experts)
    p["w_in"] = normal(held, d, 2 * m)
    p["w_out"] = normal(held, m, d)
    p["shared_in"] = normal(d, 2 * config.shared_dim)
    p["shared_out"] = normal(config.shared_dim, d)
    return p


def laguna_init(config: LagunaConfig, rng) -> dict:
    """Initialise params (fp32 masters): one stacked tree a run of like
    layers under ``layers``, keyed ``<index>_<kind>``."""
    runs = config.runs()
    keys = jax.random.split(rng, len(runs) + 2)
    std = config.init_range
    return {
        "embed": jax.random.normal(
            keys[0], (config.vocab_size, config.dim)) * std,
        "layers": {
            name: _run_init(config, kind, key, count)
            for (name, kind, count), key in zip(runs, keys[2:])
        },
        "final_norm": jnp.ones((config.dim,)),
        "lm_head": jax.random.normal(
            keys[1], (config.dim, config.vocab_size)) * std,
    }


_ATTENTION_AXES = {
    "attn_norm": ("layer", "embed"),
    "wq": ("layer", "embed", "heads"),
    "wk": ("layer", "embed", "kv_heads"),
    "wv": ("layer", "embed", "kv_heads"),
    "wo": ("layer", "heads", "embed"),
    "w_gate": ("layer", "embed", None),
    "mlp_norm": ("layer", "embed"),
}
_RUN_AXES = {
    "dense": {
        "w_in": ("layer", "embed", "mlp"),
        "w_out": ("layer", "mlp", "embed"),
    },
    # the experts held here are this chip's: the ``expert`` axis of a
    # mesh would divide the model's, which the layer refuses
    "moe": {
        "router": ("layer", "embed", None),
        "w_in": ("layer", None, "embed", "mlp"),
        "w_out": ("layer", None, "mlp", "embed"),
        "shared_in": ("layer", "embed", "mlp"),
        "shared_out": ("layer", "mlp", "embed"),
    },
}


def _kind_axes(kind: str) -> dict:
    return {**_ATTENTION_AXES, **_RUN_AXES[kind.split("_")[0]]}


def laguna_logical_axes(config: LagunaConfig) -> dict:
    """Logical sharding names matching the ``laguna_init`` tree."""
    return {
        "embed": ("vocab", "embed"),
        "layers": {name: _kind_axes(kind) for name, kind, _ in config.runs()},
        "final_norm": ("embed",),
        "lm_head": ("embed", "vocab"),
    }


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def yarn_inv_freq(dim: int, theta: float, factor: float, original: int,
                  beta_fast: float, beta_slow: float) -> np.ndarray:
    """YaRN's frequencies for ``dim`` rotated channels (Peng et al.,
    arXiv:2309.00071): the lowest interpolated by ``factor``, the
    highest kept, a linear ramp between the channels that turn
    ``beta_fast`` and ``beta_slow`` times over ``original`` positions
    (bounds rounded outwards)."""
    def channel(rotations):
        return dim * math.log(original / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(channel(beta_fast)), 0)
    high = min(math.ceil(channel(beta_slow)), dim - 1)
    if high == low:
        high += 0.001
    base = theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    kept = 1 - np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    return (1 / (factor * base)) * (1 - kept) + (1 / base) * kept


def rope_tables(config: LagunaConfig, seq: int) -> dict:
    """``{attention kind: (cos, sin)}``, float32 [S, rotated / 2] each:
    the full layers' YaRN tables times its attention factor, the window
    layers' default ones."""
    hd = config.head_dim
    full = int(hd * config.full_rotary_factor)
    window = int(hd * config.window_rotary_factor)
    freqs = {
        "full": yarn_inv_freq(
            full, config.full_rope_theta, config.yarn_factor,
            config.yarn_original, config.yarn_beta_fast,
            config.yarn_beta_slow),
        "window": 1 / config.window_rope_theta ** (
            np.arange(0, window, 2, dtype=np.float64) / window),
    }
    scale = {"full": config.yarn_attention_factor, "window": 1.0}
    positions = jnp.arange(seq, dtype=jnp.float32)[:, None]
    tables = {}
    for kind, f in freqs.items():
        angle = positions * jnp.asarray(f, jnp.float32)
        tables[kind] = (jnp.cos(angle) * scale[kind],
                        jnp.sin(angle) * scale[kind])
    return tables


def _rotary(x, cos, sin):
    """x [B, H, S, d]: rotate-half on the first ``2 x cos.shape[-1]``
    channels of each head, in float32; the rest pass."""
    half = cos.shape[-1]
    x = x.astype(jnp.float32)
    x1, x2 = x[..., :half], x[..., half:2 * half]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos, x[..., 2 * half:]], -1)


def _attention(config, attention, y, p, cos, sin):
    """The attention branch on the normed ``y [B, S, D]``: projections,
    rotary embedding, the kernels (a window where ``attention`` is
    "window"), the gate and ``W_o``."""
    dtype = y.dtype
    D = y.shape[-1]
    hd, kv = config.head_dim, config.n_kv_heads
    heads = p["wq"].shape[-1] // hd
    window = config.window if attention == "window" else None
    if _seq_axis_active():
        raise NotImplementedError(
            "laguna attention over a seq mesh axis: the ring and Ulysses "
            "schedules take no sliding window"
        )
    with jax.named_scope(f"attn_{attention}"):
        # the projections write the kernels' [B, H, S, d] layout
        w_qkv = jnp.concatenate(
            [p["wq"].astype(dtype).reshape(D, heads, hd),
             p["wk"].astype(dtype).reshape(D, kv, hd),
             p["wv"].astype(dtype).reshape(D, kv, hd)], axis=1)
        qkv = qeinsum("bsd,dhk->bhsk", y, w_qkv, site="attn_qkv")
        q = _rotary(qkv[:, :heads], cos, sin).astype(dtype)
        k = _rotary(qkv[:, heads:heads + kv], cos, sin).astype(dtype)
        v = qkv[:, heads + kv:]
        if config.attn_impl == "flash":
            if window is not None:
                blocks = flash_blocks(config, window)
                telemetry.gauge_set(
                    "model.attn.window_computed_per_kept",
                    window_computed_per_kept(q.shape[2], window,
                                             blocks["block_q"],
                                             blocks["block_k"]))
            out = bhsd_flash_attention(config, q, k, v, window=window)
        else:
            out = mha_reference(q, k, v, causal=True, window=window)
        with jax.named_scope("attn_gate"):
            gate = jax.nn.sigmoid(jnp.matmul(
                y, p["w_gate"].astype(dtype), precision=_EXACT,
                preferred_element_type=jnp.float32))
            out = (out.astype(jnp.float32)
                   * jnp.swapaxes(gate, 1, 2)[..., None]).astype(dtype)
        return qeinsum("bhsk,hkd->bsd", out,
                       p["wo"].astype(dtype).reshape(heads, hd, D),
                       site="attn_out")


def _swiglu(y, w_in, w_out, width, scope):
    """``(silu(y W_g) * y W_u) W_d``, ``w_in = [W_g | W_u]``."""
    dtype = y.dtype
    with jax.named_scope(scope):
        gu = qdot(y, w_in.astype(dtype), site="mlp")
        mid = jax.nn.silu(gu[..., :width]) * gu[..., width:]
        mid = shard_logical(mid, ("batch", "seq", "mlp"))
        return qdot(mid, w_out.astype(dtype), site="mlp")


def route(config: LagunaConfig, y, router):
    """(choice [B, S, k] int32, weight [B, S, k] float32): the top-k of
    the softmax over all the model's experts, the weights renormalised
    over the k and scaled by ``routed_scale`` (``moe.route``)."""
    return moe_route(y, router, scoring="softmax", top_k=config.top_k,
                     scale=config.routed_scale)


def _layer_fn(config: LagunaConfig, kind: str):
    """``stage_layer_scan``'s layer body of ``kind`` over the carry
    ``(x, counts)``. A forward pass that wants its choices of expert
    back gives the carry a third part, ``(choices [L, B, S, k], the
    layer's index)``, which every routed layer writes its row of."""
    mlp, attention = kind.split("_")

    def layer(carry, p, *tables):
        x, counts, *chosen = carry
        cos, sin = tables[:2] if attention == "full" else tables[2:]
        y = _rms_norm(x, p["attn_norm"], config.norm_eps)
        x = x + _attention(config, attention, y, p, cos, sin)
        x = shard_logical(x, ("batch", "seq", "embed"))
        y = _rms_norm(x, p["mlp_norm"], config.norm_eps)
        if mlp == "dense":
            x = x + _swiglu(y, p["w_in"], p["w_out"], config.mlp_dim,
                            "dense_mlp")
            choice = None
        else:
            choice, weight = route(config, y, p["router"])
            routed = routed_experts(
                y, choice, weight,
                {"w_in": p["w_in"].astype(x.dtype),
                 "w_out": p["w_out"].astype(x.dtype)},
                config.held)
            shared = _swiglu(y, p["shared_in"], p["shared_out"],
                             config.shared_dim, "shared_expert")
            x = x + routed + shared
            counts = counts + routed_counts(choice, config.held)
        x = shard_logical(x, ("batch", "seq", "embed"))
        if chosen:
            (rows, at), = chosen
            if choice is not None:
                rows = jax.lax.dynamic_update_index_in_dim(
                    rows, choice, at, 0)
            chosen = [(rows, at + 1)]
        return (x, counts, *chosen), jnp.zeros((), jnp.float32)

    return layer


def _stage_fn(config: LagunaConfig):
    """The whole stack: each run through the shared layer scan, the
    runs chained in their declared order."""
    from dlrover_tpu.parallel.pipeline import layer_input, stage_run_scan

    kinds = sorted(set(config.kinds()))
    return stage_run_scan(
        {kind: _layer_fn(config, kind) for kind in kinds},
        [(name, kind) for name, kind, _ in config.runs()],
        remat=config.remat,
        # every layer keeps its input and is recomputed from it in the
        # backward pass: what the default policy keeps, every weight
        # matmul's output, is about 1.6 GiB a routed layer at 2 x 8192
        # tokens (the pairs' rows). A full layer also keeps the
        # kernel's output and row statistic, so that its forward kernel
        # runs once; a window layer's runs again, at an eighth of a
        # full layer's work
        policy={
            kind: layer_input(keep=("attn_out",))
            if kind.endswith("full") else layer_input()
            for kind in kinds
        },
        layer_axes={
            kind: {k: tuple(v[1:]) for k, v in _kind_axes(kind).items()}
            for kind in kinds
        },
    )


def _hidden(config: LagunaConfig, params, tokens, choices=False):
    """tokens [B, S] -> (the last layer's x [B, S, D], counts [4]) and,
    with ``choices``, the experts every layer chose for every token in
    this pass, int32 [layers, B, S, k] (zeros in a dense layer's row)."""
    from dlrover_tpu.parallel.pipeline import pipe_size

    if pipe_size() > 1:
        raise NotImplementedError(
            "pipeline stages of unlike layers: the schedules shard one "
            "stacked tree over the pipe axis, and this stack is several "
            "(docs/DESIGN.md); use a mesh with pipe=1"
        )
    dtype = jnp.dtype(config.dtype)
    x = params["embed"].astype(dtype)[tokens]
    x = shard_logical(x, ("batch", "seq", "embed"))
    carry = (x, jnp.zeros((len(COUNTS),), jnp.float32))
    if choices:
        carry += ((jnp.zeros((len(config.layer_types), *tokens.shape,
                              config.top_k), jnp.int32),
                   jnp.zeros((), jnp.int32)),)
    tables = rope_tables(config, tokens.shape[1])
    (x, counts, *chosen), _aux = _stage_fn(config)(
        params["layers"], carry, *tables["full"], *tables["window"])
    return (x, counts, chosen[0][0]) if choices else (x, counts)


def laguna_apply(config: LagunaConfig, params, tokens, choices=False):
    """tokens [B, S] int32 -> logits [B, S, vocab] float32; with
    ``choices`` also the experts every layer chose for every token,
    int32 [layers, B, S, k], out of the same pass."""
    dtype = jnp.dtype(config.dtype)
    x, _counts, *chosen = _hidden(config, params, tokens, choices)
    with jax.named_scope("head"):
        x = _rms_norm(x, params["final_norm"], config.norm_eps)
        logits = x @ params["lm_head"].astype(dtype)
        logits = shard_logical(logits, ("batch", "seq", "vocab"))
        logits = logits.astype(jnp.float32)
    return (logits, *chosen) if choices else logits


def _publish_shape(config: LagunaConfig):
    """What was built, as gauges."""
    kinds = config.kinds()
    for kind in ("dense_full", "moe_window", "moe_full"):
        telemetry.gauge_set("model.layers", kinds.count(kind), kind=kind)
    for part, count in config.param_counts().items():
        telemetry.gauge_set("model.params", count, kind=part)
    telemetry.gauge_set("model.moe.experts", config.held_experts, kind="held")
    telemetry.gauge_set("model.moe.experts", config.n_experts,
                        kind="published")
    telemetry.gauge_set("model.moe.top_k", config.top_k)
    telemetry.gauge_set("model.attn.window", config.window)


def laguna_loss_fn(config: LagunaConfig):
    """Next-token CE loss closure for auto_accelerate, the head a chunk
    of rows at a time. ``with_aux`` is the same loss with the step's
    counts beside it, which the Trainer trains on; ``counters`` names
    those of them that are counts to add up (``COUNTS``: all)."""
    _publish_shape(config)

    def with_aux(params, batch, rng):
        tokens = batch["tokens"]
        x, counts = _hidden(config, params, tokens[:, :-1])
        with jax.named_scope("head"):
            loss_sum, valid = fused_linear_cross_entropy(
                x, params["lm_head"].astype(x.dtype), tokens[:, 1:],
                n_chunks=config.ce_chunks,
                norm_scale=params["final_norm"], norm_eps=config.norm_eps,
                # near the memorised batch the two roundings of the
                # unpinned logits moved the read-back loss by more than
                # it falls, below 0 once (PERF.md, section 6)
                pinned=True,
            )
        loss = loss_sum / jnp.maximum(valid, 1)
        return loss, dict(zip(COUNTS, jax.lax.stop_gradient(counts)))

    def loss_fn(params, batch, rng):
        return with_aux(params, batch, rng)[0]

    loss_fn.with_aux = with_aux
    loss_fn.counters = COUNTS
    return loss_fn
