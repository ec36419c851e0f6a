"""GLM-4.7-Flash decoder (``model_type`` ``glm4_moe_lite``): latent
attention (MLA) in every layer, sigmoid-scored routed experts with a
correction bias beside a shared expert, leading dense layers and one
multi-token-prediction (MTP) depth. Written from the published
``config.json`` and DeepSeek-V2/V3's description of MLA and MTP
(arXiv:2405.04434, arXiv:2412.19437 section 2.2); what they leave open
is listed as ``assumed`` in the benchmark's configuration file and
below.

A layer, with ``x^ = RMSNorm(x)`` (no linear map has a bias)::

    c_q           = RMSNorm_q(x^ W_qa)                  [S, q_rank]
    [q_nope|q_pe] = c_q W_qb                            H heads of nope + rope
    [c_kv | k_pe] = x^ W_kva                            [S, kv_rank + rope]
    [k_nope|v]    = RMSNorm_kv(c_kv) W_kvb              H heads of nope + v
    q_pe, k_pe rotated (rotate-half, rope_theta, positions 0..S-1);
    k_pe is one head, every head's
    a_h = softmax([q_nope|q_pe]_h [k_nope|k_pe]_h^T / sqrt(nope + rope)
                  + causal) v_h
    x   = x + W_o concat_h(a_h)
    y^  = RMSNorm(x)
    dense:  x = x + FFN(y^)
    routed: s = sigmoid(y^ W_r) over all E experts;  T = top_k(s + b)
            w_e = c s_e / sum_T s          c: routed_scaling_factor
            x = x + sum_{e in T} w_e FFN_e(y^) + FFN_shared(y^)
    FFN(z) = (silu(z W_g) * z W_u) W_d

``x_0 = E[t]``; ``logits = RMSNorm(x_L) W_head`` predicts ``t_{i+1}``
(head untied). The MTP depth takes the main stack's last hidden state
``h = x_L`` before the final norm and the next token's embedding::

    g = [RMSNorm_e(E[t_{i+1}]) | RMSNorm_h(h_i)] W_eh   [2 D, D]
    g = one routed layer of the form above (g)
    mtp_logits = RMSNorm_head(g) W_head                 predicts t_{i+2}

with the embedding and the head the main model's. The loss is ``CE_main
+ mtp_weight * CE_mtp``; the MTP depth's last position has no label.

The rotated slice and the shared ``k_pe`` are applied outside the
kernel: q and k go to the flash kernels as ``H`` heads of ``nope +
rope`` over ``H`` key/value heads, v at ``v_dim``, which the kernels
take where the two widths are equal (they are, 256, in the published
file); unlike widths are refused.

The experts run through ``parallel/moe.routed_experts``, told which of
the model's experts this chip holds (``held_first``, ``held_experts``):
the router scores all ``n_experts``, and a pair whose expert lives
elsewhere adds nothing. The correction bias ``b`` is a leaf of the
state at 0, its published initial value: no gradient reaches it, and
the rule that updates it is not run.

Layers are of two kinds, ``dense`` and ``moe``, in runs of like layers
stacked on axis 0 and scanned by ``pipeline.stage_run_scan``; the MTP
depth is one more ``moe`` run of one. The carry is ``(x, counts)``:
the routed counts (``moe.COUNTS``, the MTP depth's among them) leave
the step with the loss, beside ``lm.tokens`` and ``mtp.tokens``, the
positions with a label in each loss (``glm_lite_loss_fn(...).with_aux``).

Assumed, where the published file is silent: the latent norms' eps
(1e-6, DeepSeek-V3's ``RMSNorm`` default, where the layer norms take
``rms_norm_eps``); rotate-half pairing; the MTP layer's MLP
routed, and ``mtp_weight`` 0.3. Every matrix is normal(0,
``init_range``), every norm's scale 1.

Precision: bf16 matmuls with float32 accumulation; the router's scores,
sigmoid, choice and weights and the rotary embedding in float32.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from dlrover_tpu.common import telemetry
from dlrover_tpu.models.laguna import _rotary, _swiglu
from dlrover_tpu.models.llama import (
    _rms_norm,
    _seq_axis_active,
    bhsd_flash_attention,
)
from dlrover_tpu.ops.attention import mha_reference
from dlrover_tpu.ops.cross_entropy import fused_linear_cross_entropy
from dlrover_tpu.ops.fp8 import qdot, qeinsum
from dlrover_tpu.parallel.moe import COUNTS, routed_counts, routed_experts
from dlrover_tpu.parallel.moe import route as moe_route
from dlrover_tpu.parallel.sharding import shard_logical

# what the loss hands back beside the routed counts: the positions with
# a label in the main loss and in the MTP depth's
TOKEN_COUNTS = ("lm.tokens", "mtp.tokens")

_IGNORE = -100      # fused_linear_cross_entropy's ignore_index

# the latent norms' eps: DeepSeek-V3's RMSNorm default, which the
# published file does not set (its layer norms take rms_norm_eps)
_LATENT_EPS = 1e-6


@dataclasses.dataclass(frozen=True)
class GlmLiteConfig:
    vocab_size: int = 154880
    dim: int = 2048
    n_layers: int = 47                   # num_hidden_layers (no MTP)
    dense_layers: int = 1                # first_k_dense_replace
    heads: int = 20
    q_rank: int = 768                    # q_lora_rank
    kv_rank: int = 512                   # kv_lora_rank
    nope_dim: int = 192                  # qk_nope_head_dim
    rope_dim: int = 64                   # qk_rope_head_dim
    v_dim: int = 256                     # v_head_dim
    rope_theta: float = 1e6
    mlp_dim: int = 10240                 # intermediate_size, dense layers
    n_experts: int = 64                  # the router's outputs
    held_first: int = 0                  # the experts this chip holds:
    held_experts: int = 64               # held_first .. + held_experts - 1
    top_k: int = 4
    expert_dim: int = 1536               # moe_intermediate_size
    shared_dim: int = 1536               # n_shared_experts x the above
    routed_scale: float = 1.8            # routed_scaling_factor
    mtp_weight: float = 0.3              # the MTP loss's weight
    norm_eps: float = 1e-5
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
    # sets another value. At heads of 256 the dk/dv kernel's 1024 x 1024
    # tiles take 17.0 MB of the v5e's 16 MB of scoped VMEM (the
    # compiler's count for a described chip); 0 is the forward's block
    attn_bwd_block_q = 512
    attn_bwd_block_k = 0

    def __post_init__(self):
        if self.v_dim != self.head_dim:
            raise ValueError(
                f"q.k heads of {self.head_dim} and v heads of {self.v_dim}:"
                " the flash kernels take one head width for q, k and v"
            )
        if self.rope_dim % 2 or self.rope_dim <= 0:
            raise ValueError(f"a rotated slice of {self.rope_dim} channels")
        if not 0 <= self.dense_layers <= self.n_layers:
            raise ValueError(
                f"{self.dense_layers} dense layers of {self.n_layers}")
        if self.held_first < 0 or self.held_experts < 1 or \
                self.held_first + self.held_experts > self.n_experts:
            raise ValueError(
                f"experts {self.held_first}..{self.held_first}+"
                f"{self.held_experts} are not among {self.n_experts}"
            )
        if not 1 <= self.top_k <= self.n_experts:
            raise ValueError(f"top_k {self.top_k} of {self.n_experts}")

    @property
    def head_dim(self) -> int:
        """A head's width in q.k: the unrotated part and the slice."""
        return self.nope_dim + self.rope_dim

    @property
    def held(self) -> tuple:
        return (self.held_first, self.held_experts)

    def kinds(self) -> tuple:
        """The main stack's layer kinds, one a layer (the MTP depth's
        layer is one more ``moe``)."""
        return ("dense",) * self.dense_layers \
            + ("moe",) * (self.n_layers - self.dense_layers)

    def runs(self):
        """[(name, kind, layers)] of the main stack's runs, in order."""
        from dlrover_tpu.parallel.pipeline import layer_runs

        return [
            (f"{i:02d}_{kind}", kind, count)
            for i, (kind, count) in enumerate(layer_runs(self.kinds()))
        ]

    def param_counts(self) -> dict:
        """Parameters by part, the labels of the ``model.params`` gauge:
        a layer's attention norm with its attention, its second norm
        with its dense MLP or its shared expert, the final norm with
        the head; the MTP depth's layer among the layers' parts, its
        ``W_eh`` and three norms as ``mtp``."""
        d, h = self.dim, self.heads
        attention = (d * self.q_rank + self.q_rank
                     + self.q_rank * h * self.head_dim
                     + d * (self.kv_rank + self.rope_dim) + self.kv_rank
                     + self.kv_rank * h * (self.nope_dim + self.v_dim)
                     + h * self.v_dim * d + d)
        routed = self.n_layers - self.dense_layers + 1
        return {
            "attention": (self.n_layers + 1) * attention,
            "dense_mlp": self.dense_layers * (3 * d * self.mlp_dim + d),
            "router": routed * (d * self.n_experts + self.n_experts),
            "shared_expert": routed * (3 * d * self.shared_dim + d),
            "experts": routed * self.held_experts * 3 * d * self.expert_dim,
            "mtp": 2 * d * d + 3 * d,
            "embedding": self.vocab_size * d,
            "head": self.vocab_size * d + d,
        }

    def param_count(self) -> int:
        return sum(self.param_counts().values())


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------


def _run_init(config: GlmLiteConfig, kind: str, rng, layers: int) -> dict:
    d, h = config.dim, config.heads
    keys = iter(jax.random.split(rng, 10))

    def normal(*shape):
        return jax.random.normal(next(keys), (layers, *shape)) \
            * config.init_range

    p = {
        "attn_norm": jnp.ones((layers, d)),
        "wq_a": normal(d, config.q_rank),
        "q_norm": jnp.ones((layers, config.q_rank)),
        "wq_b": normal(config.q_rank, h * config.head_dim),
        "wkv_a": normal(d, config.kv_rank + config.rope_dim),
        "kv_norm": jnp.ones((layers, config.kv_rank)),
        "wkv_b": normal(config.kv_rank, h * (config.nope_dim + config.v_dim)),
        "wo": normal(h * config.v_dim, d),
        "mlp_norm": jnp.ones((layers, d)),
    }
    if kind == "dense":
        # [gate | up]
        p["w_in"] = normal(d, 2 * config.mlp_dim)
        p["w_out"] = normal(config.mlp_dim, d)
        return p
    m, held = config.expert_dim, config.held_experts
    p["router"] = normal(d, config.n_experts)
    p["router_bias"] = jnp.zeros((layers, config.n_experts))
    p["w_in"] = normal(held, d, 2 * m)
    p["w_out"] = normal(held, m, d)
    p["shared_in"] = normal(d, 2 * config.shared_dim)
    p["shared_out"] = normal(config.shared_dim, d)
    return p


def glm_lite_init(config: GlmLiteConfig, rng) -> dict:
    """Initialise params (fp32 masters): one stacked tree a run of like
    layers under ``layers``, keyed ``<index>_<kind>``; the MTP depth
    under ``mtp``, its layer a ``moe`` run of one."""
    runs = config.runs()
    keys = jax.random.split(rng, len(runs) + 4)
    d, std = config.dim, config.init_range
    return {
        "embed": jax.random.normal(keys[0], (config.vocab_size, d)) * std,
        "layers": {
            name: _run_init(config, kind, key, count)
            for (name, kind, count), key in zip(runs, keys[4:])
        },
        "final_norm": jnp.ones((d,)),
        "lm_head": jax.random.normal(keys[1], (d, config.vocab_size)) * std,
        "mtp": {
            "e_norm": jnp.ones((d,)),
            "h_norm": jnp.ones((d,)),
            "w_eh": jax.random.normal(keys[2], (2 * d, d)) * std,
            "layer": _run_init(config, "moe", keys[3], 1),
            "head_norm": jnp.ones((d,)),
        },
    }


_ATTENTION_AXES = {
    "attn_norm": ("layer", "embed"),
    "wq_a": ("layer", "embed", None),
    "q_norm": ("layer", None),
    "wq_b": ("layer", None, "heads"),
    "wkv_a": ("layer", "embed", None),
    "kv_norm": ("layer", None),
    "wkv_b": ("layer", None, "heads"),
    "wo": ("layer", "heads", "embed"),
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
        "router_bias": ("layer", None),
        "w_in": ("layer", None, "embed", "mlp"),
        "w_out": ("layer", None, "mlp", "embed"),
        "shared_in": ("layer", "embed", "mlp"),
        "shared_out": ("layer", "mlp", "embed"),
    },
}


def _kind_axes(kind: str) -> dict:
    return {**_ATTENTION_AXES, **_RUN_AXES[kind]}


def glm_lite_logical_axes(config: GlmLiteConfig) -> dict:
    """Logical sharding names matching the ``glm_lite_init`` tree."""
    return {
        "embed": ("vocab", "embed"),
        "layers": {name: _kind_axes(kind) for name, kind, _ in config.runs()},
        "final_norm": ("embed",),
        "lm_head": ("embed", "vocab"),
        "mtp": {
            "e_norm": ("embed",),
            "h_norm": ("embed",),
            "w_eh": (None, "embed"),
            "layer": _kind_axes("moe"),
            "head_norm": ("embed",),
        },
    }


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def rope_tables(config: GlmLiteConfig, seq: int):
    """(cos, sin), float32 [S, rope_dim / 2]: the rotated slice's
    tables, made once a step and handed to every layer."""
    inv = 1 / config.rope_theta ** (
        np.arange(0, config.rope_dim, 2, dtype=np.float64) / config.rope_dim)
    angle = jnp.arange(seq, dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv, jnp.float32)
    return jnp.cos(angle), jnp.sin(angle)


def _mla(config: GlmLiteConfig, y, p, cos, sin):
    """Latent attention on the normed ``y [B, S, D]``: the projections
    write the kernels' [B, H, S, d] layout, the rotary embedding on the
    slice, the one rotated key broadcast to every head, the kernels and
    ``W_o``."""
    if _seq_axis_active():
        raise NotImplementedError(
            "latent attention over a seq mesh axis: the ring and Ulysses "
            "schedules take no rotated key shared by the heads"
        )
    dtype = y.dtype
    D = y.shape[-1]
    H, nope, rope = config.heads, config.nope_dim, config.rope_dim
    with jax.named_scope("mla_q"):
        c_q = _rms_norm(qdot(y, p["wq_a"].astype(dtype), site="mla_q"),
                        p["q_norm"], _LATENT_EPS)
        q = qeinsum("bsr,rhk->bhsk", c_q,
                    p["wq_b"].astype(dtype).reshape(-1, H, nope + rope),
                    site="mla_q")
    with jax.named_scope("mla_kv"):
        kv_a = qdot(y, p["wkv_a"].astype(dtype), site="mla_kv")
        c_kv = _rms_norm(kv_a[..., :config.kv_rank], p["kv_norm"],
                         _LATENT_EPS)
        kv = qeinsum(
            "bsr,rhk->bhsk", c_kv,
            p["wkv_b"].astype(dtype).reshape(-1, H, nope + config.v_dim),
            site="mla_kv")
    with jax.named_scope("mla_attn"):
        q = jnp.concatenate(
            [q[..., :nope], _rotary(q[..., nope:], cos, sin).astype(dtype)],
            -1)
        k_pe = _rotary(kv_a[:, None, :, config.kv_rank:], cos, sin)
        k = jnp.concatenate(
            [kv[..., :nope],
             jnp.broadcast_to(k_pe.astype(dtype), (*kv.shape[:3], rope))],
            -1)
        v = kv[..., nope:]
        if config.attn_impl == "flash":
            out = bhsd_flash_attention(config, q, k, v)
        else:
            out = mha_reference(q, k, v, causal=True)
    with jax.named_scope("mla_out"):
        return qeinsum("bhsk,hkd->bsd", out,
                       p["wo"].astype(dtype).reshape(H, config.v_dim, D),
                       site="mla_out")


def route(config: GlmLiteConfig, y, router, bias):
    """(choice [B, S, k] int32, weight [B, S, k] float32): the top-k of
    the sigmoid scores plus the correction bias over all the model's
    experts, the weights the scores alone, normalised over the k and
    scaled by ``routed_scale`` (``moe.route``)."""
    return moe_route(y, router, scoring="sigmoid", top_k=config.top_k,
                     scale=config.routed_scale, bias=bias)


def _layer_fn(config: GlmLiteConfig, kind: str):
    """``stage_layer_scan``'s layer body of ``kind`` over the carry
    ``(x, counts)``. A forward pass that wants its choices of expert
    back gives the carry a third part, ``(choices [L, B, S, k], the
    layer's index)``, which every routed layer writes its row of."""

    def layer(carry, p, cos, sin):
        x, counts, *chosen = carry
        y = _rms_norm(x, p["attn_norm"], config.norm_eps)
        x = x + _mla(config, y, p, cos, sin)
        x = shard_logical(x, ("batch", "seq", "embed"))
        y = _rms_norm(x, p["mlp_norm"], config.norm_eps)
        if kind == "dense":
            x = x + _swiglu(y, p["w_in"], p["w_out"], config.mlp_dim,
                            "dense_mlp")
            choice = None
        else:
            choice, weight = route(config, y, p["router"], p["router_bias"])
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


def _stage_fn(config: GlmLiteConfig, runs):
    """``runs`` ``[(name, kind)]`` through the shared layer scan, in
    order. Every layer keeps its input and the kernel's output and row
    statistic, and is recomputed from them in the backward pass: the
    forward kernel runs once a layer."""
    from dlrover_tpu.parallel.pipeline import layer_input, stage_run_scan

    kinds = sorted({kind for _, kind in runs})
    return stage_run_scan(
        {kind: _layer_fn(config, kind) for kind in kinds},
        runs,
        remat=config.remat,
        policy={kind: layer_input(keep=("attn_out",)) for kind in kinds},
        layer_axes={
            kind: {k: tuple(v[1:]) for k, v in _kind_axes(kind).items()}
            for kind in kinds
        },
    )


def _forward(config: GlmLiteConfig, params, tokens, next_tokens,
             choices=False):
    """tokens [B, S] and each position's next token [B, S] -> (the main
    stack's last hidden state h [B, S, D], the MTP depth's g [B, S, D],
    counts [4]) and, with ``choices``, the experts every layer chose for
    every token, int32 [layers + 1, B, S, k] (the MTP depth's row last,
    zeros in a dense layer's)."""
    from dlrover_tpu.parallel.pipeline import pipe_size

    if pipe_size() > 1:
        raise NotImplementedError(
            "pipeline stages of unlike layers: the schedules shard one "
            "stacked tree over the pipe axis, and this stack is several "
            "(docs/DESIGN.md); use a mesh with pipe=1"
        )
    dtype = jnp.dtype(config.dtype)
    embed = params["embed"].astype(dtype)
    x = shard_logical(embed[tokens], ("batch", "seq", "embed"))
    carry = (x, jnp.zeros((len(COUNTS),), jnp.float32))
    if choices:
        carry += ((jnp.zeros((config.n_layers + 1, *tokens.shape,
                              config.top_k), jnp.int32),
                   jnp.zeros((), jnp.int32)),)
    cos, sin = rope_tables(config, tokens.shape[1])
    (h, *rest), _aux = _stage_fn(
        config, [(name, kind) for name, kind, _ in config.runs()])(
            params["layers"], carry, cos, sin)
    mtp = params["mtp"]
    with jax.named_scope("mtp"):
        e = _rms_norm(embed[next_tokens], mtp["e_norm"], config.norm_eps)
        g = qdot(jnp.concatenate(
            [e, _rms_norm(h, mtp["h_norm"], config.norm_eps)], -1),
            mtp["w_eh"].astype(dtype), site="mtp")
        g = shard_logical(g, ("batch", "seq", "embed"))
        (g, counts, *chosen), _aux = _stage_fn(config, [("layer", "moe")])(
            mtp, (g, *rest), cos, sin)
    return (h, g, counts, chosen[0][0]) if choices else (h, g, counts)


def glm_lite_apply(config: GlmLiteConfig, params, tokens, choices=False):
    """tokens [B, S] int32 -> (logits [B, S, vocab], the MTP depth's
    logits [B, S - 1, vocab], float32): the MTP depth at position ``i``
    reads ``tokens[i + 1]``, which the last position lacks (it is given
    its own token and its row dropped: no earlier row sees it). With
    ``choices`` also the experts every layer chose for every token,
    int32 [layers + 1, B, S, k], out of the same pass."""
    dtype = jnp.dtype(config.dtype)
    next_tokens = jnp.concatenate([tokens[:, 1:], tokens[:, -1:]], 1)
    h, g, _counts, *chosen = _forward(config, params, tokens, next_tokens,
                                      choices)
    with jax.named_scope("head"):
        head = params["lm_head"].astype(dtype)
        logits = _rms_norm(h, params["final_norm"], config.norm_eps) @ head
        mtp_logits = _rms_norm(g[:, :-1], params["mtp"]["head_norm"],
                               config.norm_eps) @ head
        logits, mtp_logits = (
            shard_logical(x, ("batch", "seq", "vocab")).astype(jnp.float32)
            for x in (logits, mtp_logits))
    return (logits, mtp_logits, *chosen)


def _publish_shape(config: GlmLiteConfig):
    """What was built, as gauges."""
    kinds = config.kinds()
    for kind in ("dense", "moe"):
        telemetry.gauge_set("model.layers", kinds.count(kind), kind=kind)
    telemetry.gauge_set("model.layers", 1, kind="mtp")
    for part, count in config.param_counts().items():
        telemetry.gauge_set("model.params", count, kind=part)
    telemetry.gauge_set("model.moe.experts", config.held_experts, kind="held")
    telemetry.gauge_set("model.moe.experts", config.n_experts,
                        kind="published")
    telemetry.gauge_set("model.moe.top_k", config.top_k)


def glm_lite_loss_fn(config: GlmLiteConfig):
    """``CE_main + mtp_weight * CE_mtp`` for auto_accelerate, each head a
    chunk of rows at a time. ``with_aux`` is the same loss with the
    step's counts beside it, which the Trainer trains on; ``counters``
    names them (``moe.COUNTS`` and ``TOKEN_COUNTS``)."""
    _publish_shape(config)

    def with_aux(params, batch, rng):
        tokens = batch["tokens"]
        labels = tokens[:, 1:]
        h, g, counts = _forward(config, params, tokens[:, :-1], labels)
        # t_{i+2}: the last position has none
        mtp_labels = jnp.concatenate(
            [tokens[:, 2:], jnp.full((tokens.shape[0], 1), _IGNORE,
                                     tokens.dtype)], 1)
        with jax.named_scope("head"):
            head = params["lm_head"].astype(h.dtype)
            # one call a head: the two norms' scales differ, and so do
            # the losses' weights
            main, main_n = fused_linear_cross_entropy(
                h, head, labels, n_chunks=config.ce_chunks,
                norm_scale=params["final_norm"], norm_eps=config.norm_eps,
                ignore_index=_IGNORE, pinned=True)
            mtp, mtp_n = fused_linear_cross_entropy(
                g, head, mtp_labels, n_chunks=config.ce_chunks,
                norm_scale=params["mtp"]["head_norm"],
                norm_eps=config.norm_eps, ignore_index=_IGNORE, pinned=True)
        loss = main / jnp.maximum(main_n, 1) \
            + config.mtp_weight * mtp / jnp.maximum(mtp_n, 1)
        aux = dict(zip(COUNTS, jax.lax.stop_gradient(counts)))
        aux.update(zip(TOKEN_COUNTS, (main_n.astype(jnp.float32),
                                      mtp_n.astype(jnp.float32))))
        return loss, aux

    def loss_fn(params, batch, rng):
        return with_aux(params, batch, rng)[0]

    loss_fn.with_aux = with_aux
    loss_fn.counters = COUNTS + TOKEN_COUNTS
    return loss_fn
