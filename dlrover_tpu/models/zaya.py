"""ZAYA1 decoder (``model_type`` ``zaya``): compressed convolutional
attention, a router MLP whose state is carried through depth, top-1
routed experts, learned residual scaling. Written from the published
description (Compressed Convolutional Attention, arXiv:2510.04476; the
ZAYA1 technical report, arXiv:2511.17127) and the sizes of the model's
``config.json``; what those leave open is listed as ``assumed`` in the
benchmark's configuration file and below.

Every layer is of one kind. It takes the hidden state ``x [S, D]`` and
the router state of the layer below, ``r_prev [S, R]`` (zeros into the
first layer), and hands both on. Linear maps have no bias unless one is
written; ``Hq`` query heads and ``Hk`` key/value heads of ``d``, ``G =
Hq / Hk``::

    h  = RMSNorm(x; g_a)                                attention sub-block
    q~ = h W_q,  k~ = h W_k,  u = [q~ | k~]             Hq + Hk heads of d
    c1_t = b1 + sum_j w1[j] * u_{t-T0+1+j}              depthwise, causal
    c2_t[g] = b2[g] + sum_j c1_{t-T1+1+j}[g] W2[g, j]   grouped by head
    [q_c | k_c] = c2
    m_q[i] = (q~[i] + k~[i // G]) / 2,  m_k[j] = mean of m_q over its G
    q = q_c + m_q,  k = k_c + m_k                       the q-k mean
    v_t = [h_t W_v1 | h_{t-1} W_v2]                     the value shift
    q^ = sqrt(d) q / ||q||,  k^ = tau_j sqrt(d) k / ||k||     a head
    rotary embedding on the first d * partial_rotary_factor dims a head
    a  = softmax_causal(q^ k^T / sqrt(d)) v W_o
    x  = (x + beta_1) * alpha_1 + (a + beta_2) * alpha_2

    h  = RMSNorm(x; g_m)                                expert sub-block
    s  = h W_d + b_d,  r = s + gamma * r_prev           r is handed on
    p  = softmax(W_3 gelu(W_2 gelu(W_1 RMSNorm(r; g_r) + b_1) + b_2))
    e* = argmax(p + b)              b: a balancing bias, no gradient
    y  = p[e*] FFN_e*(h),  FFN_e(h) = (silu(h Wg_e) * (h Wu_e)) Wd_e
    x  = (x + beta_3) * alpha_3 + (y + beta_4) * alpha_4

    x_0 = E[tokens],  logits = RMSNorm(x; g_f) E^T      head tied to E

The expert sub-block runs through ``parallel/moe.routed_experts``, told
which of the model's experts this chip holds (``held_first``,
``held_experts``): the router scores all ``n_experts``, and ``y = 0``
for a token whose expert lives elsewhere. No token is dropped.

The layers are one stacked run through ``pipeline.stage_layer_scan``
whose carry is ``(x, r, counts)``; the counts (tokens routed, tokens
held, the largest and the mean load of a held expert, summed over
layers) leave the step with the loss (``zaya_loss_fn(...).with_aux``).

Assumed, where the published files are silent: both convolutions carry
a bias; ``gelu`` is the exact (erf) form; ``tau`` and every ``alpha``
start at 1, every ``beta`` and bias at 0, ``gamma`` at 1; the router
MLP has two hidden layers with biases and a last map without; residual
scaling is applied in every layer; the balancing bias's update rule
belongs to the training recipe and is not here, nor is a "skip" choice
beside the experts. Every matrix is normal(0, ``init_range``), the
convolutions' weights uniform in +-1/sqrt(fan-in).

Precision: bf16 matmuls with float32 accumulation; the router (its
matmuls too), the L2 norms, the softmax and the residual scaling in
float32.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from dlrover_tpu.common import telemetry
from dlrover_tpu.models.llama import (
    _attention,
    _rms_norm,
    _rope_apply,
    _rope_tables,
)
from dlrover_tpu.ops.cross_entropy import fused_linear_cross_entropy
from dlrover_tpu.ops.fp8 import qdot
from dlrover_tpu.parallel.moe import COUNTS, routed_counts, routed_experts
from dlrover_tpu.parallel.sharding import shard_logical


@dataclasses.dataclass(frozen=True)
class ZayaConfig:
    vocab_size: int = 262272
    dim: int = 2048
    n_layers: int = 40
    n_heads: int = 8
    n_kv_heads: int = 2
    head_dim: int = 128
    conv_taps: tuple = (2, 2)            # cca_time0, cca_time1
    rotary_factor: float = 0.5
    rope_theta: float = 5_000_000.0
    n_experts: int = 16                  # the router's outputs
    held_first: int = 0                  # the experts this chip holds:
    held_experts: int = 16               # held_first .. + held_experts - 1
    expert_dim: int = 2048               # moe_intermediate_size
    router_dim: int = 256
    norm_eps: float = 1e-5
    init_range: float = 0.02
    dtype: str = "bfloat16"
    remat: bool = True                   # a layer keeps its input and o
    ce_chunks: int = 8                   # the head, a chunk of rows a time
    # attention dispatch shared with the llama family (the toy twins
    # set the implementation and the forward blocks)
    attn_impl: str = "flash"
    attn_block_q: int = 1024
    attn_block_k: int = 1024
    # what else that dispatcher reads off a config; no field: nothing
    # sets another value
    attn_bwd_block_q = 0
    attn_bwd_block_k = 0

    def __post_init__(self):
        object.__setattr__(self, "conv_taps", tuple(self.conv_taps))
        if self.n_heads % self.n_kv_heads:
            raise ValueError(
                f"{self.n_heads} query heads do not divide into "
                f"{self.n_kv_heads} key/value heads"
            )
        if self.n_kv_heads % 2:
            raise ValueError(
                "the value shift fills half the key/value heads with the "
                f"previous token's values: {self.n_kv_heads} is odd"
            )
        if self.held_first < 0 or self.held_experts < 1 or \
                self.held_first + self.held_experts > self.n_experts:
            raise ValueError(
                f"experts {self.held_first}..{self.held_first}+"
                f"{self.held_experts} are not among {self.n_experts}"
            )
        rotary = self.head_dim * self.rotary_factor
        if rotary != int(rotary) or int(rotary) % 2:
            raise ValueError(
                f"rotary_factor {self.rotary_factor} of a head of "
                f"{self.head_dim} is no even number of dims"
            )

    @property
    def rotary_dims(self) -> int:
        return int(self.head_dim * self.rotary_factor)

    @property
    def held(self) -> tuple:
        return (self.held_first, self.held_experts)

    def param_counts(self) -> dict:
        """Parameters by part, the labels of the ``model.params`` gauge."""
        d, hd, r = self.dim, self.head_dim, self.router_dim
        h, kv = self.n_heads, self.n_kv_heads
        t0, t1 = self.conv_taps
        cca = (
            2 * d * h * hd + 2 * d * kv * hd            # W_q, W_o; W_k, W_v
            + (h + kv) * hd * (t0 + 1)                  # conv 1 + bias
            + (h + kv) * (t1 * hd * hd + hd)            # conv 2 + bias
            + kv                                        # tau
        )
        router = (
            d * r + r + 2 * r                   # W_d, b_d; gamma, g_r
            + 2 * (r * r + r)                   # W_1, W_2 with biases
            + r * self.n_experts + self.n_experts       # W_3; b
        )
        experts = self.held_experts * 3 * d * self.expert_dim
        return {
            "cca": self.n_layers * cca,
            "router": self.n_layers * router,
            "experts": self.n_layers * experts,
            # the two norms and the residual scaling of every layer,
            # and the final norm
            "norms_scaling": self.n_layers * (2 * d + 8 * d) + d,
            "embedding": self.vocab_size * d,
        }

    def param_count(self) -> int:
        return sum(self.param_counts().values())


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------


def zaya_init(config: ZayaConfig, rng) -> dict:
    """Initialise params (fp32 masters), every layer's leaf stacked on
    axis 0."""
    L, d, hd, r = config.n_layers, config.dim, config.head_dim, \
        config.router_dim
    h, kv, m = config.n_heads, config.n_kv_heads, config.expert_dim
    t0, t1 = config.conv_taps
    packed, half_v = h + kv, kv * hd // 2
    std = config.init_range
    keys = iter(jax.random.split(rng, 16))

    def normal(*shape):
        return jax.random.normal(next(keys), shape) * std

    def uniform(fan_in, *shape):
        return jax.random.uniform(
            next(keys), shape, minval=-1.0, maxval=1.0) * fan_in ** -0.5

    layers = {
        "attn_norm": jnp.ones((L, d)),
        "wq": normal(L, d, h * hd),
        "wk": normal(L, d, kv * hd),
        "wv1": normal(L, d, half_v),
        "wv2": normal(L, d, half_v),
        "wo": normal(L, h * hd, d),
        "conv1_w": uniform(t0, L, t0, packed * hd),
        "conv1_b": jnp.zeros((L, packed * hd)),
        "conv2_w": uniform(t1 * hd, L, packed, t1, hd, hd),
        "conv2_b": jnp.zeros((L, packed, hd)),
        "tau": jnp.ones((L, kv)),
        "alpha": jnp.ones((L, 4, d)),
        "beta": jnp.zeros((L, 4, d)),
        "moe_norm": jnp.ones((L, d)),
        "router_down": normal(L, d, r),
        "router_down_b": jnp.zeros((L, r)),
        "router_gamma": jnp.ones((L, r)),
        "router_norm": jnp.ones((L, r)),
        "router_w1": normal(L, r, r),
        "router_b1": jnp.zeros((L, r)),
        "router_w2": normal(L, r, r),
        "router_b2": jnp.zeros((L, r)),
        "router_w3": normal(L, r, config.n_experts),
        "balance_bias": jnp.zeros((L, config.n_experts)),
        "w_in": normal(L, config.held_experts, d, 2 * m),
        "w_out": normal(L, config.held_experts, m, d),
    }
    return {
        "embed": normal(config.vocab_size, d),
        "layers": layers,
        "final_norm": jnp.ones((d,)),
    }


_LAYER_AXES = {
    "attn_norm": ("layer", "embed"),
    "wq": ("layer", "embed", "heads"),
    "wk": ("layer", "embed", "kv_heads"),
    # the two halves of the values are whole heads of their own
    "wv1": ("layer", "embed", None),
    "wv2": ("layer", "embed", None),
    "wo": ("layer", "heads", "embed"),
    "conv1_w": ("layer", None, None),
    "conv1_b": ("layer", None),
    "conv2_w": ("layer", None, None, None, None),
    "conv2_b": ("layer", None, None),
    "tau": ("layer", None),
    "alpha": ("layer", None, "embed"),
    "beta": ("layer", None, "embed"),
    "moe_norm": ("layer", "embed"),
    "router_down": ("layer", "embed", None),
    "router_down_b": ("layer", None),
    "router_gamma": ("layer", None),
    "router_norm": ("layer", None),
    "router_w1": ("layer", None, None),
    "router_b1": ("layer", None),
    "router_w2": ("layer", None, None),
    "router_b2": ("layer", None),
    "router_w3": ("layer", None, None),
    "balance_bias": ("layer", None),
    # the experts held here are this chip's: the ``expert`` axis of a
    # mesh would divide the model's, which the layer refuses
    "w_in": ("layer", None, "embed", "mlp"),
    "w_out": ("layer", None, "mlp", "embed"),
}


def zaya_logical_axes(config: ZayaConfig) -> dict:
    """Logical sharding names matching the ``zaya_init`` tree."""
    return {
        "embed": ("vocab", "embed"),
        "layers": dict(_LAYER_AXES),
        "final_norm": ("embed",),
    }


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _shift(x):
    """x_{t-1} at t along axis 1, zeros at t = 0."""
    return jnp.pad(x, ((0, 0), (1, 0)) + ((0, 0),) * (x.ndim - 2))[:, :-1]


def _causal_taps(x, taps):
    """[x_{t-taps+1}, ..., x_t], zero history on the left."""
    shifted = [x]
    for _ in range(taps - 1):
        shifted.append(_shift(shifted[-1]))
    return shifted[::-1]


def _convolutions(config, u, p):
    """u [B, S, (Hq + Hk) d] -> the two causal convolutions' output,
    float32 [B, S, Hq + Hk, d]."""
    B, S, _ = u.shape
    hd, packed = config.head_dim, config.n_heads + config.n_kv_heads
    t0, t1 = config.conv_taps
    w1 = p["conv1_w"].astype(jnp.float32)
    c1 = p["conv1_b"].astype(jnp.float32) + sum(
        w1[j] * tap
        for j, tap in enumerate(_causal_taps(u.astype(jnp.float32), t0)))
    # rounded to the compute dtype like every matmul's operand, then
    # float32 operands at the default precision: on the chip one bf16
    # pass accumulated in float32, which is exact for operands that are
    # bf16 numbers (XLA:CPU has no batched bf16 x bf16 -> float32 dot)
    c1 = c1.astype(u.dtype).astype(jnp.float32).reshape(B, S, packed, hd)
    # the taps side by side: one matmul a head over T1 * d channels
    stacked = jnp.concatenate(_causal_taps(c1, t1), axis=-1)
    w2 = p["conv2_w"].astype(u.dtype).astype(jnp.float32).reshape(
        packed, t1 * hd, hd)
    c2 = jnp.einsum("bsgc,gcd->bsgd", stacked, w2)
    return c2 + p["conv2_b"].astype(jnp.float32)


def _qk_mean(config, u):
    """The means added to the convolutions' q and k: [B, S, Hq, d] and
    [B, S, Hk, d], float32."""
    B, S, _ = u.shape
    h, kv, hd = config.n_heads, config.n_kv_heads, config.head_dim
    u = u.astype(jnp.float32)
    q_raw = u[..., :h * hd].reshape(B, S, kv, h // kv, hd)
    k_raw = u[..., h * hd:].reshape(B, S, kv, 1, hd)
    m_q = (q_raw + k_raw) / 2
    return m_q.reshape(B, S, h, hd), jnp.mean(m_q, axis=3)


def _l2_norm(x, temperature=None):
    """sqrt(d) x / ||x|| a head, times the head's temperature."""
    scale = jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True)) \
        * x.shape[-1] ** 0.5
    if temperature is not None:
        scale = scale * temperature.astype(jnp.float32)[:, None]
    return x * scale


def _rotary(config, x, cos, sin):
    """x [B, S, H, d]: rotary embedding on the first ``rotary_dims`` of
    every head, rotate-half pairing inside the slice."""
    n = config.rotary_dims
    return jnp.concatenate(
        [_rope_apply(x[..., :n], cos, sin), x[..., n:]], -1)


def _values(config, proj):
    """proj [B, S, Hk d]: ``[h_t W_v1 | h_t W_v2]`` -> the values, the
    second half read one position back: [B, S, Hk, d]."""
    B, S, width = proj.shape
    v = jnp.concatenate(
        [proj[..., :width // 2], _shift(proj[..., width // 2:])], -1)
    return v.reshape(B, S, config.n_kv_heads, config.head_dim)


def _scaled_residual(x, branch, p, first):
    """``(x + beta_a) * alpha_a + (branch + beta_b) * alpha_b`` in
    float32, rows ``first`` and ``first + 1`` of the layer's scaling."""
    with jax.named_scope("residual_scale"):
        alpha = p["alpha"].astype(jnp.float32)
        beta = p["beta"].astype(jnp.float32)
        out = (x.astype(jnp.float32) + beta[first]) * alpha[first] \
            + (branch.astype(jnp.float32) + beta[first + 1]) \
            * alpha[first + 1]
        return shard_logical(out.astype(x.dtype), ("batch", "seq", "embed"))


def _cca(config, x, p, cos, sin):
    """The attention sub-block's branch ``a`` [B, S, D]."""
    dtype = x.dtype
    B, S, _ = x.shape
    h, kv, hd = config.n_heads, config.n_kv_heads, config.head_dim
    packed = (h + kv) * hd
    y = _rms_norm(x, p["attn_norm"], config.norm_eps)
    with jax.named_scope("cca_proj"):
        # q~, k~ and both halves of the values: one matmul
        w = jnp.concatenate(
            [p[k].astype(dtype) for k in ("wq", "wk", "wv1", "wv2")], -1)
        proj = qdot(y, w, site="attn_qkv")
        u = proj[..., :packed]
        v = _values(config, proj[..., packed:])
    with jax.named_scope("cca_conv"):
        c2 = _convolutions(config, u, p)
        m_q, m_k = _qk_mean(config, u)
        q = c2[:, :, :h] + m_q
        k = c2[:, :, h:] + m_k
    with jax.named_scope("cca_qk_norm"):
        q = _rotary(config, _l2_norm(q), cos, sin).astype(dtype)
        k = _rotary(config, _l2_norm(k, p["tau"]), cos, sin).astype(dtype)
    with jax.named_scope("attn"):
        out = _attention(config, q, k, v)
    with jax.named_scope("cca_out_proj"):
        return qdot(out.reshape(B, S, h * hd), p["wo"].astype(dtype),
                    site="attn_out")


def _dense32(x, w, b=None):
    """``x w + b`` accumulated in float32 from operands in ``x``'s
    dtype. A product of two bf16 numbers is exact in float32, so the
    one wide matmul of the router, whose operands arrive as bf16, is a
    single pass; the narrow ones that follow take float32 operands in
    full (``HIGHEST``: six passes of a 256-wide matmul)."""
    out = jnp.matmul(x, w.astype(x.dtype), precision=jax.lax.Precision.HIGHEST,
                     preferred_element_type=jnp.float32)
    return out if b is None else out + b.astype(jnp.float32)


def _router(config, y, r_prev, p):
    """y [B, S, D] (normed) and the state of the layer below -> (the
    state handed on, p [B, S, E] over all the model's experts), all
    float32."""
    with jax.named_scope("router"):
        s = _dense32(y, p["router_down"], p["router_down_b"])
        r = s + p["router_gamma"].astype(jnp.float32) * r_prev
        z = _rms_norm(r, p["router_norm"].astype(jnp.float32),
                      config.norm_eps)
        z = jax.nn.gelu(_dense32(z, p["router_w1"], p["router_b1"]),
                        approximate=False)
        z = jax.nn.gelu(_dense32(z, p["router_w2"], p["router_b2"]),
                        approximate=False)
        return r, jax.nn.softmax(_dense32(z, p["router_w3"]), axis=-1)


def _choose(probs, p):
    """(e*, p[e*]): the expert of the largest ``p + b``; no gradient
    reaches the balancing bias."""
    bias = jax.lax.stop_gradient(p["balance_bias"].astype(jnp.float32))
    choice = jnp.argmax(probs + bias, axis=-1).astype(jnp.int32)
    weight = jnp.take_along_axis(probs, choice[..., None], -1)[..., 0]
    return choice, weight


def _layer_fn(config: ZayaConfig):
    """``stage_layer_scan``'s layer body over the carry ``(x, r,
    counts)``. A forward pass that wants its choices of expert back
    gives the carry a fourth part, ``(choices [L, B, S], the layer's
    index)``, which every layer writes its row of."""

    def layer(carry, p, cos, sin):
        x, r_prev, counts, *chosen = carry
        x = _scaled_residual(x, _cca(config, x, p, cos, sin), p, 0)
        y = _rms_norm(x, p["moe_norm"], config.norm_eps)
        r, probs = _router(config, y, r_prev, p)
        choice, weight = _choose(probs, p)
        out = routed_experts(
            y, choice, weight,
            {"w_in": p["w_in"].astype(x.dtype),
             "w_out": p["w_out"].astype(x.dtype)},
            config.held)
        x = _scaled_residual(x, out, p, 2)
        if chosen:
            (rows, at), = chosen
            chosen = [(jax.lax.dynamic_update_index_in_dim(
                rows, choice, at, 0), at + 1)]
        carry = (x, r, counts + routed_counts(choice, config.held), *chosen)
        return carry, jnp.zeros((), jnp.float32)

    return layer


def _embed(config, params, tokens):
    """(the scan's first carry, the rotary tables)."""
    from dlrover_tpu.parallel.pipeline import pipe_size

    if pipe_size() > 1:
        raise NotImplementedError(
            "pipeline stages of zaya layers: the stage boundary would "
            "have to carry the router's state beside the hidden state, "
            "and the schedules move one array; use a mesh with pipe=1"
        )
    dtype = jnp.dtype(config.dtype)
    B, S = tokens.shape
    x = params["embed"].astype(dtype)[tokens]
    x = shard_logical(x, ("batch", "seq", "embed"))
    r = jnp.zeros((B, S, config.router_dim), jnp.float32)
    counts = jnp.zeros((len(COUNTS),), jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    return (x, r, counts), _rope_tables(
        positions, config.rotary_dims // 2, config.rope_theta, jnp.float32)


def _hidden(config: ZayaConfig, params, tokens, choices=False):
    """tokens [B, S] -> (the last layer's x [B, S, D], counts [4]) and,
    with ``choices``, the expert every layer chose for every token in
    this pass, int32 [layers, B, S]."""
    from dlrover_tpu.parallel.pipeline import layer_input, stage_layer_scan

    carry, (cos, sin) = _embed(config, params, tokens)
    if choices:
        carry += ((jnp.zeros((config.n_layers, *tokens.shape), jnp.int32),
                   jnp.zeros((), jnp.int32)),)
    stage = stage_layer_scan(
        _layer_fn(config), remat=config.remat,
        # a layer keeps its input, (x, r), and the attention kernel's
        # output and row statistic (34 MB a layer at 2 x 8192 tokens),
        # so its recomputation does not run the forward kernel again.
        # What the default policy keeps of it, every weight matmul's
        # output, is 0.5 GiB a layer there (the configuration's file
        # has the compiler's counts)
        policy=layer_input(keep=("attn_out",)), kind="hybrid",
        layer_axes={k: tuple(v[1:]) for k, v in _LAYER_AXES.items()},
    )
    (x, _r, counts, *chosen), _aux = stage(params["layers"], carry, cos, sin)
    return (x, counts, chosen[0][0]) if choices else (x, counts)


def zaya_apply(config: ZayaConfig, params, tokens, choices=False):
    """tokens [B, S] int32 -> logits [B, S, vocab] float32; with
    ``choices`` also the expert every layer chose for every token,
    int32 [layers, B, S], out of the same pass."""
    dtype = jnp.dtype(config.dtype)
    x, _counts, *chosen = _hidden(config, params, tokens, choices)
    with jax.named_scope("head"):
        x = _rms_norm(x, params["final_norm"], config.norm_eps)
        logits = x @ params["embed"].astype(dtype).T
        logits = shard_logical(logits, ("batch", "seq", "vocab"))
        logits = logits.astype(jnp.float32)
    return (logits, *chosen) if choices else logits


def _publish_shape(config: ZayaConfig):
    """What was built, as gauges."""
    telemetry.gauge_set("model.layers", config.n_layers, kind="hybrid")
    for part, count in config.param_counts().items():
        telemetry.gauge_set("model.params", count, kind=part)
    telemetry.gauge_set("model.moe.experts", config.held_experts, kind="held")
    telemetry.gauge_set("model.moe.experts", config.n_experts,
                        kind="published")


def zaya_loss_fn(config: ZayaConfig):
    """Next-token CE loss closure for auto_accelerate. ``with_aux`` is
    the same loss with the step's counts beside it, which the Trainer
    trains on where a loss function has one; ``counters`` names those
    of them that are counts to add up (``COUNTS``: all of them)."""
    _publish_shape(config)

    def with_aux(params, batch, rng):
        tokens = batch["tokens"]
        x, counts = _hidden(config, params, tokens[:, :-1])
        with jax.named_scope("head"):
            loss_sum, valid = fused_linear_cross_entropy(
                x, params["embed"].astype(x.dtype).T, tokens[:, 1:],
                n_chunks=config.ce_chunks,
                norm_scale=params["final_norm"], norm_eps=config.norm_eps,
            )
        loss = loss_sum / jnp.maximum(valid, 1)
        return loss, dict(zip(COUNTS, jax.lax.stop_gradient(counts)))

    def loss_fn(params, batch, rng):
        return with_aux(params, batch, rng)[0]

    loss_fn.with_aux = with_aux
    loss_fn.counters = COUNTS
    return loss_fn
