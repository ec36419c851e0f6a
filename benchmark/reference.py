"""Plain references for the benchmark's model families.

Each function is the architecture's forward pass in straightforward
float32 ``jax.numpy`` under ``jax.default_matmul_precision("highest")``
(on a TPU a float32 matmul otherwise runs in bf16 passes), written from
the published descriptions and independent of ``dlrover_tpu/models``:
no kernels, no recomputation, no sharding, no lower precision. The
layers are looped with ``lax.scan`` over the stacked weights only so
that a deep stack compiles in seconds and not minutes.

Weights arrive in the layout the Trainer already holds them in (no
second copy of an 8 GB state fits the chip): every per-layer matrix is
stacked over layers on axis 0 and applied as ``x @ W``.

- ``mistral`` (Mistral-7B-v0.1, arXiv 2310.06825; the Llama block):
  pre-RMSNorm, rotary embeddings on q and k (rotate-half form over the
  two halves of a head, ``theta`` base), grouped-query attention (query
  head h reads KV head h // (H / KVH)), causal mask with the sliding
  window (key j visible to query i iff i - window < j <= i), SwiGLU
  feed-forward ``down(silu(gate(x)) * up(x))``, untied output head.
- ``gpt2`` (Radford et al. 2019, as ``transformers``' GPT2LMHeadModel
  computes it): learned position embeddings, pre-LayerNorm with bias,
  fused qkv projection with bias, causal attention, MLP with the tanh
  form of GELU ("gelu_new"), final LayerNorm, output head tied to the
  token embedding.

The loss is the mean next-token cross-entropy over all positions.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# Agreement of the system with the reference (run.py, check (a)).
#
# The system multiplies in bf16 with float32 accumulation; the
# reference keeps float32 everywhere. The distance between their logits
# is given as a share of the root mean square of the reference's. On
# the chip at the configurations' real widths (tests/precision_probe.py;
# PERF.md, Findings, PR 25) bf16 read 0.0157 (mistral, 2 layers) and
# 0.0105 (gpt2, 16 layers), and the program's own 8-bit matmuls (int8
# quant_autocast) 0.0607 and 0.0348; fp8 0.119 and 0.094. The toy sizes
# of the tests read the same within a tenth, at 2-3 layers: the
# distance does not grow with depth or width. Each limit is the
# geometric mean of the bf16 and the int8 reading, so bf16 passes with
# a factor of 1.8-2 to spare and a lower compute precision than the
# configuration states fails by as much. The largest single logit's
# distance is held to MAX_OVER_RMS times the rms limit (8M roughly
# normal errors reach 5.5-6 standard deviations; read: 5.2-6.0).
LOGITS_REL_RMS_TOL = {"mistral": 0.031, "gpt2": 0.019}
MAX_OVER_RMS = 8.0
LOSS_ABS_TOL = 0.02           # nats; the loss is ~ln(vocab) = 10.4-11.2


def tolerances(family: str) -> dict:
    rms = LOGITS_REL_RMS_TOL[family]
    return {"logits_rel_rms": rms, "logits_rel_max": MAX_OVER_RMS * rms,
            "loss_abs": LOSS_ABS_TOL}


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _layer_norm(x, scale, bias, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias


def _rotate_half(x, theta):
    """x [S, H, Dh]: rotary embedding, rotate-half form."""
    seq, _heads, head = x.shape
    half = head // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(q, k, v, window=None):
    """q [S, H, Dh], k/v [S, KVH, Dh] -> [S, H*Dh]; softmax in float32."""
    seq, heads, head = q.shape
    group = heads // k.shape[1]
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(float(head))
    qi = jnp.arange(seq)[:, None]
    kj = jnp.arange(seq)[None, :]
    visible = kj <= qi
    if window:
        visible = visible & (kj > qi - window)
    scores = jnp.where(visible[None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("hqk,khd->qhd", probs, v).reshape(seq, heads * head)


def mistral_logits(sizes: dict, params: dict, tokens):
    """tokens [S] int32 -> logits [S, vocab] float32. ``sizes`` holds
    the published keys of the model's ``config.json``."""
    heads = sizes["num_attention_heads"]
    kv_heads = sizes["num_key_value_heads"]
    eps, theta = sizes["rms_norm_eps"], sizes["rope_theta"]
    window = sizes.get("sliding_window")
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens]
        seq, dim = x.shape
        head = dim // heads

        def layer(x, w):
            y = _rms_norm(x, w["attn_norm"], eps)
            q = (y @ w["wq"]).reshape(seq, heads, head)
            k = (y @ w["wk"]).reshape(seq, kv_heads, head)
            v = (y @ w["wv"]).reshape(seq, kv_heads, head)
            q, k = _rotate_half(q, theta), _rotate_half(k, theta)
            x = x + _attention(q, k, v, window) @ w["wo"]
            y = _rms_norm(x, w["mlp_norm"], eps)
            gate = jax.nn.silu(y @ w["w_gate"])
            return x + (gate * (y @ w["w_up"])) @ w["w_down"], None

        x, _ = jax.lax.scan(layer, x, params["layers"])
        x = _rms_norm(x, params["final_norm"], eps)
        return x @ params["lm_head"]


def gpt2_logits(sizes: dict, params: dict, tokens):
    """tokens [S] int32 -> logits [S, vocab] float32."""
    heads, eps = sizes["n_head"], sizes["layer_norm_epsilon"]
    with jax.default_matmul_precision("highest"):
        seq = tokens.shape[0]
        x = params["embed"][tokens] + params["pos_embed"][:seq]
        head = x.shape[-1] // heads

        def layer(x, w):
            y = _layer_norm(x, w["ln1_scale"], w["ln1_bias"], eps)
            qkv = y @ w["w_qkv"] + w["b_qkv"]
            q, k, v = (
                part.reshape(seq, heads, head)
                for part in jnp.split(qkv, 3, axis=-1)
            )
            x = x + _attention(q, k, v) @ w["w_proj"] + w["b_proj"]
            y = _layer_norm(x, w["ln2_scale"], w["ln2_bias"], eps)
            mid = jax.nn.gelu(y @ w["w_fc"] + w["b_fc"], approximate=True)
            return x + mid @ w["w_out"] + w["b_out"], None

        x, _ = jax.lax.scan(layer, x, params["layers"])
        x = _layer_norm(
            x, params["final_ln_scale"], params["final_ln_bias"], eps
        )
        return x @ params["embed"].T


def next_token_loss(logits, tokens):
    """Mean next-token cross-entropy: ``logits`` [S, vocab] are those
    of ``tokens[:-1]``, the labels are ``tokens[1:]``."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    labels = tokens[1:]
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], 1))


def compare(system_logits, system_loss, ref_logits, ref_loss, limits,
            last=256):
    """The numbers ``correct`` is decided on: the last ``last``
    positions' logits (all of them, not the largest) and the loss,
    against ``limits`` (``tolerances()``)."""
    sys_tail = jnp.asarray(system_logits, jnp.float32)[-last:]
    ref_tail = jnp.asarray(ref_logits, jnp.float32)[-last:]
    scale = float(jnp.sqrt(jnp.mean(ref_tail ** 2)))
    diff = sys_tail - ref_tail
    report = {
        "logits_rel_rms": float(jnp.sqrt(jnp.mean(diff ** 2))) / scale,
        "logits_rel_max": float(jnp.max(jnp.abs(diff))) / scale,
        "loss_abs": abs(float(system_loss) - float(ref_loss)),
    }
    report["ok"] = all(report[key] <= limits[key] for key in limits)
    report.update(limits={k: round(v, 5) for k, v in limits.items()},
                  ref_loss=float(ref_loss), positions=int(sys_tail.shape[0]))
    return report
