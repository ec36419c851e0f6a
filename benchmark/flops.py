"""Floating-point operations a training step REQUIRES, per token, from
a configuration file's published sizes (the yardstick for ``mfu_pct``).

Forward = 2 FLOPs per multiply-add of every matmul a token crosses:
the projections and the feed-forward of each layer, the output head,
and causal attention's ``QK^T`` and ``AV`` over the keys a query sees
(on average ``(S + 1) / 2`` of a sequence of S, capped by the sliding
window). Backward = twice the forward. Table look-ups (token and
position embeddings), norms, activations and the softmax are not
counted, and neither is anything recomputed to save memory: the count
is what the mathematics needs, not what the program chose to do.
"""

from __future__ import annotations


def _attention_flops(heads: int, head: int, seq: int, window=None) -> float:
    """Forward QK^T + AV FLOPs per token per layer, causal."""
    keys = (seq + 1) / 2
    if window and window < seq:
        # rows past the window see exactly ``window`` keys
        keys = (window * (window + 1) / 2 + (seq - window) * window) / seq
    return 2 * 2 * heads * head * keys


def mistral(sizes: dict, seq: int) -> float:
    d, m = sizes["hidden_size"], sizes["intermediate_size"]
    heads, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    head = d // heads
    layer = (
        2 * d * (heads * head)        # wq
        + 2 * 2 * d * (kv * head)     # wk, wv
        + 2 * (heads * head) * d      # wo
        + 3 * 2 * d * m               # gate, up, down
        + _attention_flops(heads, head, seq, sizes.get("sliding_window"))
    )
    forward = sizes["num_hidden_layers"] * layer + 2 * d * sizes["vocab_size"]
    return 3 * forward


def gpt2(sizes: dict, seq: int) -> float:
    d, heads = sizes["n_embd"], sizes["n_head"]
    m = sizes.get("n_inner") or 4 * d
    layer = (
        2 * d * 3 * d                 # qkv
        + 2 * d * d                   # proj
        + 2 * 2 * d * m               # fc, out
        + _attention_flops(heads, d // heads, seq)
    )
    forward = sizes["n_layer"] * layer + 2 * d * sizes["vocab_size"]
    return 3 * forward
