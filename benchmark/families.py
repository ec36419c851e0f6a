"""Model families: from a configuration file to the program's objects.

A configuration file names its ``family``; the entry here turns the
file's published sizes into the program's own model config and hands
back what the Trainer needs (init, loss, logical axes), the program's
forward pass for the agreement check, and the benchmark's own reference
and FLOP count for that architecture. A new family is one entry here
plus its functions in ``reference.py`` and ``flops.py``; a new
configuration of a family that is here is a data file only.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import flops
import reference


@dataclasses.dataclass
class Family:
    model_config: Any
    init: Callable            # rng -> params
    loss_fn: Callable         # (params, batch, rng) -> loss
    logical_axes: Any
    apply: Callable           # (params, tokens[B, S]) -> logits
    reference_logits: Callable    # (params, tokens[S]) -> logits
    tolerances: dict              # of the agreement with the reference
    flops_per_token: float


def _mistral(sizes: dict) -> Family:
    from dlrover_tpu.models import llama

    config = llama.LlamaConfig(
        vocab_size=sizes["vocab_size"], dim=sizes["hidden_size"],
        n_layers=sizes["num_hidden_layers"],
        n_heads=sizes["num_attention_heads"],
        n_kv_heads=sizes["num_key_value_heads"],
        mlp_dim=sizes["intermediate_size"],
        max_seq_len=sizes["sequence"], rope_theta=sizes["rope_theta"],
        norm_eps=sizes["rms_norm_eps"], **sizes.get("program", {}),
    )
    window = sizes.get("sliding_window")
    if window and window < sizes["sequence"]:
        raise ValueError(
            "models/llama.py has no sliding window: a sequence of "
            f"{sizes['sequence']} needs the window of {window}"
        )
    return Family(
        model_config=config,
        init=lambda rng: llama.llama_init(config, rng),
        loss_fn=llama.llama_loss_fn(config),
        logical_axes=llama.llama_logical_axes(config),
        apply=lambda p, t: llama.llama_apply(config, p, t),
        reference_logits=lambda p, t: reference.mistral_logits(sizes, p, t),
        tolerances=reference.tolerances("mistral"),
        flops_per_token=flops.mistral(sizes, sizes["sequence"]),
    )


def _gpt2(sizes: dict) -> Family:
    from dlrover_tpu.models import gpt2

    config = gpt2.GPT2Config(
        vocab_size=sizes["vocab_size"], dim=sizes["n_embd"],
        n_layers=sizes["n_layer"], n_heads=sizes["n_head"],
        mlp_dim=sizes.get("n_inner") or 4 * sizes["n_embd"],
        max_seq_len=sizes["n_positions"],
        norm_eps=sizes["layer_norm_epsilon"], tie_lm_head=True,
        **sizes.get("program", {}),
    )
    return Family(
        model_config=config,
        init=lambda rng: gpt2.gpt2_init(config, rng),
        loss_fn=gpt2.gpt2_loss_fn(config),
        logical_axes=gpt2.gpt2_logical_axes(config),
        apply=lambda p, t: gpt2.gpt2_apply(config, p, t),
        reference_logits=lambda p, t: reference.gpt2_logits(sizes, p, t),
        tolerances=reference.tolerances("gpt2"),
        flops_per_token=flops.gpt2(sizes, sizes["sequence"]),
    )


FAMILIES = {"mistral": _mistral, "gpt2": _gpt2}


def build(sizes: dict) -> Family:
    family = sizes["family"]
    if family not in FAMILIES:
        raise ValueError(
            f"unknown model family {family!r}: add it to families.py "
            f"(has {sorted(FAMILIES)})"
        )
    return FAMILIES[family](sizes)
