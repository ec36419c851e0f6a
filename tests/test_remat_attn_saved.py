"""The attention forward kernel runs once a layer a step.

The step program nests two checkpoint levels: ``Strategy.remat`` wraps
the whole loss (accelerate._remat_wrap) and the model's layer scan
wraps each layer (pipeline.stage_layer_scan). The outer level's policy
rules the first forward pass right through the inner one, so a level
that does not keep the kernel's tagged outputs ("attn_out": ``o`` and
the row statistic) drops them there, and the inner level runs
``flash_fwd`` a second time to have them: two forward kernels a layer a
step on the chip, 12 ms of ``gpt2-xl.steady``'s 194 ms step (PERF.md,
PR 27). Both levels, and llama's named policies, read one set now:
``pipeline.minimal_save_policy``.

A layer that keeps its input alone (``pipeline.LAYER_INPUT``) is a
third case: its backward pass reads the layer's input and recomputes
the rest, the forward kernel too, whatever the outer level kept. The
zaya layer therefore names the kernel's outputs to its own level as
well (``pipeline.layer_input(keep=("attn_out",))``), and the hybrid's
Mamba layer its first projection's output. Such a layer also hands its
input to the outer level by name, which otherwise replays the chain of
layers in the backward pass to make every layer's input again, the
experts' grouped matmuls with it (PERF.md, PR 35).

Counted here without running a kernel: the ``pallas_call``s by name in
the dead-code-eliminated jaxpr of the step ``auto_accelerate`` builds.
Each sits in a scan body, so a count of 1 is one call a layer.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax._src.interpreters import partial_eval as pe

from dlrover_tpu.models import PRESETS, llama_init, llama_loss_fn
from dlrover_tpu.models.gpt2 import GPT2Config, gpt2_init, gpt2_loss_fn
from dlrover_tpu.models.gpt2 import gpt2_logical_axes
from dlrover_tpu.models.granite_hybrid import (
    GraniteHybridConfig,
    granite_hybrid_init,
    granite_hybrid_logical_axes,
    granite_hybrid_loss_fn,
)
from dlrover_tpu.models.llama import llama_logical_axes
from dlrover_tpu.models.zaya import (
    ZayaConfig,
    zaya_init,
    zaya_logical_axes,
    zaya_loss_fn,
)
from dlrover_tpu.parallel import MeshConfig, Strategy
from dlrover_tpu.parallel.accelerate import _remat_wrap, auto_accelerate

SEQ = 32  # two whole blocks of 16: the one-pass backward kernel


def _model(name: str, remat: bool):
    """(loss_fn, init_fn, logical axes, vocabulary) of a 2-layer model
    whose attention is the Pallas wrapper (interpret mode here)."""
    if name == "gpt2":
        cfg = GPT2Config(
            vocab_size=64, dim=32, n_layers=2, n_heads=2, mlp_dim=64,
            max_seq_len=SEQ, dtype="float32", attn_impl="flash",
            attn_block_q=16, attn_block_k=16, remat=remat,
        )
        return (gpt2_loss_fn(cfg), lambda rng: gpt2_init(cfg, rng),
                gpt2_logical_axes(cfg), cfg.vocab_size)
    if name == "zaya":
        cfg = ZayaConfig(
            vocab_size=64, dim=32, n_layers=2, n_heads=2, n_kv_heads=2,
            head_dim=16, n_experts=4, held_experts=2, expert_dim=16,
            router_dim=8, dtype="float32", ce_chunks=1, attn_block_q=16,
            attn_block_k=16, remat=remat,
        )
        return (zaya_loss_fn(cfg), lambda rng: zaya_init(cfg, rng),
                zaya_logical_axes(cfg), cfg.vocab_size)
    if name == "mamba":
        cfg = dataclasses.replace(MAMBA, remat=remat)
        return (granite_hybrid_loss_fn(cfg),
                lambda rng: granite_hybrid_init(cfg, rng),
                granite_hybrid_logical_axes(cfg), cfg.vocab_size)
    cfg = dataclasses.replace(
        PRESETS["tiny"], n_layers=2, attn_impl="flash", ce_chunks=1,
        dtype="float32", attn_block_q=16, attn_block_k=16, remat=remat,
    )
    return (llama_loss_fn(cfg), lambda rng: llama_init(cfg, rng),
            llama_logical_axes(cfg), cfg.vocab_size)


# one run of two Mamba layers; in_proj is [32, 2 x 64 + 2 x 16 + 4]
MAMBA = GraniteHybridConfig(
    vocab_size=64, dim=32, layer_types=("mamba", "mamba"), n_heads=2,
    n_kv_heads=2, mlp_dim=64, mamba_heads=4, mamba_head_dim=16,
    mamba_state=16, mamba_chunk=16, dtype="float32",
)
IN_PROJ = 2 * MAMBA.mamba_inner + 2 * MAMBA.mamba_state + MAMBA.mamba_heads


def _batch(vocab: int):
    tokens = np.random.RandomState(0).randint(0, vocab, (2, SEQ + 1))
    return {"tokens": jnp.asarray(tokens, jnp.int32)}


def _subjaxprs(val):
    if hasattr(val, "jaxpr"):  # ClosedJaxpr
        yield val.jaxpr
    elif hasattr(val, "eqns"):  # Jaxpr
        yield val
    elif isinstance(val, (tuple, list)):
        for v in val:
            yield from _subjaxprs(v)


def _count(jaxpr, key, counts=None) -> dict:
    """Equations at any depth, counted under ``key(eqn)`` (None: not
    counted)."""
    counts = {} if counts is None else counts
    for eqn in jaxpr.eqns:
        k = key(eqn)
        if k is not None:
            counts[k] = counts.get(k, 0) + 1
        for val in eqn.params.values():
            for sub in _subjaxprs(val):
                _count(sub, key, counts)
    return counts


def _kernel_name(eqn):
    if eqn.primitive.name == "pallas_call":
        return eqn.params["name"]


def _in_proj(eqn):
    """The Mamba layer's first projection in its forward form: a
    matmul whose result is a row of ``IN_PROJ`` a token."""
    if eqn.primitive.name == "dot_general" \
            and eqn.outvars[0].aval.shape == (2, SEQ, IN_PROJ):
        return "in_proj"


def _grouped_matmul(eqn):
    if eqn.primitive.name == "ragged_dot_general":
        return "ragged_dot"


def _tag(eqn):
    if eqn.primitive.name == "name":
        return eqn.params["name"]


@functools.lru_cache(maxsize=None)
def _step_jaxpr(model: str, level: str, remat: bool):
    """The dead-code-eliminated jaxpr of the step auto_accelerate
    builds: a replay's call whose outputs nothing reads is in the trace
    and not in the program, so count what the compiler keeps."""
    loss_fn, init_fn, axes, vocab = _model(model, remat)
    accel = auto_accelerate(
        loss_fn, init_fn, optax.sgd(1e-2), axes,
        strategy=Strategy(mesh=MeshConfig(data=1, fsdp=1), remat=level),
        devices=jax.devices()[:1],
    )
    closed = jax.make_jaxpr(accel.train_step)(
        accel.state, _batch(vocab), jax.random.key(0)
    )
    live, _ = pe.dce_jaxpr(
        closed.jaxpr, [True] * len(closed.jaxpr.outvars)
    )
    return live


# flash_fwd calls a layer a step, whatever the model's own remat.
# "minimal" and "offload" keep the kernel's outputs at every level.
# "full" recomputes everything, attention too: its replay is the second
# call (a third would mean the per-layer level dropped them as well).
# "none" has no checkpoint at any level.
FWD_CALLS = {"minimal": 1, "offload": 1, "full": 2, "none": 1}


@pytest.mark.parametrize("remat", [True, False], ids=["remat", "plain"])
@pytest.mark.parametrize("level", list(FWD_CALLS))
@pytest.mark.parametrize("model", ["gpt2", "llama", "zaya"])
def test_attention_forward_kernel_runs_once_a_layer(model, level, remat):
    """zaya's layer keeps its input and, by name, the kernel's outputs:
    at the parent of PR 35 it kept its input alone and read 2 under
    "minimal" and 3 under "full"."""
    calls = _count(_step_jaxpr(model, level, remat), _kernel_name)
    assert calls == {
        "flash_fwd": FWD_CALLS[level], "flash_bwd_fused": 1,
    }, calls


@pytest.mark.parametrize("remat", [True, False], ids=["remat", "plain"])
@pytest.mark.parametrize("level", list(FWD_CALLS))
def test_mamba_first_projection_runs_once_a_layer(level, remat):
    """The same count of ``in_proj``'s matmul in a run of Mamba layers,
    which keep their input and, by name, that matmul's output: 2 under
    "minimal" at the parent of PR 35."""
    calls = _count(_step_jaxpr("mamba", level, remat), _in_proj)
    assert calls == {"in_proj": FWD_CALLS[level]}, calls


@pytest.mark.parametrize("level,remat,calls", [
    ("none", True, 6), ("minimal", False, 8), ("minimal", True, 8),
    ("offload", True, 8), ("full", True, 10),
])
def test_a_layer_that_keeps_its_input_is_not_replayed_to_make_it(
        level, remat, calls):
    """The experts' grouped matmuls in a zaya layer, two forward and
    four backward, are no ``dot_general``, so no level keeps their
    outputs: each replay of the layer runs the forward two again. A
    layer that keeps its input hands it (and the last layer its output)
    to the whole-loss checkpoint by name, so under "minimal" the one
    replay is the layer's own, in its backward pass: 8 calls. Before
    PR 35 the whole-loss checkpoint also replayed the chain of layers
    to make each layer's input again: 10, as under "full" still."""
    assert _count(_step_jaxpr("zaya", level, remat), _grouped_matmul) \
        == {"ragged_dot": calls}


@pytest.mark.parametrize("model", ["gpt2", "llama"])
def test_a_step_without_checkpoints_carries_no_tag(model):
    """``Strategy.remat="none"`` has no checkpoint to read a tag: the
    step is traced without them, and the kernel's own statistic goes to
    the backward kernel with no narrowing and no rebuilding, as it did
    before there was anything to save."""
    assert _count(_step_jaxpr(model, "none", True), _tag) == {}
    tags = _count(_step_jaxpr(model, "minimal", True), _tag)
    assert tags.get("attn_out", 0) >= 2, tags


@pytest.mark.parametrize("model", ["gpt2", "llama"])
def test_gradients_equal_those_without_any_checkpoint(model):
    """Saving ``o`` and the statistic changes no number: the gradients
    under both checkpoint levels equal those of the same model with
    none at all."""
    grads = {}
    for level, remat in (("minimal", True), ("none", False)):
        loss_fn, init_fn, _axes, vocab = _model(model, remat)
        wrapped = _remat_wrap(loss_fn, level)
        grads[level] = jax.jit(jax.grad(
            lambda p, b: wrapped(p, b, jax.random.key(0))
        ))(init_fn(jax.random.key(1)), _batch(vocab))
    flat = jax.tree.leaves(jax.tree.map(
        lambda a, b: jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-30),
        grads["minimal"], grads["none"],
    ))
    assert len(flat) > 4
    assert max(float(x) for x in flat) <= 1e-6


@pytest.mark.parametrize("level,on_device,on_host", [
    ("minimal", 3, 0), ("offload", 1, 2), ("full", 0, 0),
])
def test_strategy_level_keeps_the_tagged_output(level, on_device, on_host):
    """The whole-loss checkpoint itself, on a function of two dots and
    one tagged value: "minimal" holds all three on the device from
    forward to backward, "offload" sends the dots to the host and holds
    the tagged value on the device, "full" holds nothing."""
    from jax._src.ad_checkpoint import saved_residuals
    from jax.ad_checkpoint import checkpoint_name

    def loss(w, x, rng):
        h = checkpoint_name(jnp.tanh(x @ w), "attn_out")
        return jnp.sum((h @ w) ** 2)

    kept = [
        str(aval) for aval, why in saved_residuals(
            _remat_wrap(loss, level), jnp.ones((8, 8)), jnp.ones((4, 8)),
            None,
        ) if "from the argument" not in why
    ]
    host = [aval for aval in kept if "<host>" in aval]
    assert (len(kept) - len(host), len(host)) == (on_device, on_host), kept
