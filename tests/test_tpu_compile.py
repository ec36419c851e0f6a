"""Ask the installed TPU compiler, without a chip.

Interpret mode — what every other kernel test here runs — cannot see
what Mosaic and XLA:TPU refuse: a block that does not fit VMEM, a cast
Mosaic does not lower, a program that does not fit HBM. These tests
AOT-compile the main path's kernels and programs at the published
Llama-2-7B widths (dim 4096, 32 heads x 128, mlp 11008, vocab 32000,
sequence 2048) for a *described* v5e (jax.experimental.topologies), so
each later PR is held to them at no chip time. A compile that passes is
not a chip run: nothing here executes, and no time or result comes out.

One file on purpose: only one process may hold the TPU library, so the
topology is described inside a module fixture (never at import, in a
``skipif`` or in ``parametrize``) and every compile runs in this
process. The program's CPU branches (``use_interpret``) are steered
here, in the test: ``interpret=False`` for kernels, a patched
``require_backend`` for whole programs.
"""

import dataclasses
import functools
import os
import re

import jax
import jax.numpy as jnp
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

from dlrover_tpu.common import backend
from dlrover_tpu.models.llama import (
    PRESETS,
    llama_init,
    llama_logical_axes,
    llama_loss_fn,
)
from dlrover_tpu.ops.attention import (
    flash_attention,
    flash_attention_bshd,
    window_block,
)
from dlrover_tpu.ops.fused_optim import fused_adamw
from dlrover_tpu.ops.quantization import (
    BLOCK,
    dequantize_int8,
    quantize_int8,
)
from dlrover_tpu.parallel import MeshConfig, Strategy, auto_accelerate
from dlrover_tpu.parallel.accelerate import TrainState
from dlrover_tpu.serving.engine import (
    init_slot_cache,
    slot_decode,
    slot_prefill,
)

SEQ = 2048
# depth cut to 2 (the layer scan compiles one body whatever the depth)
CONFIG = dataclasses.replace(
    PRESETS["llama2-7b"], n_layers=2, max_seq_len=SEQ
)
LEAF = (CONFIG.dim, CONFIG.mlp_dim)  # the largest per-layer leaf
V5E_HBM_BYTES = 16 * 1024 ** 3


@pytest.fixture(scope="module")
def topo():
    # the compiler would otherwise log under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent
    cache but cannot be read back without one (the next run warns and
    recompiles): switch the cache off around this file's compiles."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def on_tpu(monkeypatch):
    """Whole programs pick their kernels' mode from the backend: make
    them take the TPU branch (compiled kernels, not interpret mode)."""
    monkeypatch.setattr(backend, "require_backend", lambda: "tpu")


def _shaped(tree, sharding):
    """Shapes placed on the described device(s): there is no device to
    hold an array, so every program is lowered from shapes."""
    if isinstance(sharding, jax.sharding.Sharding):
        return jax.tree.map(
            lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype,
                                           sharding=sharding),
            tree,
        )
    return jax.tree.map(
        lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=s),
        tree, sharding,
    )


def _compile(fn, *args, **jit_kw):
    return jax.jit(fn, **jit_kw).lower(*args).compile()


def _kernels(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


def _kernel_names(compiled) -> list:
    """The instruction names of the program's Pallas kernels, without
    the compiler's numbering: what a device trace shows them under."""
    return re.findall(
        r"%([A-Za-z0-9_\-]+?)(?:\.\d+)? = [^\n]*"
        r"custom_call_target=\"tpu_custom_call\"",
        compiled.as_text(),
    )


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (
        m.temp_size_in_bytes + m.argument_size_in_bytes
        + m.output_size_in_bytes - m.alias_size_in_bytes
    )


def _sum_grad(fn):
    return jax.value_and_grad(
        lambda *a: fn(*a).astype(jnp.float32).sum(), argnums=(0, 1, 2)
    )


# ------------------------------------------------------------- kernels


@pytest.mark.parametrize("layout", ["bhsd", "fused-rope", "bshd"])
def test_flash_attention_forward_and_backward(one_chip, layout):
    b, h, hd = 1, CONFIG.n_heads, CONFIG.head_dim
    blocks = dict(
        block_q=CONFIG.attn_block_q, block_k=CONFIG.attn_block_k,
        interpret=False,
    )
    if layout == "bshd":
        qkv = jax.ShapeDtypeStruct((b, SEQ, h, hd), jnp.bfloat16)
        fn = functools.partial(flash_attention_bshd, **blocks)
        args = (qkv,) * 3
    else:
        qkv = jax.ShapeDtypeStruct((b, h, SEQ, hd), jnp.bfloat16)
        args = (qkv,) * 3
        if layout == "fused-rope":
            table = jax.ShapeDtypeStruct((b, SEQ, hd), jnp.bfloat16)
            args += (table, table)

            def fn(q, k, v, cos, sin):
                return flash_attention(
                    q, k, v, rope_cos=cos, rope_sin=sin, **blocks
                )
        else:
            fn = functools.partial(flash_attention, **blocks)
    compiled = _compile(_sum_grad(fn), *_shaped(args, one_chip))
    # the forward kernel and at least one backward kernel
    assert _kernels(compiled) >= 2


def test_quantize_int8_real_leaf(one_chip):
    """One whole-array VMEM block was refused here (172 MB against
    128 MB of VMEM): the kernel walks a grid of row tiles."""
    x = jax.ShapeDtypeStruct(LEAF, jnp.float32)
    compiled = _compile(
        lambda x: quantize_int8(x, interpret=False)[:2],
        *_shaped((x,), one_chip),
    )
    assert _kernels(compiled) == 1


def test_dequantize_int8_real_leaf(one_chip):
    rows = LEAF[0] * LEAF[1] // BLOCK
    q = jax.ShapeDtypeStruct((rows, BLOCK), jnp.int8)
    scales = jax.ShapeDtypeStruct((rows, 1), jnp.float32)
    compiled = _compile(
        lambda q, s: dequantize_int8(q, s, LEAF, interpret=False),
        *_shaped((q, scales), one_chip),
    )
    assert _kernels(compiled) == 1


@pytest.mark.parametrize("bits", [32, 8])
def test_fused_adamw_real_leaves(one_chip, bits):
    """bits=8 was refused (Mosaic lowers no float32 -> uint8 cast; the
    nu re-encode goes through int32)."""
    tree = {
        "mlp": jax.ShapeDtypeStruct(LEAF, jnp.float32),
        "attn": jax.ShapeDtypeStruct(
            (CONFIG.dim, CONFIG.dim), jnp.float32
        ),
    }
    opt = fused_adamw(
        1e-3, weight_decay=0.1, clip_norm=1.0, bits=bits, interpret=False
    )
    state = jax.eval_shape(opt.init, tree)
    compiled = _compile(
        opt.update,
        *_shaped((tree, state, tree), one_chip),
    )
    assert _kernels(compiled) == 1


# A kernel's ``name=`` and the ``jax.named_scope`` round its call
# (ops/named.py) make the compiler name the custom call after the
# kernel; the benchmark's trace reduction finds it by that. Widths of
# the two benchmark configurations: Mistral-7B (32 heads of 128 over 8
# KV heads, 2 x 2048) and GPT-2 XL (25 heads of 64, 4 x 1024).
ATTENTION_NAMES = {
    "mistral-7b": (
        flash_attention, (2, 32, 2048, 128), (2, 8, 2048, 128),
        {"flash_fwd", "flash_bwd_fused"},
    ),
    "gpt2-xl": (
        flash_attention, (4, 25, 1024, 64), (4, 25, 1024, 64),
        {"flash_fwd", "flash_bwd_fused"},
    ),
    "mistral-7b-bshd": (
        flash_attention_bshd, (2, 2048, 32, 128), (2, 2048, 8, 128),
        {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"},
    ),
    # granite-4.0-h-micro's attention layer: 32 heads of 64 over 8 KV
    # heads, 1 x 8192, the published multiplier as the softmax scale;
    # the one-pass backward at 8192 positions asks for more than the
    # default scoped VMEM, and Mosaic takes it
    "granite-4.0-h-micro": (
        functools.partial(flash_attention, sm_scale=0.015625,
                          block_q=1024, block_k=1024),
        (1, 32, 8192, 64), (1, 8, 8192, 64),
        {"flash_fwd", "flash_bwd_fused"},
    ),
    # zaya1-8b's attention: 8 heads of 128 over 2 KV heads (4 query
    # heads a KV head), 2 x 8192, q and k normalised and rotated
    # outside the kernel
    "zaya1-8b": (
        functools.partial(flash_attention, block_q=1024, block_k=1024),
        (2, 8, 8192, 128), (2, 2, 8192, 128),
        {"flash_fwd", "flash_bwd_fused"},
    ),
    # laguna-xs.2's window layers: 64 heads of 128 over 8 KV heads, 2 x
    # 8192, a window of 512, at the tiles the window takes where 1024 is
    # configured: the kernels take the window's names, which
    # ``^flash_fwd`` / ``^flash_bwd`` still match
    "laguna-xs.2-window": (
        functools.partial(flash_attention, block_q=window_block(1024, 512),
                          block_k=window_block(1024, 512), window=512),
        (2, 64, 8192, 128), (2, 8, 8192, 128),
        {"flash_fwd_window", "flash_bwd_window_fused"},
    ),
    # glm-4.7-flash's latent attention: 20 heads of 256, 2 x 8192, the
    # backward at 512-row blocks: the one-pass kernel's 28.5 MiB count,
    # asked for, is what Mosaic has to take here
    "glm-4.7-flash": (
        functools.partial(flash_attention, block_q=1024, block_k=1024,
                          bwd_block_q=512),
        (2, 20, 8192, 256), (2, 20, 8192, 256),
        {"flash_fwd", "flash_bwd_fused"},
    ),
}


@pytest.mark.parametrize("widths", sorted(ATTENTION_NAMES))
def test_attention_kernels_carry_their_names(one_chip, widths):
    fn, q_shape, kv_shape, names = ATTENTION_NAMES[widths]
    q = jax.ShapeDtypeStruct(q_shape, jnp.bfloat16)
    kv = jax.ShapeDtypeStruct(kv_shape, jnp.bfloat16)
    compiled = _compile(
        _sum_grad(functools.partial(fn, interpret=False)),
        *_shaped((q, kv, kv), one_chip),
    )
    assert set(_kernel_names(compiled)) == names
    text = compiled.as_text()
    for name in names:
        # in the operation's metadata too, under the scope of the call
        assert f"/{name}/pallas_call" in text


# batch, heads, KV heads, positions, head size, backward tile rows and
# columns, rope inside the kernels: glm-4.7-flash's, zaya1-8b's,
# granite-4.0-h-micro's and mistral-7b's attention
ONEPASS_COUNTED = {
    "glm-4.7-flash": (2, 20, 20, 8192, 256, 512, 1024, False),
    "zaya1-8b": (2, 8, 2, 8192, 128, 1024, 1024, False),
    "granite-4.0-h-micro": (1, 32, 8, 8192, 64, 1024, 1024, False),
    "mistral-7b": (2, 32, 8, 2048, 128, 1024, 1024, True),
}


@pytest.mark.parametrize("cell", sorted(ONEPASS_COUNTED))
def test_onepass_backward_fits_its_count(one_chip, monkeypatch, cell):
    """Mosaic takes the one-pass backward with no more scoped VMEM than
    ``_onepass_vmem_bytes`` counts: a kernel that comes to hold more than
    its count fails here, before a cell that asks for the count + 25%
    (or that stays on the 16 MiB default) fails on the chip."""
    from jax.experimental.pallas import tpu as pltpu

    from dlrover_tpu.ops import attention as A

    b, h, kvh, s, d, bq, bk, rope = ONEPASS_COUNTED[cell]
    monkeypatch.setattr(A, "_onepass_params", lambda vmem: (
        pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=vmem)))
    q = jax.ShapeDtypeStruct((b, h, s, d), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((b, kvh, s, d), jnp.bfloat16)
    args = (q, kv, kv)
    if rope:
        args += (jax.ShapeDtypeStruct((b, s, d), jnp.bfloat16),) * 2

    def fn(q, k, v, *tables):
        return flash_attention(
            q, k, v, block_q=1024, block_k=1024, bwd_block_q=bq,
            bwd_block_k=bk, interpret=False,
            **dict(zip(("rope_cos", "rope_sin"), tables)))

    compiled = _compile(_sum_grad(fn), *_shaped(args, one_chip))
    assert "flash_bwd_fused" in _kernel_names(compiled)


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_causal_conv_kernels_at_the_cell_size(one_chip, dtype):
    """The convolution's kernel pair alone at granite-4.0-h-micro's
    sizes, ``xBC`` read out of the projection's output of one
    8192-token sequence: Mosaic takes the default blocks in the cell's
    bf16 and in float32, and each kernel carries its name."""
    from dlrover_tpu.ops.causal_conv import causal_conv_silu_kernel

    x = jax.ShapeDtypeStruct((1, 8192, 8512), dtype)
    weight = jax.ShapeDtypeStruct((4, 4352), dtype)
    bias = jax.ShapeDtypeStruct((4352,), dtype)
    compiled = _compile(
        _sum_grad(functools.partial(causal_conv_silu_kernel, first=4096,
                                    interpret=False)),
        *_shaped((x, weight, bias), one_chip),
    )
    assert sorted(_kernel_names(compiled)) == [
        "causal_conv_bwd", "causal_conv_fwd"]
    text = compiled.as_text()
    for name in ("causal_conv_fwd", "causal_conv_bwd"):
        assert f"/{name}/pallas_call" in text


# sequence, heads, head size, groups, states, chunk, dtype
SCAN_SHAPES = [
    # granite-4.0-h-micro's cell, and the same in float32 (8 heads a
    # grid step where bf16 has 16)
    (8192, 64, 64, 1, 128, 256, "bfloat16"),
    (8192, 64, 64, 1, 128, 256, "float32"),
    # what the CPU tests run in interpret mode: a chunk of one lane tile
    (384, 8, 16, 1, 128, 128, "float32"),
    (256, 32, 16, 2, 128, 128, "bfloat16"),
    # two groups, wider states, a longer chunk
    (2048, 16, 32, 2, 256, 512, "bfloat16"),
]


@pytest.mark.parametrize("shape", SCAN_SHAPES,
                         ids=lambda s: "x".join(map(str, s)))
def test_ssd_scan_kernels_compile(one_chip, shape):
    """The chunked scan's kernel pair alone, at the cell's sizes (64
    heads of 64 in one group, 128 states, one 8192-token sequence in
    chunks of 256) and at others the rule takes: Mosaic takes the
    blocks, each kernel carries its name, and the masked decay
    [.., chunk, chunk] is nowhere in the program."""
    from dlrover_tpu.ops import ssd_kernel

    seq, heads, head, groups, states, chunk, dtype = shape
    assert ssd_kernel.kernel_takes(seq, chunk, heads, groups, head, states)
    x = jax.ShapeDtypeStruct((1, seq, heads, head), dtype)
    dt = jax.ShapeDtypeStruct((1, seq, heads), jnp.float32)
    per_head = jax.ShapeDtypeStruct((heads,), jnp.float32)
    state = jax.ShapeDtypeStruct((1, seq, groups, states), dtype)
    compiled = _compile(
        jax.value_and_grad(
            lambda *operands: ssd_kernel.ssd_scan_kernel(
                *operands, chunk=chunk, interpret=False
            ).astype(jnp.float32).sum(), argnums=tuple(range(6))),
        *_shaped((x, dt, per_head, state, state, per_head), one_chip),
    )
    assert sorted(_kernel_names(compiled)) == ["ssd_scan_bwd", "ssd_scan_fwd"]
    text = compiled.as_text()
    for name in ("ssd_scan_fwd", "ssd_scan_bwd"):
        assert f"/{name}/pallas_call" in text
    if chunk not in (states, heads * head):     # no other [.., n, n]
        assert f"{chunk},{chunk}]" not in text


def test_mamba_layer_forward_backward(one_chip, on_tpu):
    """One Mamba-2 layer of granite-4.0-h-micro at its published widths
    and the cell's 8192 tokens, forward and backward: it compiles for
    the chip, every part under its scope, beside a layer's own weights
    and gradients; the convolution and the chunked scan are their
    kernel pairs (forward, the recomputed forward, backward) and the
    layer has no other kernel."""
    from dlrover_tpu.models import granite_hybrid as gh

    config = gh.GraniteHybridConfig(vocab_size=12544,
                                    layer_types=("mamba",))
    loss = gh.granite_hybrid_loss_fn(config)
    params = jax.eval_shape(
        lambda: gh.granite_hybrid_init(config, jax.random.key(0)))
    compiled = _compile(
        jax.value_and_grad(lambda p, b: loss(
            jax.tree.map(lambda x: x.astype(jnp.bfloat16), p), b, None
        )),
        *_shaped(
            (params,
             {"tokens": jax.ShapeDtypeStruct((1, 8192 + 1), jnp.int32)}),
            one_chip,
        ),
    )
    text = compiled.as_text()
    for scope in ("mamba_in_proj", "mamba_conv", "ssd_scan",
                  "mamba_gate_norm", "mamba_out_proj", "mlp", "head"):
        assert f"/{scope}/" in text or f"({scope})" in text, scope
    names = _kernel_names(compiled)
    assert len(names) == _kernels(compiled)
    assert set(names) == {"causal_conv_fwd", "causal_conv_bwd",
                          "ssd_scan_fwd", "ssd_scan_bwd"}, names
    assert names.count("causal_conv_bwd") == 1
    assert names.count("ssd_scan_bwd") == 1
    assert _device_bytes(compiled) < 0.5 * V5E_HBM_BYTES


def test_zaya_layer_forward_backward(one_chip, on_tpu):
    """One layer of zaya1-8b at its published widths, 8 of 16 experts
    held, the cell's 2 x 8192 tokens, forward and backward: it compiles
    for the chip, every part under its scope; the attention kernels
    and the compiler's own grouped matmuls (``ragged-dot-*``, which the
    benchmark's ``moe_expert_ms`` finds by that name) are its only
    kernels; the layer keeps its input and the forward kernel's
    outputs, so that kernel runs once (twice while the layer kept its
    input alone, before PR 35)."""
    from dlrover_tpu.models import zaya

    config = zaya.ZayaConfig(vocab_size=32784, n_layers=1, held_experts=8)
    loss = zaya.zaya_loss_fn(config)
    params = jax.eval_shape(lambda: zaya.zaya_init(config, jax.random.key(0)))
    compiled = _compile(
        jax.value_and_grad(lambda p, b: loss(
            jax.tree.map(lambda x: x.astype(jnp.bfloat16), p), b, None
        )),
        *_shaped(
            (params,
             {"tokens": jax.ShapeDtypeStruct((2, 8192 + 1), jnp.int32)}),
            one_chip,
        ),
    )
    text = compiled.as_text()
    for scope in ("cca_proj", "cca_conv", "cca_qk_norm", "attn",
                  "cca_out_proj", "router", "moe_dispatch", "moe_experts",
                  "moe_combine", "residual_scale", "head"):
        assert f"/{scope}/" in text or f"({scope})" in text, scope
    names = _kernel_names(compiled)
    assert len(names) == _kernels(compiled)
    assert set(names) == {
        "flash_fwd", "flash_bwd_fused", "ragged-dot-metadata",
        "ragged-dot-none"}, names
    assert names.count("flash_fwd") == 1
    # gate | up and down: forward, the recomputed forward, and both
    # transposes of each backward
    assert names.count("ragged-dot-none") == 8
    assert _device_bytes(compiled) < 0.5 * V5E_HBM_BYTES


def test_quantization_kernels_carry_their_names(one_chip):
    x = jax.ShapeDtypeStruct(LEAF, jnp.float32)
    compiled = _compile(
        lambda x: dequantize_int8(
            *quantize_int8(x, interpret=False)[:2], LEAF, interpret=False
        ),
        *_shaped((x,), one_chip),
    )
    assert _kernel_names(compiled) == ["quantize_int8", "dequantize_int8"]


@pytest.mark.parametrize("bits,name", [
    (32, "fused_adamw"), (8, "fused_adamw_8bit"),
])
def test_fused_adamw_kernel_carries_its_name(one_chip, bits, name):
    tree = {"mlp": jax.ShapeDtypeStruct(LEAF, jnp.float32)}
    opt = fused_adamw(1e-3, bits=bits, interpret=False)
    state = jax.eval_shape(opt.init, tree)
    compiled = _compile(
        opt.update, *_shaped((tree, state, tree), one_chip)
    )
    assert _kernel_names(compiled) == [name]


# ------------------------------------------------------------ programs


def _abstract_params(dtype=None):
    params = jax.eval_shape(
        lambda: llama_init(CONFIG, jax.random.key(0))
    )
    if dtype is None:
        return params
    return jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape, dtype), params
    )


def _abstract_key():
    return jax.eval_shape(lambda: jax.random.key(0))


def test_llama_forward_backward(one_chip, on_tpu):
    loss = llama_loss_fn(CONFIG)
    compiled = _compile(
        jax.value_and_grad(lambda p, b: loss(
            jax.tree.map(lambda x: x.astype(jnp.bfloat16), p), b, None
        )),
        *_shaped(
            (_abstract_params(),
             {"tokens": jax.ShapeDtypeStruct((2, SEQ + 1), jnp.int32)}),
            one_chip,
        ),
    )
    # flash forward + backward inside the layer scan
    assert _kernels(compiled) >= 2
    assert _device_bytes(compiled) < V5E_HBM_BYTES


def _train_step(topo, mesh_config, n_devices, batch):
    """The Trainer's jitted step (auto_accelerate, default AdamW) on
    described devices: lowered from an abstract state, donated like
    the real one."""
    optimizer = optax.adamw(1e-3)

    def init(rng):
        return llama_init(CONFIG, rng)

    def init_state():
        params = init(jax.random.key(0))
        return TrainState(
            step=jnp.zeros((), jnp.int32), params=params,
            opt_state=optimizer.init(params),
        )

    state = jax.eval_shape(init_state)
    accel = auto_accelerate(
        llama_loss_fn(CONFIG), init, optimizer,
        llama_logical_axes(CONFIG),
        strategy=Strategy(mesh=mesh_config),
        devices=topo.devices[:n_devices], reuse_state=state,
    )
    replicated = NamedSharding(accel.mesh, PartitionSpec())
    return accel, _compile(
        accel.train_step,
        _shaped(state, accel.state_shardings),
        _shaped(
            {"tokens": jax.ShapeDtypeStruct((batch, SEQ + 1), jnp.int32)},
            replicated,
        ),
        _shaped(_abstract_key(), replicated),
        donate_argnums=(0,),
    )


def test_train_step_fits_one_chip(topo, on_tpu):
    _accel, compiled = _train_step(
        topo, MeshConfig(data=1, fsdp=1), n_devices=1, batch=2
    )
    assert _kernels(compiled) >= 2
    # every kernel of the step program under a name it was given, none
    # under one the compiler made (closed_call.19, checkpoint.15)
    names = _kernel_names(compiled)
    assert len(names) == _kernels(compiled)
    # one forward kernel a layer: every checkpoint level keeps its
    # outputs (tests/test_remat_attn_saved.py counts every level)
    assert names.count("flash_fwd") == 1, names
    assert all(n.startswith("flash_bwd") for n in set(names) - {"flash_fwd"})
    # state (fp32 params + two AdamW moments) + gradients + activations
    assert _device_bytes(compiled) < 0.9 * V5E_HBM_BYTES


def test_train_step_fsdp_over_four_chips(topo, on_tpu):
    """The sharded path chip_smoke.py --chips 4 runs: every chip holds
    its quarter of the state, and the compiler put the fsdp collectives
    in."""
    accel, compiled = _train_step(
        topo, MeshConfig(fsdp=4), n_devices=4, batch=4
    )
    assert accel.mesh.shape["fsdp"] == 4
    text = compiled.as_text()
    assert "all-gather" in text and "tpu_custom_call" in text
    one_chip_state = 12 * CONFIG.param_count()  # bytes, unsharded
    m = compiled.memory_analysis()
    assert m.argument_size_in_bytes < one_chip_state / 4 * 1.05


# The train step of each accepted benchmark cell, as the Trainer builds
# it (``Trainer._accelerate``, the workload's own training arguments) and
# lowers it for one described v5e: the first 16 hex digits of the
# SHA-256 of its StableHLO, every Pallas kernel's serialized body cut
# out (it holds its source file's path). A cell's ``setup_s`` is mostly
# this program's compile, or its load from the compile cache, whose key
# the program is: a PR that does not mean to touch a cell's step leaves
# these as they are, and one that does says so by changing its line.
# The first three read at the parent of PR 34 and on PR 34's tree,
# equal.
STEP_PROGRAMS = {
    "mistral-7b": "00266e80d14bddf4",
    "gpt2-xl": "48eb149feb8c79c2",
    # The five cells at 8192 positions: the one-pass attention backward
    # in place of the split trio, asking for the scoped VMEM its count
    # needs (were b3bd6dbcb7768cd6, 16082d6ad790c866, 4722ec3f4eebaa2d,
    # ca739c4742b00312 and 8a208fc34d2b8c1d)
    "granite-4.0-h-micro": "8252be6bfaeae149",  # keeps in_proj
    "zaya1-8b": "907feeff88e6c8fa",         # keeps attn_out
    # PR 40: the head's loss per chip, its matrix gathered once a pass
    # and its gradient reduce-scattered once, where PR 38's
    # a242b4f22b927984 left the partitioner to cross the chips in every
    # chunk (PR 38: the rule's kernel pair where PR 37's
    # a21aed4543a6c099 ran the chunked form)
    "olmo-hybrid-7b": "987004624bf6f598",
    # top-8 routed pairs, window and full attention, a dense layer 0;
    # the window layers' kernels at the window's 512 tiles (was
    # 86dc0727812e7eab at the configured 1024)
    "laguna-xs.2": "cce260f9f42776c7",
    # latent attention in every layer, sigmoid top-4 routed pairs and a
    # shared expert, one MTP depth; a second loss over the same head
    "glm-4.7-flash": "23a3c44d3d6f9198",
}


def _lowered_cell_step(topo, name):
    """The step of the accepted cell ``<name>.steady``, lowered for
    the described v5e chips its configuration's mesh takes, from shapes
    alone."""
    import math
    import sys
    import types

    from dlrover_tpu.trainer.trainer import (
        Trainer,
        TrainingArgs,
        _build_optimizer,
    )

    bench = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmark")
    sys.path.insert(0, bench)
    try:
        import families
        import lookup

        sizes = lookup.data("configs", name)
        workload = lookup.data("workloads", name + ".steady")
        family = families.build(sizes)
    finally:
        sys.path.remove(bench)
    args = TrainingArgs(output_dir="unused", **workload["training_args"])
    optimizer = _build_optimizer(args)

    def init_state():
        params = family.init(jax.random.key(0))
        return TrainState(
            step=jnp.zeros((), jnp.int32), params=params,
            opt_state=optimizer.init(params),
        )

    state = jax.eval_shape(init_state)
    # the Trainer's own way to its step, without a Trainer's state
    accel = Trainer._accelerate(
        types.SimpleNamespace(
            loss_fn=family.loss_fn, init_fn=family.init,
            optimizer=optimizer, param_logical_axes=family.logical_axes,
            args=args),
        Strategy(mesh=MeshConfig(**sizes["mesh"])),
        devices=topo.devices[:math.prod(sizes["mesh"].values())],
        reuse_state=state,
    )
    replicated = NamedSharding(accel.mesh, PartitionSpec())
    return jax.jit(accel.train_step, donate_argnums=(0,)).lower(
        _shaped(state, accel.state_shardings),
        _shaped({"tokens": jax.ShapeDtypeStruct(
            (sizes["batch"], sizes["sequence"] + 1), jnp.int32)},
            replicated),
        _shaped(_abstract_key(), replicated),
    )


@pytest.mark.parametrize("name", sorted(STEP_PROGRAMS))
def test_accepted_cells_step_programs_are_what_they_were(
        topo, on_tpu, name):
    import hashlib

    text = _lowered_cell_step(topo, name).as_text()
    text, kernels = re.subn(
        r'\\22body\\22: \\22[A-Za-z0-9+/=]*\\22',
        r'\\22body\\22: \\22\\22', text)
    assert kernels >= 2         # the attention kernels, at the least
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == STEP_PROGRAMS[name]


# The cells whose layers keep their input and what they name beside it
# (pipeline.layer_input): the kernel that made a kept value runs once a
# layer, and the compiler, whose limit for a program on this chip is
# 15.75 GiB, takes the step (it refuses one it counts above that, and
# its count is the buffer assignment's: ``memory_analysis()`` reads
# every stack a scan writes a second time, PERF.md, PR 35).
@pytest.mark.parametrize("name", ["granite-4.0-h-micro", "zaya1-8b"])
def test_cells_that_keep_named_values_compile_for_the_chip(
        topo, on_tpu, name):
    compiled = _lowered_cell_step(topo, name).compile()
    assert _kernel_names(compiled).count("flash_fwd") == 1


def test_laguna_cell_compiles_and_fits(topo, on_tpu):
    """``laguna-xs.2.steady`` on one described v5e: the compiler takes
    the step (its limit is 15.75 GiB by its buffer assignment: 14.60e9
    bytes when the cell was built, 8.30e9 of it the train state), and
    the kernels are there under the names the per-layer metrics read:
    the full layers' flash pair once a run of one (the layer keeps the
    forward kernel's outputs), the window layers' under the window's
    names, forward twice (the recomputation runs it again), both
    backwards the one-pass kernel, and the compiler's grouped matmuls of
    the two routed runs."""
    compiled = _lowered_cell_step(topo, "laguna-xs.2").compile()
    names = _kernel_names(compiled)
    assert len(names) == _kernels(compiled)
    assert names.count("flash_fwd") == 2, names
    assert names.count("flash_fwd_window") == 2, names
    assert {n for n in names if n.startswith("flash_bwd")} == {
        "flash_bwd_fused", "flash_bwd_window_fused"}
    # gate | up and down: forward, the recomputed forward, and both
    # transposes of each backward, in each of the two routed runs
    assert names.count("ragged-dot-none") == 16
    m = compiled.memory_analysis()
    assert m.argument_size_in_bytes < 12 * 691_623_936 * 1.01
    assert m.peak_memory_in_bytes < 15.75 * 1024 ** 3


def test_glm_lite_cell_compiles_and_fits(topo, on_tpu):
    """``glm-4.7-flash.steady`` on one described v5e: the compiler takes
    the step (its limit is 15.75 GiB by its buffer assignment: 14.96e9
    bytes when the cell was built, 8.48e9 of it the train state), and
    the kernels are there under the names the per-layer metrics read:
    the flash pair at 20 heads of 256 once a layer in each of the three
    runs (the dense layer, the routed ones, the MTP depth's: a layer
    keeps the forward kernel's outputs), the backward the one-pass
    kernel at 512-row blocks, with the scoped VMEM its count asks for,
    and the compiler's grouped matmuls of the two routed runs."""
    compiled = _lowered_cell_step(topo, "glm-4.7-flash").compile()
    names = _kernel_names(compiled)
    assert len(names) == _kernels(compiled)
    for name in ("flash_fwd", "flash_bwd_fused"):
        assert names.count(name) == 3, names
    assert not [n for n in names if n.startswith("flash_bwd_d")]
    assert not [n for n in names if "window" in n]
    # gate | up and down: forward, the recomputed forward, and both
    # transposes of each backward, in each of the two routed runs
    assert names.count("ragged-dot-none") == 16
    m = compiled.memory_analysis()
    assert m.argument_size_in_bytes < 12 * 706_518_848 * 1.01
    assert m.peak_memory_in_bytes < 15.75 * 1024 ** 3


def _computations(hlo: str) -> dict:
    """``{name: (header, body lines)}`` of every computation in ``hlo``."""
    comps, name = {}, None
    for line in hlo.splitlines():
        if line.endswith("{") and not line.startswith(" "):
            name = re.match(r"(?:ENTRY )?%?([\w.\-]+)", line).group(1)
            comps[name] = (line, [])
        elif name is not None:
            comps[name][1].append(line)
    return comps


def _head_collectives(hlo: str) -> dict:
    """Where ``olmo-hybrid-7b``'s head matrix crosses the chips in the
    compiled step: for each ``all-gather`` to ``bf16[3840,100352]``,
    ``reduce-scatter`` to ``bf16[960,100352]`` and ``all-reduce-scatter``
    fusion to it, ``(inside a while body, inside an async collective
    fusion, chain)``."""
    comps = _computations(hlo)
    callees = {
        c: set(re.findall(r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)",
                          "\n".join(lines)))
        for c, (_, lines) in comps.items()
    }
    in_loop, todo = set(), re.findall(r"body=%?([\w.\-]+)", hlo)
    while todo:
        c = todo.pop()
        if c not in in_loop:
            in_loop.add(c)
            todo.extend(callees.get(c, ()))
    patterns = {
        "all-gather": r"= bf16\[3840,100352\]\S* all-gather\(",
        "reduce-scatter": r"= bf16\[960,100352\]\S* reduce-scatter\(",
        "all-reduce-scatter": r"= bf16\[960,100352\]\S* fusion\(.*"
                              r"calls=%all-reduce-scatter",
    }
    found = {kind: [] for kind in patterns}
    for c, (_, lines) in comps.items():
        for line in lines:
            for kind, pattern in patterns.items():
                if re.search(pattern, line):
                    chain = re.search(r'chain_id="(\d+)"', line)
                    found[kind].append((
                        c in in_loop, c.startswith("async_collective_fusion"),
                        chain and chain.group(1)))
    return found


_LOGITS = r"(?:1,)?1024,100352\]"   # one chunk's logits on one chip


def _head_logits(hlo: str) -> tuple:
    """How the forward loop's logits leave the head's matmul in the
    compiled ``olmo-hybrid-7b`` step: the output types of each
    computation that holds that ``convolution`` (the forward pass's
    ``dot_general`` under ``jvp(head)``, not its transpose), and the
    names of the forward pass's computations that widen a bf16
    parameter of the logits' shape to float32."""
    made, widened = [], []
    for name, (header, lines) in _computations(hlo).items():
        body = "\n".join(lines)
        if re.search(r"= bf16\[" + _LOGITS + r"\S* convolution\(.*"
                     r'op_name="[^"]*/jvp\(head\)/[^"]*dot_general"', body):
            made.append(header.split(" -> ", 1)[1])
        elif (re.search(r"\(\w[\w.]*: bf16\[" + _LOGITS, header)
              and re.search(r"= f32\[" + _LOGITS + r"\S* convert\(.*"
                            r'op_name="[^"]*/jvp\(head\)/', body)):
            widened.append(name)
    return made, widened


def test_four_chip_cell_compiles_and_fits(topo, on_tpu):
    """``olmo-hybrid-7b.steady`` under fsdp=4 on the four chips of a
    described v5e:2x2: the compiler takes the step (its limit is 15.75
    GiB a chip, by its buffer assignment: 15.84e9 bytes when the cell
    was built, of which the quarter of the train state 7.31e9), every
    chip holds a quarter of the state, the collectives are in, and the
    kernels are there under the names the per-layer metrics read: the
    flash pair of the two full-attention layers, run once a layer, and
    the convolution pair and (PR 38) the gated delta rule's pair of the
    six linear ones. Since that pair the rule's float32 [C, C] stacks
    are gone from a linear layer's backward pass, where the peak lies:
    14.3e9 bytes. Since PR 40 the head's matrix crosses the chips once a
    pass and its gradient once a step, outside every loop, where the
    partitioner gathered it in every chunk of both loops and all-reduced
    each chunk's gradient: 14,864,602,624 bytes, the 578 MB more a
    whole-width accumulator of the gradient on the chip."""
    compiled = _lowered_cell_step(topo, "olmo-hybrid-7b").compile()
    names = _kernel_names(compiled)
    assert len(names) == _kernels(compiled)
    # one body a run of like layers and a pass: two runs of each kind
    assert names.count("flash_fwd") == 2, names
    assert {n for n in names if n.startswith("flash_bwd")}
    assert names.count("causal_conv_fwd") == 4        # + recomputation
    assert names.count("causal_conv_bwd") == 2
    assert names.count("gdn_chunk_fwd") == 4          # + recomputation
    assert names.count("gdn_chunk_bwd") == 2
    assert sum(n.startswith("gdn_chunk_") for n in names) == 6
    text = compiled.as_text()
    assert "all-gather" in text and "all-reduce" in text
    head = _head_collectives(text)
    # one gather a pass: each an async chain begun outside every loop;
    # the compiler may step one along inside the loop it hides behind
    chains = {chain for *_, chain in head["all-gather"]}
    assert len(chains) == 2, head
    for in_loop, in_async, chain in head["all-gather"]:
        assert in_async or not in_loop, head
    outside = {chain for in_loop, _, chain in head["all-gather"]
               if not in_loop}
    assert outside == chains, head
    # the gradient's partial sums meet once, after the loop
    assert [loc[0] for loc in head["reduce-scatter"]] == [False], head
    assert not head["all-reduce-scatter"], head
    # the forward loop's logits leave the matmul once, rounded to bf16,
    # and a fusion of their own widens them: where the matmul's fusion
    # also handed out a float32 copy, made before the bf16 rounding, the
    # label's pick read other logits than the sum (PR 40: the loss on
    # the chip then read up to twice its size)
    made, widened = _head_logits(text)
    assert len(made) == 1, made
    assert len(re.findall(r"bf16\[" + _LOGITS, made[0])) == 1, made
    assert not re.search(r"f32\[" + _LOGITS, made[0]), made
    assert widened, made
    m = compiled.memory_analysis()
    state = 12 * 2_435_748_072                  # bytes, unsharded
    assert m.argument_size_in_bytes < state / 4 * 1.01
    assert m.peak_memory_in_bytes < 15.75 * 1024 ** 3


@pytest.mark.parametrize("program", ["prefill-1024", "decode"])
def test_serving_programs(one_chip, on_tpu, program):
    slots, capacity = 8, 1024
    params = _abstract_params(jnp.bfloat16)
    cache = jax.eval_shape(
        lambda: init_slot_cache(CONFIG, slots, capacity)
    )
    i32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32)
    f32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.float32)
    if program == "decode":
        fn = functools.partial(slot_decode, CONFIG)
        args = (
            params, cache, i32((slots,)), i32((slots,)),
            jax.ShapeDtypeStruct((slots,), jnp.bool_), _abstract_key(),
            f32((slots,)),
        )
    else:
        fn = functools.partial(slot_prefill, CONFIG)
        args = (
            params, cache, i32((capacity,)), i32(()), i32(()),
            _abstract_key(), f32(()),
        )
    compiled = _compile(fn, *_shaped(args, one_chip))
    assert _device_bytes(compiled) < V5E_HBM_BYTES
