#!/usr/bin/env python3
"""Chip smoke: the system's main path, once, on a real accelerator.

    python chip_smoke.py             # one chip: train -> save -> kill ->
                                     # resume -> serve
    python chip_smoke.py --chips 4   # four chips: fsdp=4 training against
                                     # the same run on one device

The quickest proof that dlrover_tpu still starts on the chip, through the
entry points a user calls: ``python -m dlrover_tpu.trainer.run`` (local
master -> elastic agent -> worker: ``init_distributed`` -> ``Trainer`` ->
flash checkpoint through the AGENT's saver) and ``python -m
dlrover_tpu.master.main`` + a ``DecodeWorker`` over the RPC plane. The
model is Llama-2-7B at its published widths (dim 4096, 32 heads x 128,
mlp 11008, vocab 32000), sequence 2048, random weights from ``--seed``,
depth cut to what ``memory_analysis()`` says fits the chip with the
Trainer's default AdamW (the number is printed).

One process per chip: this parent never imports JAX. Every phase that
needs the chip is a child process, one at a time, fully exited before
the next starts; the parent learns platform, device kind and count from
a line the child prints and fails unless the platform is ``tpu``.

Output: one JSON object per phase, then — only when every phase
passed — the contract line ``{"ok": true, "device": {...}}``. On any
failure (child exit code, time limit, failed check) the phase's name,
the child's exit code and the tails of the worker, agent and master
logs are printed, and the exit code says which phase (table below).

``--rehearsal`` runs the same control flow at a toy size wherever JAX
lands (``JAX_PLATFORMS=cpu``: Pallas interpret mode); it proves paths
and arguments, never prints ``"ok": true`` and exits 0 only to say the
rehearsal itself passed.
"""

import argparse
import json
import math
import os
import random
import signal
import subprocess
import sys
import time

# ----------------------------------------------------------- exit codes
EXIT_OK = 0
EXIT_KERNELS = 10   # device/kernels child: no tpu, or a repaired kernel
#                     disagrees with its interpret-mode twin on the chip
EXIT_TRAIN = 11     # tpu-run: first worker never reached an acked save
EXIT_RESUME = 12    # kill -> agent restart -> restore -> train to the end
EXIT_SERVE = 13     # master + decode worker + client requests
EXIT_FSDP4 = 14     # --chips 4: sharded training vs one device
EXIT_LEFTOVER = 15  # a process this script started outlived its phase

REPO = os.path.dirname(os.path.abspath(__file__))
MARK = "SMOKE "
LOG_TAIL_LINES = 60

REAL = dict(
    preset="llama2-7b", seq=2048, batch=2, layer_candidates=(3, 2),
    steps=12, save_step=4, lr=1e-4,
    slots=8, capacity=1024, requests=8, new_tokens=32,
    prompt_lens=(100, 214, 328, 442, 557, 671, 785, 900),
    # --chips 4: four batch rows (one per fsdp shard) must also fit ONE
    # chip for the comparison run, so the sequence is halved there
    fsdp_batch=4, fsdp_seq=1024, fsdp_steps=6, fsdp_save_step=3,
    # bf16 compute, a different reduction order across four chips
    loss_rtol=2e-2,
)
TOY = dict(
    preset="tiny", seq=128, batch=2, layer_candidates=(3, 2),
    steps=8, save_step=3, lr=1e-2,
    slots=4, capacity=64, requests=4, new_tokens=4,
    prompt_lens=(9, 17, 30, 45),
    fsdp_batch=4, fsdp_seq=64, fsdp_steps=4, fsdp_save_step=2,
    loss_rtol=2e-2,
)


def emit(event, **fields):
    print(MARK + json.dumps({"event": event, **fields}), flush=True)


def finite(values):
    return all(math.isfinite(v) for v in values)


def make_prompt(seed, index, length, vocab):
    """Request prompts, from the seed alone: the parent submits them
    and the decode worker re-derives the one it checks, with no JAX and
    no numpy on the parent's side."""
    rng = random.Random(seed * 1000003 + index)
    return [rng.randrange(vocab) for _ in range(length)]


# ======================================================================
# children (these import JAX; the parent below never does)
# ======================================================================


def _model_config(sizes, layers, seq=None):
    import dataclasses

    from dlrover_tpu.models.llama import PRESETS

    config = dataclasses.replace(
        PRESETS[sizes["preset"]], n_layers=layers,
        max_seq_len=seq or sizes["seq"], attn_impl="flash",
    )
    if sizes["preset"] == "tiny":
        # toy rehearsal: blocks no larger than the toy sequence
        config = dataclasses.replace(
            config, attn_block_q=64, attn_block_k=64
        )
    return config


def _report_device(opts):
    import jax

    from dlrover_tpu.common.backend import require_backend

    require_backend()
    devices = jax.devices()
    emit(
        "device", platform=devices[0].platform,
        kind=devices[0].device_kind, count=len(devices),
    )
    if devices[0].platform != "tpu" and not opts.rehearsal:
        # JAX_PLATFORMS=cpu: a real-size run in interpret mode proves
        # nothing and takes hours
        raise SystemExit("no accelerator: not a rehearsal, not on a tpu")
    return devices


def _cache_counters():
    """Persistent-cache hits/misses of this process, from JAX's own
    monitoring events."""
    from jax._src import monitoring

    counts = {"hits": 0, "misses": 0}

    def on_event(name, **_kw):
        if name == "/jax/compilation_cache/cache_hits":
            counts["hits"] += 1
        elif name == "/jax/compilation_cache/cache_misses":
            counts["misses"] += 1

    monitoring.register_event_listener(on_event)
    return counts


def _cache_entries():
    import jax

    cache_dir = jax.config.jax_compilation_cache_dir or ""
    if not os.path.isdir(cache_dir):
        return cache_dir, -1
    return cache_dir, sum(
        1 for e in os.scandir(cache_dir) if e.is_file()
    )


def _fit_layers(sizes, devices, mesh_config, batch, seq):
    """The deepest candidate whose whole train step (the Trainer's own
    jitted step: default AdamW, donated state) the compiler places
    within 90% of the device's memory. Returns (layers, report)."""
    import jax
    import jax.numpy as jnp
    import optax

    from dlrover_tpu.models.llama import (
        llama_init,
        llama_logical_axes,
        llama_loss_fn,
    )
    from dlrover_tpu.parallel import Strategy, auto_accelerate
    from dlrover_tpu.parallel.accelerate import TrainState

    stats = [d.memory_stats() for d in devices]
    limit = (
        min(s["bytes_limit"] for s in stats) if all(stats) else None
    )
    tried = []
    for layers in sizes["layer_candidates"]:
        config = _model_config(sizes, layers, seq)
        optimizer = optax.adamw(sizes["lr"])

        def init(rng, config=config):
            return llama_init(config, rng)

        def init_state(init=init, optimizer=optimizer):
            params = init(jax.random.key(0))
            return TrainState(
                step=jnp.zeros((), jnp.int32), params=params,
                opt_state=optimizer.init(params),
            )

        state = jax.eval_shape(init_state)
        accel = auto_accelerate(
            llama_loss_fn(config), init, optimizer,
            llama_logical_axes(config),
            strategy=Strategy(mesh=mesh_config), devices=devices,
            reuse_state=state,
        )
        state = jax.tree.map(
            lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=s),
            state, accel.state_shardings,
        )
        t0 = time.perf_counter()
        try:
            compiled = jax.jit(
                accel.train_step, donate_argnums=(0,)
            ).lower(
                state,
                {"tokens": jax.ShapeDtypeStruct(
                    (batch, seq + 1), jnp.int32)},
                jax.eval_shape(lambda: jax.random.key(0)),
            ).compile()
        except Exception as e:  # noqa: BLE001 - only "does not fit"
            if "RESOURCE_EXHAUSTED" not in str(e):
                raise
            tried.append({"layers": layers, "fits": False,
                          "why": "compiler: RESOURCE_EXHAUSTED"})
            continue
        m = compiled.memory_analysis()
        need = (
            m.temp_size_in_bytes + m.argument_size_in_bytes
            + m.output_size_in_bytes - m.alias_size_in_bytes
        )
        fits = limit is None or need <= 0.9 * limit
        tried.append({
            "layers": layers, "fits": fits, "need_bytes": need,
            "limit_bytes": limit,
            "compile_s": round(time.perf_counter() - t0, 2),
            "pallas_kernels": compiled.as_text().count("tpu_custom_call"),
        })
        if fits:
            return layers, tried
    raise SystemExit(
        f"no layer count of {sizes['layer_candidates']} fits: {tried}"
    )


class _LogTap:
    """Structured taps on the framework's own log records (their
    ``args`` carry the numbers): per-step loss at the moment the
    Trainer has read it back from the device, and each shm save's
    blocking seconds."""

    def __init__(self, t_start, restart):
        import logging

        self.t_start, self.restart = t_start, restart
        self.steps = []
        self._last = None
        self._saved_since_last = False
        tap = self

        class Handler(logging.Handler):
            def emit(self, record):
                tap.on_record(record)

        for name in (
            "dlrover_tpu.trainer.trainer",
            "dlrover_tpu.trainer.flash_checkpoint.engine",
        ):
            logging.getLogger(name).addHandler(Handler())

    def on_record(self, record):
        msg = str(record.msg)
        now = time.perf_counter()
        if msg.startswith("step %d epoch %d loss"):
            step, _epoch, loss = record.args
            fields = dict(step=int(step), loss=float(loss),
                          wall=time.time(), restart=self.restart)
            # float(loss) has just waited for the device: the gap since
            # the previous record is one whole step — unless a save sat
            # in between, which is reported on its own
            if self._last is not None and not self._saved_since_last:
                fields["seconds"] = round(now - self._last, 4)
            if not self.steps:
                fields["since_start_s"] = round(
                    time.time() - self.t_start, 2
                )
            self._last, self._saved_since_last = now, False
            self.steps.append(fields)
            emit("step", **fields)
        elif msg.startswith("saved step %s to shm in"):
            step, seconds, mb = record.args
            self._saved_since_last = True
            emit("save", step=int(step), stall_s=round(float(seconds), 3),
                 mb=round(float(mb), 1), restart=self.restart)


class _RepeatedBatch:
    """The same batch every step (the loss must fall on it). In the
    first incarnation it also runs the kill protocol: after the save
    step it waits until the AGENT's saver committed that step to
    storage (the tracker file), says so, and then paces itself so the
    parent's SIGKILL lands mid-training."""

    def __init__(self, tokens, sizes, ckpt_dir, restart):
        self.tokens, self.sizes = tokens, sizes
        self.ckpt_dir, self.restart = ckpt_dir, restart
        self.acked = False

    def _tracker_step(self):
        from dlrover_tpu.common.constants import CheckpointConstant

        path = os.path.join(self.ckpt_dir, CheckpointConstant.TRACKER_FILE)
        try:
            with open(path) as f:
                return int(f.read().strip())
        except (OSError, ValueError):
            return -1

    def __iter__(self):
        save_step = self.sizes["save_step"]
        for pulled in range(10 ** 6):
            if self.restart == 0 and pulled >= save_step:
                if not self.acked:
                    t0 = time.perf_counter()
                    while self._tracker_step() < save_step:
                        if time.perf_counter() - t0 > 300:
                            raise SystemExit(
                                "agent saver never committed step "
                                f"{save_step}"
                            )
                        time.sleep(0.05)
                    self.acked = True
                    emit("save_acked", step=save_step, pid=os.getpid(),
                         waited_s=round(time.perf_counter() - t0, 3))
                time.sleep(1.0)
            yield {"tokens": self.tokens}


def _restore_report():
    """The restore this incarnation made, from the repo's telemetry."""
    from dlrover_tpu.common import telemetry

    snap = telemetry.snapshot() or {}
    out = {}
    for e in snap.get("events", []):
        if e["kind"] == "ckpt.restore":
            out.update(step=e.get("step"), restore_s=round(e["dur"], 3),
                       source=e.get("source_kind"))
        elif e["kind"] == "ckpt.restore.h2d":
            out.update(h2d_s=round(e["dur"], 3), mb=round(e["mb"], 1))
    return out


def child_train(opts, sizes):
    """The tpu-run worker of the one-chip smoke (both incarnations)."""
    t_start = time.time()
    from dlrover_tpu.trainer import init_distributed

    init_distributed()
    import jax
    import numpy as np

    from dlrover_tpu import native
    from dlrover_tpu.common.constants import NodeEnv
    from dlrover_tpu.models.llama import (
        llama_init,
        llama_logical_axes,
        llama_loss_fn,
    )
    from dlrover_tpu.parallel import MeshConfig, Strategy
    from dlrover_tpu.trainer.trainer import Trainer, TrainingArgs

    devices = _report_device(opts)
    counts = _cache_counters()
    cache_dir, entries_start = _cache_entries()
    restart = int(os.environ.get(NodeEnv.RESTART_COUNT, "0"))
    layers_file = os.path.join(opts.run_dir, "layers.json")
    mesh_config = MeshConfig(data=1, fsdp=1)
    if os.path.exists(layers_file):
        with open(layers_file) as f:
            layers = json.load(f)["layers"]
    else:
        t_fit = time.perf_counter()
        layers, tried = _fit_layers(
            sizes, devices, mesh_config, sizes["batch"], sizes["seq"]
        )
        with open(layers_file, "w") as f:
            json.dump({"layers": layers}, f)
        emit("layers", layers=layers, tried=tried,
             fit_s=round(time.perf_counter() - t_fit, 2))
    config = _model_config(sizes, layers)
    emit(
        "worker_start", restart=restart, pid=os.getpid(), layers=layers,
        params=config.param_count(), cache_dir=cache_dir,
        cache_entries_at_start=entries_start,
        native_lib=native.get_lib() is not None,
    )
    tokens = np.random.RandomState(opts.seed).randint(
        0, config.vocab_size, (sizes["batch"], sizes["seq"] + 1)
    ).astype(np.int32)
    out_dir = os.path.join(opts.run_dir, "out")
    data = _RepeatedBatch(
        tokens, sizes, os.path.join(out_dir, "checkpoints"), restart
    )
    tap = _LogTap(t_start, restart)
    trainer = Trainer(
        llama_loss_fn(config),
        lambda rng: llama_init(config, rng),
        llama_logical_axes(config),
        TrainingArgs(
            output_dir=out_dir, max_steps=sizes["steps"], log_steps=1,
            save_steps=sizes["save_step"], save_storage_every=1,
            flash_checkpoint=True, learning_rate=sizes["lr"],
            seed=opts.seed, strategy=Strategy(mesh=mesh_config),
        ),
        train_data=data,
    )
    trainer.train()
    trainer.close()
    _dir, entries_end = _cache_entries()
    emit(
        "worker_done", restart=restart, final_step=trainer.global_step,
        first_step=tap.steps[0]["step"] if tap.steps else None,
        cache_hits=counts["hits"], cache_misses=counts["misses"],
        cache_entries_at_end=entries_end, **_restore_report(),
    )


def child_kernels(opts, sizes):
    """Device line + the kernels this PR repaired, compiled on the chip
    against their interpret-mode twins (the compiler's word that they
    lower says nothing about what they compute)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dlrover_tpu.ops.attention import flash_attention_bshd, mha_reference
    from dlrover_tpu.ops.fused_optim import fused_adamw
    from dlrover_tpu.ops.quantization import dequantize_int8, quantize_int8

    _report_device(opts)
    toy = sizes["preset"] == "tiny"
    rs = np.random.RandomState(opts.seed)
    # ragged last row tile; real leaf width unless rehearsing
    shape = (520, 300) if toy else (2100, 11008)
    x = jnp.asarray(rs.randn(*shape).astype(np.float32) * 3)
    checks = {}

    # None = the backend's own mode: compiled on the chip (on a
    # rehearsal's CPU both sides are interpret mode, which proves paths)
    q, s, orig = quantize_int8(x, stochastic=False, interpret=None)
    qi, si, _ = quantize_int8(x, stochastic=False, interpret=True)
    # the two modes may round a tie apart; a real fault is far off
    checks["quantize_int8"] = bool(
        np.abs(np.asarray(q, np.int32) - np.asarray(qi, np.int32)).max() <= 1
        and np.allclose(np.asarray(s), np.asarray(si), rtol=1e-6)
    )
    d = dequantize_int8(q, s, orig, interpret=None)
    di = dequantize_int8(q, s, orig, interpret=True)
    checks["dequantize_int8"] = bool(
        np.allclose(np.asarray(d), np.asarray(di), rtol=1e-6, atol=1e-7)
        and float(jnp.max(jnp.abs(d - x))) <= float(s.max()) * 0.51
    )

    tree = {"w": x, "b": x[:7, :5]}
    grads = jax.tree.map(lambda p: p * 0.01 + 0.003, tree)
    for bits in (32, 8):
        outs = []
        for interpret in (None, True):
            opt = fused_adamw(1e-3, weight_decay=0.1, clip_norm=1.0,
                              bits=bits, interpret=interpret)
            state = opt.init(tree)
            for _ in range(2):  # the second step decodes 8-bit state
                upd, state = jax.jit(opt.update)(grads, state, tree)
            outs.append((upd, state))
        (u, st), (ui, sti) = outs
        ok = all(
            np.allclose(np.asarray(a), np.asarray(b), rtol=2e-2, atol=1e-6)
            for a, b in zip(jax.tree.leaves(u), jax.tree.leaves(ui))
        )
        if bits == 8:
            # the nu codes are what the repaired uint8 cast writes
            ok = ok and int(np.abs(
                np.asarray(st.nu_q, np.int32) - np.asarray(sti.nu_q, np.int32)
            ).max()) <= 1 and int(np.asarray(st.nu_q).max()) > 127
        checks[f"fused_adamw_{bits}"] = bool(ok)

    # bshd layout at the 4096-wide minor dim (per-head grid since the
    # fused backward does not fit VMEM there): forward and backward
    b, seq, h, hd = (1, 128, 4, 16) if toy else (1, 2048, 32, 128)
    qkv = [
        jnp.asarray(rs.randn(b, seq, h, hd) * 0.5, jnp.bfloat16)
        for _ in range(3)
    ]
    blk = 64 if toy else 1024

    def flash_loss(q, k, v):
        return flash_attention_bshd(
            q, k, v, block_q=blk, block_k=blk
        ).astype(jnp.float32).sum()

    def ref_loss(q, k, v):
        t = lambda a: a.transpose(0, 2, 1, 3)  # noqa: E731
        return mha_reference(t(q), t(k), t(v)).astype(jnp.float32).sum()

    got = jax.jit(jax.value_and_grad(flash_loss, argnums=(0, 1, 2)))(*qkv)
    ref = jax.jit(jax.value_and_grad(ref_loss, argnums=(0, 1, 2)))(*qkv)
    checks["flash_bshd_fwd_bwd"] = bool(
        np.allclose(float(got[0]), float(ref[0]), rtol=2e-2)
        and all(
            np.allclose(np.asarray(a, np.float32), np.asarray(r, np.float32),
                        atol=0.1, rtol=0.1)
            for a, r in zip(got[1], ref[1])
        )
    )
    emit("kernels", checks=checks)
    if not all(checks.values()):
        raise SystemExit(1)


def child_decode(opts, sizes):
    """One decode-pool member over the real RPC plane."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dlrover_tpu.agent.master_client import MasterClient
    from dlrover_tpu.models.llama import llama_apply, llama_init
    from dlrover_tpu.serving.engine import DecodeEngine
    from dlrover_tpu.serving.worker import DecodeWorker, RpcServingClient

    _report_device(opts)
    counts = _cache_counters()
    layers_file = os.path.join(opts.run_dir, "layers.json")
    layers = 2
    if os.path.exists(layers_file):
        with open(layers_file) as f:
            layers = json.load(f)["layers"]
    config = _model_config(sizes, layers)
    dtype = jnp.dtype(config.dtype)
    params = jax.jit(lambda: jax.tree.map(
        lambda p: p.astype(dtype), llama_init(config, jax.random.key(opts.seed))
    ))()
    engine = DecodeEngine(
        config, params, slots=sizes["slots"], capacity=sizes["capacity"]
    )
    t0 = time.perf_counter()
    engine.warmup(buckets=list(sizes["prompt_lens"]))
    warmup_s = time.perf_counter() - t0

    # the checked request: among a few prompts of the first length, the
    # one whose greedy first token a cache-free forward is surest of
    # (random weights leave top-1 and top-2 close; a comparison across
    # two bf16 code paths should not hang on a near-tie)
    import dataclasses

    plain = dataclasses.replace(config, attn_impl="reference")
    forward = jax.jit(lambda p, t: llama_apply(plain, p, t)[0, -1])
    best = None
    for variant in range(6):
        prompt = make_prompt(opts.seed, 100 * variant,
                             sizes["prompt_lens"][0], config.vocab_size)
        logits = np.asarray(forward(params, jnp.asarray([prompt], jnp.int32)))
        top2 = np.sort(logits)[-2:]
        gap = float(top2[1] - top2[0])
        if best is None or gap > best[0]:
            best = (gap, variant, int(np.argmax(logits)))
    gap, variant, want = best
    emit("decode_ready", layers=layers, vocab=config.vocab_size,
         warmup_s=round(warmup_s, 2),
         check_variant=variant, check_gap=round(gap, 4),
         prefill_traces=engine.prefill_traces(),
         cache_hits=counts["hits"], cache_misses=counts["misses"])

    client = MasterClient(opts.master, 0, "decode")
    worker = DecodeWorker(RpcServingClient(client, 0), engine, 0)
    worker.start()
    deadline = time.time() + opts.child_timeout
    while len(worker.finished) < sizes["requests"]:
        if time.time() > deadline or worker.crashed:
            break
        time.sleep(0.05)
    worker.stop()
    by_id = {f.request_id: f for f in worker.finished}
    got = by_id["r0"].tokens[0] if "r0" in by_id else None
    emit(
        "decode_done", served=len(worker.finished),
        tokens=sum(len(f.tokens) for f in worker.finished),
        first_token=got, reference_first_token=want,
        first_token_ok=got == want,
        # compiled after warm-up = a bucket the warm-up missed
        prefill_traces=engine.prefill_traces(),
        decode_traces=engine.decode_traces(),
    )
    client.close()
    if got != want or len(worker.finished) < sizes["requests"]:
        raise SystemExit(1)


def child_fsdp4(opts, sizes):
    """--chips 4: one tpu-run worker owns every chip, trains the model
    under MeshConfig(fsdp=<all>) with a flash save, then runs the same
    seed and global batch on one device in the same process."""
    from dlrover_tpu.trainer import init_distributed

    init_distributed()
    import jax
    import numpy as np
    import optax

    from dlrover_tpu.models.llama import (
        llama_init,
        llama_logical_axes,
        llama_loss_fn,
    )
    from dlrover_tpu.parallel import MeshConfig, Strategy, auto_accelerate
    from dlrover_tpu.parallel.mesh import get_mesh
    from dlrover_tpu.trainer.trainer import Trainer, TrainingArgs

    devices = _report_device(opts)
    n = len(devices)
    batch, seq = sizes["fsdp_batch"], sizes["fsdp_seq"]
    steps = sizes["fsdp_steps"]
    # depth: what fits ONE chip at this batch (the comparison run)
    layers, tried = _fit_layers(
        sizes, devices[:1], MeshConfig(data=1, fsdp=1), batch, seq
    )
    emit("layers", layers=layers, tried=tried, batch=batch, seq=seq)
    config = _model_config(sizes, layers, seq)
    tokens = np.random.RandomState(opts.seed).randint(
        0, config.vocab_size, (batch, seq + 1)
    ).astype(np.int32)
    loss_fn = llama_loss_fn(config)

    def init(rng):
        return llama_init(config, rng)

    tap = _LogTap(time.time(), 0)
    trainer = Trainer(
        loss_fn, init, llama_logical_axes(config),
        TrainingArgs(
            output_dir=os.path.join(opts.run_dir, "out"), max_steps=steps,
            log_steps=1, save_steps=sizes["fsdp_save_step"],
            flash_checkpoint=True, learning_rate=sizes["lr"],
            seed=opts.seed, strategy=Strategy(mesh=MeshConfig(fsdp=n)),
        ),
        train_data=[{"tokens": tokens}] * steps,
    )
    trainer.train()
    sharded_losses = [s["loss"] for s in tap.steps]

    # where the state lives: code that has never seen a second chip may
    # put everything on the first
    per_device = {d.id: 0 for d in devices}
    narrow, unsharded_bytes = [], 0
    for path, leaf in jax.tree_util.tree_leaves_with_path(trainer.state):
        unsharded_bytes += leaf.nbytes
        holders = set()
        for shard in leaf.addressable_shards:
            holders.add(shard.device.id)
            per_device[shard.device.id] += shard.data.nbytes
        if len(holders) != n:
            narrow.append(jax.tree_util.keystr(path))
    in_use = {
        d.id: (d.memory_stats() or {}).get("bytes_in_use") for d in devices
    }
    emit(
        "fsdp_state", devices=n, mesh=dict(get_mesh().shape),
        state_bytes_unsharded=unsharded_bytes,
        state_bytes_per_device=per_device, bytes_in_use=in_use,
        leaves_not_on_all_devices=narrow,
    )
    # each chip holds its quarter (small replicated leaves on top), and
    # its allocator agrees
    share_ok = not narrow and all(
        b <= 1.1 * unsharded_bytes / n for b in per_device.values()
    ) and all(
        v is None or v >= per_device[k] for k, v in in_use.items()
    )
    trainer.close()
    for leaf in jax.tree.leaves(trainer.state):
        leaf.delete()

    # the same seed and global batch on one device: the functions the
    # Trainer calls, without a second Trainer (it would take every chip)
    accel = auto_accelerate(
        loss_fn, init, optax.adamw(sizes["lr"]), llama_logical_axes(config),
        strategy=Strategy(mesh=MeshConfig(data=1, fsdp=1)),
        devices=devices[:1], seed=opts.seed,
    )
    state, single_losses = accel.state, []
    for step in range(steps):
        state, metrics = accel.train_step(
            state, {"tokens": tokens},
            jax.random.fold_in(jax.random.key(opts.seed), step),
        )
        single_losses.append(float(metrics["loss"]))
    agree = bool(np.allclose(
        sharded_losses, single_losses, rtol=sizes["loss_rtol"]
    ))
    emit(
        "fsdp_done", layers=layers, steps=steps, share_ok=share_ok,
        sharded_losses=sharded_losses, single_device_losses=single_losses,
        losses_agree=agree, rtol=sizes["loss_rtol"],
        losses_finite=bool(np.all(np.isfinite(sharded_losses))),
        falling=sharded_losses[-1] < sharded_losses[0],
        step_s=[s["seconds"] for s in tap.steps if "seconds" in s],
    )
    if not (agree and share_ok and len(sharded_losses) == steps):
        raise SystemExit(1)


CHILDREN = {
    "kernels": child_kernels, "train": child_train,
    "decode": child_decode, "fsdp4": child_fsdp4,
}

# ======================================================================
# parent (never imports JAX)
# ======================================================================


class PhaseFailed(Exception):
    def __init__(self, phase, code, why, rc=None):
        super().__init__(why)
        self.phase, self.code, self.why, self.rc = phase, code, why, rc


class Run:
    """Processes and logs of one smoke run; everything started here is
    stopped here."""

    def __init__(self, opts, sizes):
        self.opts, self.sizes = opts, sizes
        self.dir = os.path.join(
            REPO, ".smoke_run", f"{int(time.time())}-{os.getpid()}"
        )
        self.log_dir = os.path.join(self.dir, "logs")
        os.makedirs(self.log_dir)
        self.procs = []
        self.device = None
        self.phase, self.code = "start", 1
        # the run is isolated from whatever an earlier job left on this
        # machine: its own IPC sockets, shm segment names, logs, outputs
        from dlrover_tpu.common.backend import compile_cache_env

        self.env = compile_cache_env(dict(os.environ))
        self.env.update(
            PYTHONPATH=os.pathsep.join(
                [REPO] + [p for p in os.environ.get(
                    "PYTHONPATH", "").split(os.pathsep) if p]
            ),
            DLROVER_TPU_SOCKET_DIR=os.path.join(self.dir, "socks"),
            ELASTIC_JOB_NAME=f"smoke{os.getpid()}",
            DLROVER_TELEMETRY_DIR=os.path.join(self.dir, "telemetry"),
            DLROVER_TPU_MAX_CKPTS_TO_KEEP="1",
            PYTHONUNBUFFERED="1",
        )
        self.env.pop("DLROVER_MASTER_ADDR", None)

    # ------------------------------------------------------- processes

    def child_cmd(self, name, *extra):
        cmd = [sys.executable, os.path.join(REPO, "chip_smoke.py"),
               "--child", name, "--run-dir", self.dir,
               "--seed", str(self.opts.seed), *extra]
        if self.opts.rehearsal:
            cmd.append("--rehearsal")
        return cmd

    def spawn(self, name, cmd):
        path = os.path.join(self.log_dir, f"{name}.log")
        log = open(path, "ab")
        # its own process group: a whole tree (tpu-run -> master,
        # agent -> workers) can be stopped at once
        proc = subprocess.Popen(
            cmd, env=self.env, cwd=REPO, stdout=log,
            stderr=subprocess.STDOUT, start_new_session=True,
        )
        log.close()
        self.procs.append((name, proc))
        return proc, path

    def stop_all(self):
        """Stop whatever is still alive; return the names that were."""
        alive = []
        for name, proc in self.procs:
            if proc.poll() is None:
                alive.append(name)
            try:
                os.killpg(proc.pid, signal.SIGTERM)
            except ProcessLookupError:
                continue
        deadline = time.time() + 20
        for _name, proc in self.procs:
            try:
                proc.wait(timeout=max(deadline - time.time(), 0.1))
            except subprocess.TimeoutExpired:
                pass
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
        self.procs = []
        return alive

    def cleanup(self):
        """Give back the space the run took: the persisted checkpoints
        (gigabytes at real size) and any shm segment a failed run left
        — the logs stay."""
        import glob
        import shutil

        shutil.rmtree(os.path.join(self.dir, "out"), ignore_errors=True)
        for path in glob.glob(
            f"/dev/shm/dlrtpu_*{self.env['ELASTIC_JOB_NAME']}*"
        ):
            try:
                os.unlink(path)
            except OSError:
                pass

    # ------------------------------------------------------------ logs

    def logs(self):
        # (the agent keeps its worker channels' directories here too)
        return sorted(
            e.path for e in os.scandir(self.log_dir) if e.is_file()
        )

    def print_tails(self):
        for path in self.logs():
            print(f"----- tail of {os.path.relpath(path, REPO)} -----")
            try:
                with open(path, errors="replace") as f:
                    lines = f.readlines()
            except OSError as e:
                lines = [f"(unreadable: {e})\n"]
            for line in lines[-LOG_TAIL_LINES:]:
                line = line.rstrip("\n")
                print(line if len(line) <= 400 else line[:400] + " ...")
        sys.stdout.flush()

    def events(self, prefix=""):
        """Every SMOKE line written so far by logs named ``prefix*``."""
        out = []
        for path in self.logs():
            if not os.path.basename(path).startswith(prefix):
                continue
            with open(path, errors="replace") as f:
                for line in f:
                    at = line.find(MARK)
                    if at < 0:
                        continue
                    try:
                        out.append(json.loads(line[at + len(MARK):]))
                    except ValueError:
                        pass
        return out

    def enter(self, phase, code):
        """Name the phase every later failure is charged to."""
        self.phase, self.code = phase, code
        return time.time()

    def fail(self, why, rc=None):
        return PhaseFailed(self.phase, self.code, why, rc)

    def wait_for(self, proc, want, timeout, prefix=""):
        """Block until ``want(events)`` returns something truthy, the
        process ends, or the phase's time limit passes."""
        deadline = time.time() + timeout
        while True:
            found = want(self.events(prefix))
            if found:
                return found
            rc = proc.poll()
            if rc is not None:
                found = want(self.events(prefix))
                if found:
                    return found
                raise self.fail(f"process ended (exit {rc}) first", rc)
            if time.time() > deadline:
                raise self.fail(f"time limit of {timeout:.0f}s passed")
            time.sleep(0.1)

    def wait_exit(self, proc, timeout):
        try:
            rc = proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            raise self.fail(
                f"time limit of {timeout:.0f}s passed"
            ) from None
        if rc != 0:
            raise self.fail(f"exit code {rc}", rc)

    def note_device(self, events):
        """Platform, kind and count as the child's JAX reports them."""
        devs = [e for e in events if e["event"] == "device"]
        if not devs:
            raise self.fail("child printed no device line")
        dev = {k: devs[-1][k] for k in ("platform", "kind", "count")}
        if dev["platform"] != "tpu" and not self.opts.rehearsal:
            raise self.fail(
                f"no accelerator: the child ran on {dev['platform']!r}"
            )
        if dev["count"] != self.opts.chips and not self.opts.rehearsal:
            raise self.fail(
                f"{dev['count']} device(s), wanted {self.opts.chips}"
            )
        self.device = dev

    def check(self, ok, what):
        if not ok:
            raise self.fail(f"check failed: {what}")

    # ---------------------------------------------------------- phases

    def tpu_run(self, worker):
        return [
            sys.executable, "-m", "dlrover_tpu.trainer.run",
            "--nnodes", "1", "--nproc_per_node", "1",
            "--max-restarts", "2", "--log-dir", self.log_dir,
            *self.child_cmd(worker)[1:],
        ]

    def phase_kernels(self):
        self.enter("kernels", EXIT_KERNELS)
        proc, _ = self.spawn("kernels", self.child_cmd("kernels"))
        try:
            self.wait_exit(proc, 300)
        finally:
            # even a failed child said where it ran
            events = self.events("kernels")
            if any(e["event"] == "device" for e in events):
                self.note_device(events)
        (checks,) = [e for e in events if e["event"] == "kernels"]
        print(json.dumps({"phase": self.phase, **checks["checks"]}),
              flush=True)

    def phase_train_kill_resume(self):
        sizes = self.sizes
        t0 = self.enter("train", EXIT_TRAIN)
        proc, _ = self.spawn("tpu_run", self.tpu_run("train"))
        acked = self.wait_for(
            proc,
            lambda ev: [e for e in ev if e["event"] == "save_acked"],
            600, prefix="worker_",
        )[0]
        events = self.events("worker_")
        self.note_device(events)
        first = [e for e in events if e.get("restart") == 0]
        steps = [e for e in first if e["event"] == "step"]
        start = [e for e in first if e["event"] == "worker_start"][0]
        layers = [e for e in events if e["event"] == "layers"][0]
        saves = [e for e in first if e["event"] == "save"]
        losses = [s["loss"] for s in steps]
        self.check(start["layers"] >= 2, "at least 2 layers")
        self.check(finite(losses),
                   f"finite losses {losses}")
        self.check(len(losses) >= 2 and losses[-1] < losses[0],
                   f"loss falls on the repeated batch {losses}")
        kernels = [t for t in layers["tried"] if t["layers"] == start["layers"]]
        self.check(
            self.opts.rehearsal or kernels[0]["pallas_kernels"] >= 2,
            "flash kernel (tpu_custom_call) in the compiled train step",
        )
        self.check(saves and saves[0]["step"] == acked["step"],
                   "the acked save is the shm save the worker made")
        print(json.dumps({
            "phase": self.phase, "layers": start["layers"],
            "params": start["params"], "fit": layers["tried"],
            "losses": losses,
            "step_s_after_warmup": [s["seconds"] for s in steps[1:]
                                    if "seconds" in s],
            "time_to_first_step_cold_s": steps[0]["since_start_s"],
            "of_which_layer_fit_compiles_s": layers["fit_s"],
            "save_stall_s": saves[0]["stall_s"], "save_mb": saves[0]["mb"],
            "agent_persist_ack_s": acked["waited_s"],
            "native_lib": start["native_lib"],
            "compile_cache_dir": start["cache_dir"],
            "compile_cache_entries_at_start": start["cache_entries_at_start"],
            "seconds": round(time.time() - t0, 1),
        }), flush=True)

        # ---- kill the worker; the agent's restart path does the rest
        t_kill = self.enter("resume", EXIT_RESUME)
        try:
            os.kill(acked["pid"], signal.SIGKILL)
        except ProcessLookupError:
            raise self.fail("worker was gone before the kill landed"
            ) from None
        self.wait_exit(proc, 600)
        events = self.events("worker_")
        second = [e for e in events if e.get("restart") == 1]
        done = [e for e in second if e["event"] == "worker_done"]
        self.check(done, "restarted worker ran to its end")
        done = done[0]
        rstart = [e for e in second if e["event"] == "worker_start"][0]
        rsteps = [e for e in second if e["event"] == "step"]
        save_step = sizes["save_step"]
        self.check(not [
            e for e in events
            if e["event"] == "worker_done" and e.get("restart") == 0
        ], "the first worker was killed, not finished")
        self.check(done.get("step") == save_step
                   and done.get("source") == "shm",
                   f"restored step {save_step} from shm, got {done}")
        self.check(rsteps and rsteps[0]["step"] == save_step + 1,
                   f"training went on from step {save_step + 1}")
        self.check(done["final_step"] == sizes["steps"],
                   f"trained to max_steps={sizes['steps']}")
        rlosses = [s["loss"] for s in rsteps]
        self.check(finite(rlosses),
                   f"finite losses after resume {rlosses}")
        # the killed run's loss at the same step: the restored state is
        # the saved one, so the resumed loss continues that curve
        before = {s["step"]: s["loss"] for s in steps}
        again = rsteps[0]["step"]
        if again in before:
            self.check(
                abs(rsteps[0]["loss"] - before[again])
                <= 1e-2 * max(abs(before[again]), 1.0),
                f"step {again} loss {rsteps[0]['loss']} after resume vs "
                f"{before[again]} before the kill",
            )
        self.check(done["cache_hits"] >= 1,
                   "restarted worker hit the compile cache")
        print(json.dumps({
            "phase": self.phase,
            "kill_to_first_resumed_step_s": round(
                rsteps[0]["wall"] - t_kill, 2),
            # on the clock of the telemetry's ``t``: the ``resume``
            # trace's root runs from ``worker.exit``'s ``died_t``
            "t_kill": t_kill, "t_first_resumed_step": rsteps[0]["wall"],
            "restore_s": done.get("restore_s"),
            "restore_h2d_s": done.get("h2d_s"),
            "restore_mb": done.get("mb"),
            "restored_step": done.get("step"),
            "first_resumed_step": rsteps[0]["step"],
            "final_step": done["final_step"],
            "losses": rlosses,
            "time_to_first_step_cached_s": rsteps[0]["since_start_s"],
            "time_to_first_step_cold_s": steps[0]["since_start_s"],
            "compile_cache_entries": {
                "first_worker_start": start["cache_entries_at_start"],
                "restarted_worker_start": rstart["cache_entries_at_start"],
                "restarted_worker_end": done["cache_entries_at_end"],
            },
            "restarted_worker_cache_hits": done["cache_hits"],
            "restarted_worker_cache_misses": done["cache_misses"],
        }), flush=True)

    def phase_serve(self):
        sizes = self.sizes
        t0 = self.enter("serve", EXIT_SERVE)
        master, master_log = self.spawn("master", [
            sys.executable, "-m", "dlrover_tpu.master.main",
            "--platform", "local", "--node_num", "1", "--port", "0",
        ])

        def master_addr(_events):
            with open(master_log, errors="replace") as f:
                for line in f:
                    if line.startswith("DLROVER_MASTER_ADDR="):
                        return line.strip().partition("=")[2]

        addr = self.wait_for(master, master_addr, 60)
        decode, _ = self.spawn("decode", self.child_cmd(
            "decode", "--master", addr, "--child-timeout", "300",
        ))
        ready = self.wait_for(
            decode,
            lambda ev: [e for e in ev if e["event"] == "decode_ready"],
            600, prefix="decode",
        )[0]
        self.note_device(self.events("decode"))

        from dlrover_tpu.agent.master_client import MasterClient

        vocab = ready["vocab"]
        client = MasterClient(addr, 0, "client")
        try:
            t_submit = time.time()
            for i, length in enumerate(sizes["prompt_lens"]):
                # r0 is the checked request: greedy, the variant the
                # worker picked; the rest sample
                index = 100 * ready["check_variant"] if i == 0 else i
                client.serve_submit(
                    f"r{i}", make_prompt(self.opts.seed, index, length, vocab),
                    max_new_tokens=sizes["new_tokens"],
                    temperature=0.0 if i == 0 else 0.7,
                )
            results, deadline = {}, time.time() + 300
            while len(results) < sizes["requests"]:
                for i in range(sizes["requests"]):
                    res = client.serve_fetch(f"r{i}")
                    if res is not None and res.state in ("done", "failed"):
                        results[f"r{i}"] = res
                if decode.poll() not in (None, 0):
                    raise self.fail("decode worker died", decode.poll())
                if time.time() > deadline:
                    raise self.fail("requests not served within 300s")
                time.sleep(0.05)
            served_s = time.time() - t_submit
            status = client.serve_status()
        finally:
            client.close()
        self.wait_exit(decode, 120)
        master.terminate()
        master.wait(timeout=30)
        done = [e for e in self.events("decode")
                if e["event"] == "decode_done"][0]
        counts = status.get("counts", {})
        self.check(all(r.state == "done" for r in results.values()),
                   f"every request done: {counts}")
        self.check(counts.get("failed") == 0, "failed=0")
        self.check(all(
            1 <= len(r.tokens) <= sizes["new_tokens"]
            for r in results.values()
        ), "1..max_new_tokens tokens each")
        self.check(done["first_token_ok"],
                   "first greedy token == argmax of a cache-free forward")
        print(json.dumps({
            "phase": self.phase, "layers": ready["layers"],
            "requests": len(results),
            "tokens_served": sum(len(r.tokens) for r in results.values()),
            "prompt_tokens": sum(sizes["prompt_lens"]),
            "submit_to_all_done_s": round(served_s, 2),
            "warmup_compile_s": ready["warmup_s"],
            "warmup_cache_hits": ready["cache_hits"],
            "warmup_cache_misses": ready["cache_misses"],
            "first_token": done["first_token"],
            "reference_first_token": done["reference_first_token"],
            "reference_top2_gap": ready["check_gap"],
            "prefill_traces": done["prefill_traces"],
            "decode_traces": done["decode_traces"],
            "ledger": counts,
            "seconds": round(time.time() - t0, 1),
        }), flush=True)

    def phase_fsdp4(self):
        t0 = self.enter("fsdp4", EXIT_FSDP4)
        proc, agent_log = self.spawn("tpu_run", self.tpu_run("fsdp4"))
        self.wait_exit(proc, 900)
        events = self.events("worker_")
        self.note_device(events)
        done = [e for e in events if e["event"] == "fsdp_done"]
        self.check(done, "worker reported fsdp_done")
        done = done[0]
        state = [e for e in events if e["event"] == "fsdp_state"][0]
        saves = [e for e in events if e["event"] == "save"]
        self.check(done["losses_finite"] and done["falling"],
                   f"finite, falling losses {done['sharded_losses']}")
        self.check(saves, "a flash save was made")
        # the join-time probe's legs (the collective one crosses chips)
        probe = ""
        with open(agent_log, errors="replace") as f:
            for line in f:
                if "hardware probe (child)" in line:
                    probe = line.split("hardware probe (child): ", 1)[1].strip()
        self.check("collective" in probe and "ERROR" not in probe,
                   f"join-time probe ran its legs on the chips: {probe!r}")
        print(json.dumps({
            "phase": self.phase, "layers": done["layers"], "mesh": state["mesh"],
            "sharded_losses": done["sharded_losses"],
            "single_device_losses": done["single_device_losses"],
            "losses_agree_rtol": done["rtol"],
            "step_s_after_warmup": done["step_s"],
            "state_bytes_per_device": state["state_bytes_per_device"],
            "bytes_in_use_per_device": state["bytes_in_use"],
            "save_stall_s": saves[0]["stall_s"], "save_mb": saves[0]["mb"],
            "join_probe": probe,
            "seconds": round(time.time() - t0, 1),
        }), flush=True)


def parent(opts, sizes):
    run = Run(opts, sizes)
    phases = (
        [run.phase_fsdp4] if opts.chips == 4
        else [run.phase_kernels, run.phase_train_kill_resume,
              run.phase_serve]
    )
    if opts.phases:
        # a measurement of one phase (never the contract line below)
        phases = [p for p in phases
                  if p.__name__[len("phase_"):] in opts.phases.split(",")]
    code = EXIT_OK
    try:
        for phase in phases:
            phase()
            leftover = run.stop_all()
            if leftover:
                raise PhaseFailed(
                    phase.__name__, EXIT_LEFTOVER,
                    f"still running after the phase: {leftover}",
                )
    except PhaseFailed as e:
        code = e.code
        print(json.dumps({
            "phase": e.phase, "failed": True, "why": e.why,
            "child_exit_code": e.rc,
        }), flush=True)
        run.stop_all()
        run.print_tails()
    finally:
        run.stop_all()
        run.cleanup()
    if code != EXIT_OK:
        return code
    if opts.rehearsal or opts.phases:
        print(json.dumps({"rehearsal": opts.rehearsal, "ok": False,
                          "phases": opts.phases, "device": run.device,
                          "telemetry": run.env["DLROVER_TELEMETRY_DIR"]}))
        return EXIT_OK
    print(json.dumps({"ok": True, "device": run.device}))
    return EXIT_OK


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--chips", type=int, default=1, choices=(1, 4))
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--rehearsal", action="store_true")
    parser.add_argument("--child", choices=sorted(CHILDREN))
    parser.add_argument("--run-dir")
    parser.add_argument("--master")
    parser.add_argument("--child-timeout", type=float, default=300)
    parser.add_argument(
        "--phases", default="",
        help="run only these one-chip phases (kernels, "
             "train_kill_resume, serve; comma-separated): a measurement, "
             "so the last line never says ok",
    )
    opts = parser.parse_args(argv)
    sizes = TOY if opts.rehearsal else REAL
    if opts.child:
        sys.path.insert(0, REPO)
        CHILDREN[opts.child](opts, sizes)
        return EXIT_OK
    return parent(opts, sizes)


if __name__ == "__main__":
    sys.exit(main())
