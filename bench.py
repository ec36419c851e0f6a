"""Headline bench: training goodput with in-loop Flash Checkpoint on one
TPU chip.

Mirrors the reference's flagship claim (BASELINE.md): flash checkpointing
raises training goodput to >=95% by making the in-loop pause tiny
(~0.2 s per save on GLM-65B; 151 s -> 0.5 s for Megatron GPT-1.5B saves).

Protocol (single chip):
1. headline model = the largest config that fits the chip with optimizer
   state (llama2-1b class, 941M params): measure bf16 and int8 steps,
   SELECT the faster dtype gated on loss parity (int8 x int8 -> int32
   dots ride the v5e MXU's 2x int8 path) — the reference ships low
   precision as a production win (Fp8Optimization via TransformerEngine,
   amp_optimization.py:197);
2. measure the in-loop blocking pause of engine.save_to_memory_async
   (dispatches the HBM->host transfers; a copier thread fills shm while
   the device keeps training). The pause is dispatch-side and
   state-size-independent; the drain/restore legs that the host<->device
   link bounds run on the 1 GB nano-350m state (the link is measured
   alone as device_link_*), while the ENGINE-limited throughput is
   measured separately on a headline-sized host-resident state
   (ckpt_engine_gbps);
3. goodput = interval / (interval + pause) at a 30 s checkpoint
   interval (the reference's production cadence);
4. vs_baseline = goodput / 0.95 (the reference's published goodput).

Prints ONE JSON line. Needs the chip: without an accelerator it exits
non-zero, unless the caller pinned ``JAX_PLATFORMS=cpu`` — then it runs
the tiny "smoke" arm as a control-flow check, whose numbers are not
device metrics (``"backend": "cpu"`` says so in the output).
"""

import json
import os
import shutil
import tempfile
import time


def _sparse_bench(on_tpu: bool) -> dict:
    """KvEmbedding / TieredKvEmbedding lookup+update throughput vs a
    dense gather baseline (TFPlus exists because sparse lookups are a
    perf play: kv_variable/kernels/hashmap.h, hybrid_embedding/).

    Each step: host id->slot mapping, device gather, squared-norm loss,
    SGD scatter-update of the touched rows. Rows/s counts looked-up ids
    per wall second. The tiered arm draws ids from a vocab 4x the
    device capacity so steps promote spilled rows through prepare_batch
    (host tier -> device scatter).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dlrover_tpu.ops.sparse_embedding import (
        KvEmbedding,
        TieredKvEmbedding,
    )

    dim = 128
    cap = (1 << 16) if on_tpu else (1 << 10)
    batch = 8192 if on_tpu else 256
    steps = 30 if on_tpu else 3
    rs = np.random.RandomState(0)

    @jax.jit
    def sgd_step(table, slots):
        def loss_fn(t):
            return jnp.sum(KvEmbedding.embed(t, slots) ** 2)

        grads = jax.grad(loss_fn)(table)
        return table - 0.01 * grads

    # --- KvEmbedding: host mapper + device gather/update -------------
    kv = KvEmbedding(dim=dim, capacity=cap)
    table = kv.init_table(jax.random.key(0))
    active = cap - (cap // 8)  # stay under capacity: no eviction here
    ids_pool = rs.randint(0, 1 << 40, size=active)
    slots = jnp.asarray(kv.lookup_slots(rs.choice(ids_pool, batch)))
    table = sgd_step(table, slots)  # compile
    jax.block_until_ready(table)
    t0 = time.perf_counter()
    for _ in range(steps):
        slots = jnp.asarray(kv.lookup_slots(rs.choice(ids_pool, batch)))
        table = sgd_step(table, slots)
    jax.block_until_ready(table)
    kv_rows_s = batch * steps / (time.perf_counter() - t0)

    # --- dense gather baseline: same device work, no host mapper -----
    dense = jnp.asarray(np.asarray(table))  # same size/dtype
    slots = jnp.asarray(rs.randint(0, cap, batch))
    dense = sgd_step(dense, slots)
    jax.block_until_ready(dense)
    t0 = time.perf_counter()
    for _ in range(steps):
        slots = jnp.asarray(rs.randint(0, cap, batch))
        dense = sgd_step(dense, slots)
    jax.block_until_ready(dense)
    dense_rows_s = batch * steps / (time.perf_counter() - t0)

    # --- tiered: vocab 4x device capacity, host-tier promotion -------
    # zipf-distributed ids (the sparse-feature reality the tier is built
    # for: hot ids stay device-resident, the cold tail lives on the
    # host) — a uniform draw would promote ~the whole batch every step
    # and measure only the host->device transfer latency. The
    # whole 4x vocab is imported up front: the device table FILLS and
    # 3x capacity spills to the host tier, so every timed step runs the
    # real demote/promote round-trip instead of cold-table inserts.
    tiered = TieredKvEmbedding(dim=dim, capacity=cap)
    ttable = tiered.init_table(jax.random.key(1))
    big_vocab = rs.randint(0, 1 << 40, size=4 * cap)
    ttable = tiered.import_(
        ttable, big_vocab,
        (rs.randn(big_vocab.size, dim) * 0.01).astype(np.float32),
    )
    assert tiered.host_ids > 0, "tiered import did not overflow"

    # exponent 1.5: ~0.4% of draws land past the device-resident head
    # at bench capacity — tens of demote/promote rows per step, so the
    # timed loop measures the tiering machinery with the spill path
    # continuously live. Heavier tails just scale the rows moved per
    # step, which re-measures the host<->device link (device_link_*),
    # not the tier.
    def zipf_ids(n):
        ranks = np.minimum(
            rs.zipf(1.5, size=n), len(big_vocab)
        ) - 1
        return big_vocab[ranks]

    # warmup compiles the bucketed gather/scatter variants the zipf
    # demote/promote traffic actually hits (power-of-two buckets: a
    # handful of sizes) so the timed loop measures steady state, not
    # compilation
    for _ in range(4):
        ttable, tslots = tiered.prepare_batch(ttable, zipf_ids(batch))
        ttable = sgd_step(ttable, jnp.asarray(tslots))
    jax.block_until_ready(ttable)
    c0 = dict(tiered.counters)
    t0 = time.perf_counter()
    for _ in range(steps):
        ttable, tslots = tiered.prepare_batch(ttable, zipf_ids(batch))
        ttable = sgd_step(ttable, jnp.asarray(tslots))
    jax.block_until_ready(ttable)
    tiered_rows_s = batch * steps / (time.perf_counter() - t0)

    return {
        "sparse_lookup_mrows_s": round(kv_rows_s / 1e6, 3),
        "sparse_dense_gather_mrows_s": round(dense_rows_s / 1e6, 3),
        "sparse_tiered_mrows_s": round(tiered_rows_s / 1e6, 3),
        "sparse_tier_host_rows": tiered.host_ids,
        "sparse_tier_demoted_rows":
            tiered.counters["demoted_rows"] - c0["demoted_rows"],
        "sparse_tier_promoted_rows":
            tiered.counters["promoted_rows"] - c0["promoted_rows"],
        "sparse_tier_fresh_rows":
            tiered.counters["fresh_rows"] - c0["fresh_rows"],
        "sparse_dim_capacity_batch": f"{dim}x{cap} B{batch}",
    }


def _control_plane_bench(n_agents: int = 8, seconds: float = 1.5) -> dict:
    """Master control-plane latency baseline: an in-process master with
    N client threads driving the real agent call mix (rendezvous joins,
    comm-world polls, step reports, kv traffic). Publishes the keys the
    future 1000-agent swarm harness will regress against:
    ``master_rpc_p99_ms`` (per-verb servicer latency, quantiles
    interpolated from the le-bucket histograms the RPC server records)
    and ``joins_per_sec`` (sustained join throughput)."""
    import threading

    from dlrover_tpu.agent.master_client import MasterClient
    from dlrover_tpu.common import telemetry
    from dlrover_tpu.common.constants import NodeType, RendezvousName
    from dlrover_tpu.common.telemetry import (
        hist_quantile,
        sum_bucket_counts,
    )
    from dlrover_tpu.master.master import LocalJobMaster
    from dlrover_tpu.scheduler.job import new_job_args

    master = LocalJobMaster(
        0, new_job_args("local", "cp-bench", node_num=n_agents)
    )
    master.prepare()
    deadline = time.monotonic() + seconds
    joins = [0] * n_agents
    errors = [0]

    def agent_loop(rank: int):
        client = MasterClient(master.addr, rank, NodeType.WORKER)
        try:
            while time.monotonic() < deadline:
                client.join_rendezvous(
                    rank, 1, RendezvousName.ELASTIC_TRAINING
                )
                joins[rank] += 1
                client.get_comm_world(
                    RendezvousName.ELASTIC_TRAINING, rank
                )
                client.report_heart_beat()
                client.report_global_step(joins[rank])
                client.kv_store_set(f"k{rank}", b"v")
        except Exception:  # noqa: BLE001 - surfaced via error count
            errors[0] += 1
        finally:
            client.close()

    threads = [
        threading.Thread(target=agent_loop, args=(r,), daemon=True)
        for r in range(n_agents)
    ]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=seconds + 30)
    wall = time.perf_counter() - t0
    master.stop()

    snap = telemetry.snapshot() or {}
    bounds, overall = sum_bucket_counts(
        h for h in snap.get("histograms", ())
        if h["name"] == "master.rpc.seconds"
    )
    if bounds is None:
        return {"control_plane_error": "no master.rpc.seconds recorded"}
    return {
        "master_rpc_p50_ms": round(
            hist_quantile(bounds, overall, 0.50) * 1e3, 4
        ),
        "master_rpc_p99_ms": round(
            hist_quantile(bounds, overall, 0.99) * 1e3, 4
        ),
        "master_rpc_calls": sum(overall),
        "joins_per_sec": round(sum(joins) / wall, 1),
        "control_plane_agents": n_agents,
        "control_plane_errors": errors[0],
    }


def _profiling_bench(nsteps: int = 512, repeats: int = 3) -> dict:
    """Deep-profiling plane cost surface:
    ``profile_sample_overhead_pct`` — the governed sampler's
    steady-state cost: the MEASURED per-window overhead amortized over
    the MEASURED governed gap (window_cost / (gap * step_time)); the
    cost governor picks the gap so this stays under the 2% budget by
    construction, and this key proves it with real numbers from this
    machine (plus ``profile_sample_loop_delta_pct``, the raw sampled-
    vs-bare loop delta over the bench span, as the unmodeled sanity
    check). ``capture_roundtrip_s`` is operator request -> directive
    -> worker capture window -> parsed artifact -> ledger ``done``,
    the full deep-capture path in one process."""
    import shutil
    import tempfile
    import threading

    import jax
    import jax.numpy as jnp
    import numpy as np

    from dlrover_tpu.common import profiling, trace_summary
    from dlrover_tpu.master.capture import CaptureManager

    x0 = jnp.asarray(
        np.random.RandomState(0).randn(256, 256).astype(np.float32)
    )

    @jax.jit
    def step(a):
        return a @ a / 256.0

    step(x0).block_until_ready()  # compile outside every window
    # one throwaway trace: the profiler's one-time init (seconds) must
    # not be billed to the steady-state number
    warm_dir = tempfile.mkdtemp(prefix="dlrtpu_prof_warm_")
    try:
        jax.profiler.start_trace(warm_dir)
        step(x0).block_until_ready()
        jax.profiler.stop_trace()
    except Exception:  # noqa: BLE001 - a trace already active
        pass
    finally:
        shutil.rmtree(warm_dir, ignore_errors=True)

    def run(sampler, n):
        y = x0
        t0 = time.perf_counter()
        for i in range(1, n + 1):
            ts = time.perf_counter()
            if sampler is not None:
                sampler.on_step_start(i)
            y = step(y)
            y.block_until_ready()
            if sampler is not None:
                sampler.on_step_end(
                    i, time.perf_counter() - ts, block_on=y
                )
        return time.perf_counter() - t0

    parse_fn = None
    if not trace_summary.toolchain_available():
        # no offline parser in this environment: a trace-stat stub
        # keeps the capture-side overhead honest (start/stop + file
        # writes still happen) with a deterministic payload
        def parse_fn(trace_dir, steps):
            total = sum(
                os.path.getsize(p)
                for p in trace_summary.xplane_paths(trace_dir)
            )
            return {"fusion": total / 1e6}

    tmp = tempfile.mkdtemp(prefix="dlrtpu_prof_bench_")
    try:
        base = min(run(None, nsteps) for _ in range(repeats))
        sampler = profiling.DeviceTimeSampler(
            os.path.join(tmp, "prof"),
            sample_steps=16,  # floor; the governor stretches it
            parse_fn=parse_fn,
            baseline=profiling.OpCostBaseline(
                os.path.join(tmp, "baseline.json")
            ),
            capture_channel=None,
            artifact_root=os.path.join(tmp, "captures"),
        )
        sampler.set_context("bench", "devices=1")
        try:
            on = min(run(sampler, nsteps) for _ in range(repeats))
            window_cost_s = sampler.last_window_cost_s
            gap = sampler.last_gap
            # the governor's own denominator: the steady-state ratio
            # it actually enforced (falls back to the bare-loop step)
            step_s = sampler.step_ewma_s or (base / nsteps)
        finally:
            sampler.close()
        loop_delta_pct = (on / base - 1.0) * 100 if base > 0 else 0.0
        overhead_pct = (
            window_cost_s / (gap * step_s) * 100
            if gap > 0 and step_s > 0 else 0.0
        )

        # capture round trip: master ledger -> channel -> worker
        # window -> artifact -> result, all in process
        channel = profiling.CaptureChannel(os.path.join(tmp, "chan"))
        cap_sampler = profiling.DeviceTimeSampler(
            os.path.join(tmp, "prof2"),
            sample_steps=0,
            parse_fn=parse_fn,
            baseline=profiling.OpCostBaseline(
                os.path.join(tmp, "baseline.json")
            ),
            capture_channel=channel,
            artifact_root=os.path.join(tmp, "captures"),
        )
        cap_sampler.set_context("bench", "devices=1")
        manager = CaptureManager(cooldown_s=0.0)
        try:
            t0 = time.perf_counter()
            ack = manager.request(0, steps=2, reason="bench")
            directive = manager.poll_directive(0)
            executor = threading.Thread(
                target=profiling.execute_capture,
                args=(directive, channel,
                      lambda cid, ok, artifact, summary, error:
                      manager.report_result(
                          cid, 0, ok, artifact=artifact,
                          summary=summary, error=error,
                      )),
                kwargs={"timeout": 60.0},
                daemon=True,
            )
            executor.start()
            deadline = time.time() + 60
            y = x0
            i = 0
            while time.time() < deadline:
                i += 1
                cap_sampler.on_step_start(i)
                y = step(y)
                cap_sampler.on_step_end(i, 0.0, block_on=y)
                rec = next(
                    (r for r in manager.list()
                     if r["id"] == ack["capture_id"]), None,
                )
                if rec is not None and rec["state"] in (
                    "done", "failed",
                ):
                    break
            executor.join(timeout=60)
            roundtrip = time.perf_counter() - t0
            rec = next(
                (r for r in manager.list()
                 if r["id"] == ack["capture_id"]), None,
            )
            state = rec["state"] if rec else "missing"
        finally:
            cap_sampler.close()
        return {
            "profile_sample_overhead_pct": round(overhead_pct, 3),
            "profile_sample_loop_delta_pct": round(loop_delta_pct, 2),
            "profile_sample_window_cost_ms": round(
                window_cost_s * 1e3, 3
            ),
            "profile_sample_gap_steps": gap,
            "profile_sample_base_step_us": round(
                base / nsteps * 1e6, 2
            ),
            "capture_roundtrip_s": (
                round(roundtrip, 3) if state == "done" else None
            ),
            "capture_roundtrip_state": state,
            "profile_parse_backend": (
                "xprof" if trace_summary.toolchain_available()
                else "stub"
            ),
        }
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def main():
    import gc
    import dataclasses as _dc

    # latency-hiding scheduler flags for the "xla" overlap mode,
    # appended BEFORE first backend use (XLA parses XLA_FLAGS lazily at
    # backend init, never at import). Opt-in: flag availability depends
    # on the XLA/libtpu build — this repo's CPU wheel rejects all three
    # as unknown flags, fatally — so the operator asks for them
    # explicitly on a build known to carry them.
    if os.environ.get("DLROVER_TPU_LATENCY_HIDING") == "1":
        from dlrover_tpu.parallel.overlap import latency_hiding_flags

        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") + " " + latency_hiding_flags()
        ).strip()

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from dlrover_tpu.models import (
        PRESETS,
        llama_init,
        llama_logical_axes,
        llama_loss_fn,
    )
    from dlrover_tpu.common.backend import (
        enable_compile_cache,
        require_backend,
    )
    from dlrover_tpu.parallel import MeshConfig, Strategy, auto_accelerate
    from dlrover_tpu.trainer.flash_checkpoint.engine import (
        ReplicatedCheckpointEngine,
    )

    # no silent CPU run: raises unless the chip is there or the caller
    # pinned JAX_PLATFORMS=cpu (common/backend.py)
    on_tpu = require_backend() == "tpu"
    enable_compile_cache()
    if on_tpu:
        headline_cfg = _dc.replace(PRESETS["llama2-1b"], ce_chunks=4)
        headline_arm = "llama2-1b dim2048 B4 ce4"
        nano_cfg = PRESETS["nano-350m"]
        h_batch, batch, seq, steps = 4, 8, 2048, 20
    else:  # JAX_PLATFORMS=cpu: control-flow check, no device metric
        headline_cfg = _dc.replace(PRESETS["tiny"], ce_chunks=2)
        headline_arm = "smoke"
        nano_cfg = PRESETS["tiny"]
        h_batch, batch, seq, steps = 8, 8, 64, 3

    strategy = Strategy(
        mesh=MeshConfig(data=1, fsdp=1),
        compute_dtype="bfloat16",
        remat="none",
        donate=True,
    )

    def build(cfg, strat):
        return auto_accelerate(
            llama_loss_fn(cfg),
            lambda rng: llama_init(cfg, rng),
            optax.adafactor(1e-3),
            llama_logical_axes(cfg),
            strategy=strat,
            devices=jax.devices()[:1],
        )

    def run_arm(cfg, strat, toks, nsteps):
        """(step_s, final_loss) then free everything."""
        r = build(cfg, strat)
        s = r.state
        s, m = r.train_step(s, {"tokens": toks}, jax.random.key(0))
        _ = float(m["loss"])
        t0 = time.perf_counter()
        for i in range(nsteps):
            s, m = r.train_step(s, {"tokens": toks}, jax.random.key(i))
        loss = float(m["loss"])  # waits for the device
        dt = (time.perf_counter() - t0) / nsteps
        del r, s
        gc.collect()
        return dt, loss

    # ---- headline: largest-fitting model; measured PER-SITE dtype
    # selection + measured overlap selection (every lever picked the
    # way int8 always was: speed gated on loss parity, never
    # hardcoded) ----
    rng = np.random.RandomState(0)
    h_tokens = jnp.asarray(
        rng.randint(0, headline_cfg.vocab_size, (h_batch, seq + 1))
    )
    t_bf16, loss_bf16 = run_arm(headline_cfg, strategy, h_tokens, steps)

    from dlrover_tpu.parallel.engine import LOSS_PARITY_TOL

    def parity_pct(loss):
        return abs(loss - loss_bf16) / max(abs(loss_bf16), 1e-9) * 100

    # int8 per-site arms: everything / MLP einsums only / attention
    # projections only — the per-site split the qdot/qeinsum site tags
    # enable (ops/fp8.py quant_sites)
    site_arms = {}
    for sites in ("all", "mlp", "attn_qkv,attn_out"):
        arm_strategy = _dc.replace(
            strategy, compute_dtype="int8", quant_sites=sites
        )
        site_arms[sites] = run_arm(
            headline_cfg, arm_strategy, h_tokens, steps
        )
    t_int8, loss_int8 = site_arms["all"]
    int8_vs_bf16_pct = (t_int8 / t_bf16 - 1.0) * 100
    int8_mlp_vs_bf16_pct = (
        site_arms["mlp"][0] / t_bf16 - 1.0
    ) * 100
    int8_attn_vs_bf16_pct = (
        site_arms["attn_qkv,attn_out"][0] / t_bf16 - 1.0
    ) * 100

    # selection: fastest parity-passing candidate (bf16 always passes)
    candidates = [("bfloat16", "all", t_bf16, loss_bf16)] + [
        ("int8", sites, dt, loss)
        for sites, (dt, loss) in site_arms.items()
    ]
    feasible = [
        c for c in candidates
        if parity_pct(c[3]) < LOSS_PARITY_TOL * 100
    ]
    selected_dtype, selected_sites, step_time, headline_loss = min(
        feasible, key=lambda c: c[2]
    )
    loss_parity_pct = (
        parity_pct(headline_loss) if selected_dtype != "bfloat16"
        else parity_pct(loss_int8)
    )
    sel_strategy = _dc.replace(
        strategy, compute_dtype=selected_dtype,
        quant_sites=selected_sites,
    )

    # overlap lever on top of the selected arm: the double-buffered
    # per-layer fsdp gather schedule (parallel/overlap.py). On a
    # fsdp=1 mesh the gather is a no-op and the trace is structurally
    # identical to the plain one (layer_gather_fn bails out), so the
    # arms would only publish run-to-run jitter — skip them and report
    # the delta as None; on fsdp>1 meshes BOTH mechanisms are raced
    # (GSPMD's native all-gather at the double-buffered position vs
    # the decomposed ppermute ring) and the fastest parity-passing one
    # is selected — "manual" winning is what arms the require-ops gate
    # below.
    headline_fsdp = sel_strategy.mesh.fsdp
    overlap_step_delta_pct = None
    if headline_fsdp > 1:
        ovl_arms = {
            mode: run_arm(
                headline_cfg,
                _dc.replace(sel_strategy, overlap_collectives=mode),
                h_tokens, steps,
            )
            for mode in ("xla", "manual")
        }
        ovl_mode = min(ovl_arms, key=lambda k: ovl_arms[k][0])
        t_ovl, loss_ovl = ovl_arms[ovl_mode]
        overlap_step_delta_pct = (t_ovl / step_time - 1.0) * 100
        overlap_selected = (
            t_ovl < step_time
            and parity_pct(loss_ovl) < LOSS_PARITY_TOL * 100
        )
        if overlap_selected:
            sel_strategy = _dc.replace(
                sel_strategy, overlap_collectives=ovl_mode
            )
            step_time, headline_loss = t_ovl, loss_ovl
    tokens_per_sec = h_batch * seq / step_time

    # the kernel profile below must describe the SELECTED arm
    res = build(headline_cfg, sel_strategy)
    state = res.state
    state, m = res.train_step(
        state, {"tokens": h_tokens}, jax.random.key(0)
    )
    _ = float(m["loss"])

    from dlrover_tpu.common import mfu as mfu_mod

    params = sum(x.size for x in jax.tree.leaves(state.params))
    # ONE FLOPs/MFU definition shared with the trainer's live
    # ``train.mfu`` gauge (common/mfu.py), so the offline headline and
    # the live metrics plane cannot drift. Peak defaults to the bf16
    # v5e figure: conservative for the int8 arm, whose dots run on the
    # 2x int8 MXU path.
    model_flops = mfu_mod.transformer_step_flops(
        params, h_batch * seq, n_layers=headline_cfg.n_layers,
        dim=headline_cfg.dim, seq=seq,
    )
    # a CPU run has no peak (mfu.peak_flops is None there): no MFU
    peak = mfu_mod.peak_flops(jax.devices()[0])
    mfu = mfu_mod.mfu(model_flops, step_time, peak) if peak else 0.0

    # online per-kernel attribution (reference xpu_timer's named-kernel
    # Prometheus export): profile a short window on the SELECTED arm,
    # publish the top ops, serve them from the agent's /metrics endpoint
    top_ops, kernel_metrics_served = [], False
    # None = gate not run (remat!=none) or no profiled ops to inspect;
    # True/False only when an op list was actually checked
    remat_none_checkpoint_free = None
    remat_none_checkpoint_detail = ""
    # same contract for the require-ops gate (decomposed-collective pin,
    # armed only with manual overlap on a sharded mesh)
    overlap_require_ops_ok = None
    overlap_require_ops_detail = ""
    prof_dir = tempfile.mkdtemp(prefix="bench_prof_")
    try:
        from dlrover_tpu.agent.monitor import MetricsEndpoint
        from dlrover_tpu.common.constants import ConfigPath
        from dlrover_tpu.trainer.profiler import StepProfiler

        kpath = os.environ.get(
            ConfigPath.ENV_KERNEL_METRICS, ConfigPath.KERNEL_METRICS)
        if os.path.exists(kpath):
            os.unlink(kpath)  # a stale file must not fake the signal
        # the PR-1 forbid-ops gate, ARMED on the headline arm: a
        # remat=none step must profile checkpoint-free (the chunked CE
        # is a custom_vjp now — no intentional jax.checkpoint remains
        # anywhere in the headline trace). With manual overlapped
        # collectives on a sharded mesh the require-ops gate also pins
        # the decomposed collective-permute ring (XLA re-serializing it
        # into one all-gather would silently undo the overlap).
        forbid = (
            ("checkpoint",) if sel_strategy.remat == "none" else ()
        )
        require = (
            ("collective-permute",)
            if (sel_strategy.overlap_collectives == "manual"
                and headline_fsdp > 1)
            else ()
        )
        prof = StepProfiler(prof_dir, start_step=0, num_steps=2,
                            publish_top_ops=True, forbid_ops=forbid,
                            require_ops=require)
        forbid_error = None
        for i in range(2):
            prof.maybe_start(i)
            state, m = res.train_step(
                state, {"tokens": h_tokens}, jax.random.key(500 + i))
            try:
                prof.maybe_stop(i, block_on=m["loss"])
            except AssertionError as err:
                # gate verdicts are published in the JSON rather than
                # aborting the bench mid-emit; only the forbid failure
                # is recorded here (it fires first inside maybe_stop) —
                # the require gate gets its own explicit check below so
                # each failure lands under its own verdict key. HEAD
                # truncation: the "forbidden ops"/"required ops" marker
                # that classifies the failure is at the start, the op
                # list tail is the expendable part
                forbid_error = str(err)[:240]
        if sel_strategy.remat == "none":
            if forbid_error is not None and "forbidden ops" in forbid_error:
                remat_none_checkpoint_free = False
                remat_none_checkpoint_detail = forbid_error
            else:
                try:
                    n_ops = prof.assert_ops_absent(("checkpoint",))
                except AssertionError as err:
                    # reachable when maybe_stop died before its gates
                    # ran (e.g. stats publish threw): still a verdict,
                    # never an abort before the JSON emits
                    remat_none_checkpoint_free = False
                    remat_none_checkpoint_detail = str(err)[:240]
                else:
                    if n_ops:
                        remat_none_checkpoint_free = True
                    else:
                        remat_none_checkpoint_detail = (
                            "no profiled ops available to inspect"
                        )
        if require:
            # checked directly against the finished window: a forbid
            # failure in maybe_stop pre-empts its require check, and a
            # require failure must never masquerade as a checkpoint leak
            try:
                n_ops = prof.assert_ops_present(require)
                if n_ops:
                    overlap_require_ops_ok = True
                else:
                    overlap_require_ops_detail = (
                        "no profiled ops available to inspect"
                    )
            except AssertionError as err:
                overlap_require_ops_ok = False
                overlap_require_ops_detail = str(err)[:240]
        endpoint = MetricsEndpoint(exporter=None, host="127.0.0.1")
        port = endpoint.start()
        try:
            import urllib.request

            body = urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=10
            ).read().decode()
            kernel_metrics_served = "dlrtpu_kernel_self_ms" in body
        finally:
            endpoint.stop()
        import json as _json

        if os.path.exists(kpath):
            with open(kpath) as f:
                top_ops = _json.load(f).get("top_ops", [])[:5]
    except Exception:  # noqa: BLE001 - profiling is best-effort
        pass
    finally:
        shutil.rmtree(prof_dir, ignore_errors=True)

    # ---- optimizer-step attribution: the update timed SEPARATELY
    # from fwd/bwd (opt_step_ms = the headline arm's real optimizer on
    # the headline param tree), plus the fused one-pass lever measured
    # against the per-leaf 8-bit Adam kernel chain on a many-leaf tree
    # (the dispatch-tail scenario the fusion exists for; headline-sized
    # 8-bit state would also need the f32 moment transients in HBM, so
    # the lever is attributed at a size that isolates dispatch
    # overhead, not HBM pressure) ----
    opt_keys = {}
    try:
        from dlrover_tpu.ops.fused_optim import (
            fused_adamw,
            pallas_call_count,
        )
        from dlrover_tpu.optimizers import adam8bit

        def time_opt(opt, tree, nsteps):
            st = jax.jit(opt.init)(tree)
            upd_fn = jax.jit(opt.update)
            u, st = upd_fn(tree, st, tree)  # grads stand-in: same tree
            jax.block_until_ready(jax.tree.leaves(u)[0])
            t0 = time.perf_counter()
            for _ in range(nsteps):
                u, st = upd_fn(tree, st, tree)
            jax.block_until_ready(jax.tree.leaves(u)[0])
            return (time.perf_counter() - t0) / nsteps

        o_steps = 5 if on_tpu else 2
        opt_keys["opt_step_ms"] = round(
            time_opt(optax.adafactor(1e-3), state.params, o_steps)
            * 1e3, 3,
        )
        n_leaves = 64 if on_tpu else 8
        leaf_elems = (1 << 22) if on_tpu else (1 << 10)
        many = {
            f"w{i}": jnp.full((leaf_elems,), 0.01 * (i + 1), jnp.float32)
            for i in range(n_leaves)
        }
        fused8 = fused_adamw(1e-3, bits=8)
        perleaf8 = adam8bit(1e-3)
        t_fused = time_opt(fused8, many, o_steps)
        t_perleaf = time_opt(perleaf8, many, o_steps)
        opt_keys.update({
            "opt_fused_step_ms": round(t_fused * 1e3, 3),
            "opt_adam8bit_step_ms": round(t_perleaf * 1e3, 3),
            "opt_fused_vs_perleaf_pct": round(
                (t_fused / t_perleaf - 1.0) * 100, 2
            ),
            # the bounded-dispatch gate: one pallas_call regardless of
            # leaf count vs the per-leaf kernel chain
            "opt_fused_dispatches": pallas_call_count(
                lambda g, s, p: fused8.update(g, s, p),
                many, fused8.init(many), many,
            ),
            "opt_adam8bit_dispatches": pallas_call_count(
                lambda g, s, p: perleaf8.update(g, s, p),
                many, perleaf8.init(many), many,
            ),
            "opt_attrib_leaves_elems": f"{n_leaves}x{leaf_elems}",
            "fused_optim_selected": bool(t_fused < t_perleaf),
        })
        del many
        gc.collect()
    except Exception as e:  # noqa: BLE001 - attribution is best-effort
        opt_keys["opt_bench_error"] = f"{type(e).__name__}: {e}"[:120]

    # free the headline model before the checkpoint-section compile
    del res, state, m
    gc.collect()

    # device<->host link bandwidth, measured in isolation so the
    # D2H/H2D-dependent numbers below can be read against it.
    probe = jnp.ones((64, 1024, 1024), jnp.float32)  # 256 MB
    jax.block_until_ready(probe)
    t0 = time.perf_counter()
    host_probe = jax.device_get(probe)
    d2h_gbps = probe.nbytes / (time.perf_counter() - t0) / (1 << 30)
    t0 = time.perf_counter()
    back = jax.device_put(host_probe)
    jax.block_until_ready(back)
    _ = float(back.ravel()[0])
    h2d_gbps = probe.nbytes / (time.perf_counter() - t0) / (1 << 30)
    del probe, host_probe, back

    # ---- checkpoint section (nano-350m state for the link-bound legs;
    # the engine-limited number is measured at headline size below via
    # a host-resident state) ----
    res = build(nano_cfg, strategy)
    tokens = jnp.asarray(
        rng.randint(0, nano_cfg.vocab_size, (batch, seq + 1))
    )
    state = res.state
    state, m = res.train_step(state, {"tokens": tokens}, jax.random.key(0))
    _ = float(m["loss"])
    t0 = time.perf_counter()
    for i in range(4):
        state, m = res.train_step(state, {"tokens": tokens}, jax.random.key(i))
    _ = float(m["loss"])
    nano_step_time = (time.perf_counter() - t0) / 4

    ckpt_dir = tempfile.mkdtemp(prefix="bench_ckpt_")
    try:
        # production saver path: start the agent-side factory listener
        # (exactly what tpu-run's elastic agent does) so the engine
        # routes saves through the event queue + agent-hosted saver
        # daemon instead of the standalone in-process fallback.
        from dlrover_tpu.agent.ckpt_saver import AsyncCheckpointSaver

        AsyncCheckpointSaver.start_async_saving_ckpt()
        engine = ReplicatedCheckpointEngine(ckpt_dir)
        saver_path = "in-process" if engine._standalone else "agent"
        snap = jax.jit(lambda s: jax.tree.map(jnp.copy, s))(state)
        host_state = {"params": snap.params, "opt": snap.opt_state,
                      "step": snap.step}
        t0 = time.perf_counter()
        ok = engine.save_to_memory_async(1, host_state)
        ckpt_pause = time.perf_counter() - t0
        assert ok, "async ckpt save was skipped"
        # training continues while shm fills: run a few overlapped steps
        t0 = time.perf_counter()
        overlapped = 0
        while engine._async_thread.is_alive() and overlapped < 50:
            state, m = res.train_step(
                state, {"tokens": tokens}, jax.random.key(100 + overlapped)
            )
            overlapped += 1
        _ = float(m["loss"])
        engine.wait_for_shm_save()
        transfer_s = time.perf_counter() - t0
        state_bytes = sum(
            x.size * x.dtype.itemsize for x in jax.tree.leaves(host_state)
        )
        # transfer_s is the whole drain window, which includes the
        # copier thread BLOCKING on each shard's in-flight D2H
        # transfer. The engine times its two drain legs separately:
        # the fill metric is the shm memcpy leg alone, and the D2H wait
        # is disclosed alongside as ckpt_shm_d2h_wait_s.
        drain_stats = dict(engine.last_save_stats)
        fill_s = drain_stats.get("fill_s", 0.0)
        shm_d2h_wait_s = drain_stats.get("materialize_s", 0.0)
        assert engine.latest_step() == 1

        # restore half of the north star (<10 s from the host-memory
        # path): shm -> host state, disk -> host state, then host -> HBM.
        # restore_shm_s times the HOST-side state materialization under
        # the zero-copy contract (read-only shm-backed arrays, valid
        # until the next save); restore_shm_copy_s is the defensive
        # full-copy variant — now ONE threaded native gather pass out
        # of shm instead of a single-threaded numpy memcpy per leaf.
        # The targeted production restore (trainer.py
        # engine.load(target=...)) is shard-wise and device-transfer-
        # bound — its device leg is what restore_h2d_s measures below.
        t0 = time.perf_counter()
        loaded = engine.load(zero_copy=True)
        restore_shm_s = time.perf_counter() - t0
        assert loaded is not None and loaded, "shm restore empty"
        t0 = time.perf_counter()
        loaded_copy = engine.load()
        restore_shm_copy_s = time.perf_counter() - t0
        assert loaded_copy is not None and loaded_copy
        # target-less load() wraps the state in a {step, state} envelope;
        # unwrap so the re-save and H2D timings see the real state tree
        # (the COPY, not the views: saving views back into the same shm
        # segment would memcpy regions onto themselves)
        restored = (
            loaded_copy["state"] if "state" in loaded_copy else loaded_copy
        )

        # memory saves never persist (that is the flash-ckpt contract);
        # trigger a storage save from the already-host-side state so the
        # disk timing is independent of the device link
        engine.save_to_storage(2, restored)
        persisted = engine.wait_for_persist(2, timeout=300)
        restore_disk_s = -1.0
        restore_disk_read_s = restore_disk_verify_s = -1.0
        if persisted:
            t0 = time.perf_counter()
            from_disk = engine.load_from_storage()
            restore_disk_s = time.perf_counter() - t0
            assert from_disk is not None and from_disk, "disk restore empty"
            # staged breakdown of the eager disk restore: parallel
            # chunked shard reads with the CRC folded into the same
            # pass (read_s/verify_s are summed thread-seconds; wall
            # time is restore_disk_s)
            dstats = dict(engine.last_restore_stats)
            restore_disk_read_s = dstats.get("read_s", -1.0)
            restore_disk_verify_s = dstats.get("verify_s", -1.0)

        # H2D leg, PIPELINED: per-leaf transfers all dispatched before
        # any is waited on, so the puts overlap instead of running one
        # after another
        from dlrover_tpu.trainer.flash_checkpoint.engine import (
            pipelined_device_put,
        )

        t0 = time.perf_counter()
        on_device = pipelined_device_put(restored)
        _ = float(jax.tree.leaves(on_device)[0].ravel()[0])
        restore_h2d_s = time.perf_counter() - t0
        del on_device

        # the ROADMAP's sub-10s-restore headline: the full staged
        # return trip after a preemption — host-side materialization
        # (verified disk read, wall time; shm copy leg when the
        # storage persist was skipped) plus the pipelined H2D leg.
        # The individually-measured legs above stay the breakdown;
        # this is the single number the target is driven against.
        restore_total_s = (
            restore_disk_s if restore_disk_s >= 0 else restore_shm_copy_s
        ) + restore_h2d_s

        # in-process scale event (restart-free elasticity): rebuild the
        # mesh over half the devices and reshard the LIVE train state
        # onto it device-to-device via the generalized pytree reshaper
        # — the wall-clock an elastic scale-in pays instead of a full
        # process restart + recompile + restore. Published as
        # ``reshape_s`` next to the restore keys so the two recovery
        # paths are priced side by side.
        reshape_s = -1.0
        reshape_moved_mb = -1.0
        ndev = len(jax.devices())
        if ndev >= 2:
            from jax.sharding import NamedSharding

            from dlrover_tpu.parallel.mesh import (
                MeshConfig,
                build_mesh,
            )
            from dlrover_tpu.parallel.reshaper import reshape_pytree

            half = jax.devices()[: ndev // 2]
            small_mesh = build_mesh(
                MeshConfig(data=len(half)), devices=half
            )
            target_sh = jax.tree.map(
                lambda sh: NamedSharding(small_mesh, sh.spec),
                res.state_shardings,
                is_leaf=lambda s: isinstance(s, NamedSharding),
            )
            reshaped, reshape_report = reshape_pytree(
                state, target_sh
            )
            _ = float(jax.tree.leaves(reshaped.params)[0].ravel()[0])
            reshape_s = reshape_report.seconds
            reshape_moved_mb = reshape_report.bytes_moved / 1e6
            del reshaped

        # engine-limited save throughput at HEADLINE size: the full
        # engine path (lock, barrier, meta build, shm reserve, chunked
        # double-buffered drain) over a host-resident state the size of
        # the headline model's fp32 train state — no device link in the
        # loop. On a real host the link binds first; the reference's
        # 18 GB in 0.5 s needs ~36 GB/s of drain. The COLD save pays
        # single-core tmpfs page fault-in for the fresh segment; the
        # production cadence (save every 30 s into the same segment)
        # runs at the WARM number, which is the steady-state claim.
        # (The fresh segment is now PREFAULTED across threads at
        # creation — dlrtpu_prefault — so the cold number should sit
        # within ~2x of warm instead of the old 4-5x gap.)
        if on_tpu:
            synth_bytes = int(3.8 * (1 << 30))
        else:
            synth_bytes = 64 << 20
        n_chunks = 16
        chunk = synth_bytes // n_chunks // 4
        synth = {
            f"p{i}": np.full(chunk, float(i + 1), np.float32)
            for i in range(n_chunks)
        }
        synth_total = sum(a.nbytes for a in synth.values())
        t0 = time.perf_counter()
        assert engine.save_to_memory(3, synth), "engine save skipped"
        cold_s = time.perf_counter() - t0
        ckpt_engine_cold_gbps = synth_total / cold_s / (1 << 30)
        # median of 3 warm saves, min/max published alongside: this
        # environment is a 1-core VM with up to 10x memory-bandwidth
        # variance from host steal — the spread makes the neighbor
        # noise visible instead of silently selecting the best sample
        warm_ts = []
        for i in range(3):
            t0 = time.perf_counter()
            assert engine.save_to_memory(4 + i, synth), "save skipped"
            warm_ts.append(time.perf_counter() - t0)
        warm_ts.sort()
        ckpt_engine_save_s_minmax = [warm_ts[0], warm_ts[-1]]
        ckpt_engine_gbps = synth_total / warm_ts[1] / (1 << 30)
        del synth  # load() reads shm; bound peak host memory
        gc.collect()
        # restore at HEADLINE size from the host path (shm): the
        # north-star's <10 s restore leg at the real state size —
        # zero-copy hands back shm-backed views instantly; the
        # defensive full copy pays one memcpy of the state
        t0 = time.perf_counter()
        synth_zc = engine.load(zero_copy=True)
        restore_shm_headline_s = time.perf_counter() - t0
        assert synth_zc, "headline shm restore empty"
        copy_ts = []
        for _ in range(3):  # median-of-3: 1-core VM bandwidth variance
            t0 = time.perf_counter()
            synth_copy = engine.load()
            copy_ts.append(time.perf_counter() - t0)
            assert synth_copy, "headline shm copy-restore empty"
            del synth_copy
            gc.collect()
        copy_ts.sort()
        restore_shm_headline_copy_s = copy_ts[1]
        restore_shm_headline_copy_s_minmax = [copy_ts[0], copy_ts[-1]]
        del synth_zc
        gc.collect()

        # shm scatter-copy stage in isolation: time the exact native
        # copy the engines' _write_shm_locked hot path runs (threaded,
        # GIL-released), on the already-host state — no D2H time mixed
        # in, so the number reflects the at-scale sharded-save stage
        # rather than the device link
        host_leaves = [
            np.ascontiguousarray(x) for x in jax.tree.leaves(restored)
        ]
        parts, off = [], 0
        for a in host_leaves:
            parts.append((off, a))
            off += a.nbytes
        scatter_buf = memoryview(bytearray(off))
        from dlrover_tpu import native as dlrtpu_native

        t0 = time.perf_counter()
        if not dlrtpu_native.scatter_copy(scatter_buf, parts):
            for o, a in parts:  # pure-python fallback, same as engine
                scatter_buf[o:o + a.nbytes] = (
                    a.reshape(-1).view(np.uint8).tobytes()
                )
        shm_scatter_s = time.perf_counter() - t0
        shm_scatter_gbps = off / shm_scatter_s / (1 << 30)
        del scatter_buf, host_leaves, restored
        engine.close()
    finally:
        shutil.rmtree(ckpt_dir, ignore_errors=True)

    ckpt_interval = 30.0  # reference production cadence (flash_checkpoint.md)
    goodput = ckpt_interval / (ckpt_interval + ckpt_pause)
    # the fill leg only (see the METRIC FIX note above); the old
    # whole-window division is kept as ckpt_background_transfer_s
    shm_gbps = (
        state_bytes / fill_s / (1 << 30) if fill_s > 0 else -1.0
    )

    # schedule/precision overhead arms (nano-350m, relative to its own
    # bf16 step): 1F1B microbatched loss and the (emulated) fp8 path
    def _step_time_for(cfg, strat, nsteps):
        dt, _ = run_arm(cfg, strat, tokens, nsteps)
        return dt

    del state, snap, host_state, loaded, loaded_copy, res, m
    gc.collect()

    sched_steps = 8 if on_tpu else 2
    t_1f1b = _step_time_for(
        _dc.replace(nano_cfg, pipe_schedule="1f1b", pipe_microbatches=4),
        strategy, sched_steps,
    )
    fp8_strategy = _dc.replace(strategy, compute_dtype="fp8")
    t_fp8 = _step_time_for(nano_cfg, fp8_strategy, sched_steps)
    overhead_1f1b_pct = (t_1f1b / nano_step_time - 1.0) * 100
    fp8_vs_bf16_pct = (t_fp8 / nano_step_time - 1.0) * 100

    try:
        sparse = _sparse_bench(on_tpu)
    except Exception as e:  # noqa: BLE001 - best-effort micro-bench
        sparse = {"sparse_bench_error": f"{type(e).__name__}: {e}"[:120]}

    # control-plane latency surface (pure CPU/socket work, backend-
    # independent): master_rpc_p99_ms / joins_per_sec baseline
    try:
        control_plane = _control_plane_bench()
    except Exception as e:  # noqa: BLE001 - best-effort micro-bench
        control_plane = {
            "control_plane_error": f"{type(e).__name__}: {e}"[:120]
        }

    # deep-profiling plane cost surface: steady-state sampler overhead
    # (<2% contract) + the deep-capture round trip
    try:
        profiling_bench = _profiling_bench()
    except Exception as e:  # noqa: BLE001 - best-effort micro-bench
        profiling_bench = {
            "profiling_bench_error": f"{type(e).__name__}: {e}"[:120]
        }

    from dlrover_tpu.common.arena import get_arena

    arena_stats = get_arena().stats()

    print(json.dumps({
        "metric": "training_goodput_with_flash_ckpt",
        "value": round(goodput * 100, 3),
        "unit": "%",
        "vs_baseline": round(goodput / 0.95, 4),
        "detail": {
            "headline_arm": headline_arm,
            "model_params_m": round(params / 1e6, 1),
            "tokens_per_sec": round(tokens_per_sec, 1),
            "step_time_ms": round(step_time * 1e3, 2),
            # vs bf16 peak (197 TFLOP/s): conservative when int8 is
            # selected (its dots run the 2x int8 MXU path)
            "mfu_pct": round(mfu * 100, 2),
            # measured dtype selection on the HEADLINE model, gated on
            # loss parity (engine.py StrategySearchEngine._pick_best) —
            # now PER-SITE: "all" / "mlp" / "attn_qkv,attn_out" arms
            # race and the fastest parity-passing one wins
            "selected_compute_dtype": selected_dtype,
            "selected_quant_sites": selected_sites,
            "int8_vs_bf16_step_pct": round(int8_vs_bf16_pct, 2),
            "int8_mlp_vs_bf16_step_pct": round(int8_mlp_vs_bf16_pct, 2),
            # the attention-projection lever in isolation: QKV/out
            # einsums int8, MLP bf16, vs the all-bf16 step
            "int8_attn_vs_bf16_step_pct": round(
                int8_attn_vs_bf16_pct, 2
            ),
            "int8_loss_parity_pct": round(loss_parity_pct, 3),
            # collective-overlap lever: selected arm with the
            # double-buffered per-layer fsdp gather scan, on vs off.
            # null = arm skipped because the headline mesh is fsdp=1
            # (the gather is a no-op there — the win needs a sharded
            # mesh)
            "overlap_step_delta_pct": (
                round(overlap_step_delta_pct, 2)
                if overlap_step_delta_pct is not None else None
            ),
            "overlap_mode_selected": sel_strategy.overlap_collectives,
            "headline_loss": round(headline_loss, 4),
            **opt_keys,
            "ckpt_blocking_pause_s": round(ckpt_pause, 4),
            "ckpt_state_model": "nano-350m (pause is dispatch-side and "
                                "size-independent)",
            "ckpt_state_gb": round(state_bytes / (1 << 30), 3),
            "ckpt_background_transfer_s": round(transfer_s, 2),
            "ckpt_overlapped_train_steps": overlapped,
            # the shm MEMCPY leg of the drain only (metric fixed: the
            # old value divided state bytes by the whole drain window
            # and so reported the device link); the D2H wait the copier
            # thread spends blocked on the link is disclosed separately
            "ckpt_shm_fill_gbps": round(shm_gbps, 3),
            "ckpt_shm_d2h_wait_s": round(shm_d2h_wait_s, 3),
            "ckpt_shm_scatter_gbps": round(shm_scatter_gbps, 2),
            # full engine path over a host-resident headline-sized
            # state: engine-limited, vs device_link_* = link ceiling.
            # warm = steady-state (segment reused every save); cold
            # pays one-time single-core tmpfs fault-in of a new segment.
            # gbps is the MEDIAN of 3 warm saves; the _minmax spread
            # shows this 1-core VM's neighbor-steal variance
            "ckpt_engine_gbps": round(ckpt_engine_gbps, 2),
            "ckpt_engine_save_s_minmax": [
                round(t, 3) for t in ckpt_engine_save_s_minmax
            ],
            "ckpt_engine_cold_gbps": round(ckpt_engine_cold_gbps, 2),
            "ckpt_engine_synth_gb": round(synth_total / (1 << 30), 2),
            "restore_shm_s": round(restore_shm_s, 3),
            "restore_shm_copy_s": round(restore_shm_copy_s, 3),
            # host-path restore at headline state size (<10 s north
            # star); copy_s is the median of 3 with min/max spread
            "restore_shm_headline_s": round(restore_shm_headline_s, 3),
            "restore_shm_headline_copy_s": round(
                restore_shm_headline_copy_s, 3
            ),
            "restore_shm_headline_copy_s_minmax": [
                round(t, 3) for t in restore_shm_headline_copy_s_minmax
            ],
            "restore_disk_s": round(restore_disk_s, 3),
            # staged restore breakdown (tentpole: the return trip is a
            # pipeline now) — disk reads are chunk-parallel with the
            # CRC folded into the read pass (read/verify are summed
            # thread-seconds), and the H2D leg dispatches every leaf
            # before waiting on any
            "restore_disk_read_s": round(restore_disk_read_s, 3),
            "restore_disk_verify_s": round(restore_disk_verify_s, 3),
            "restore_h2d_s": round(restore_h2d_s, 3),
            "restore_h2d_mode": "pipelined-per-leaf",
            # full preemption-restore wall clock (host leg + H2D): the
            # <10 s north-star's single headline number
            "restore_total_s": round(restore_total_s, 3),
            # in-process scale event (mesh rebuild + batched
            # device-to-device reshard of the live train state onto
            # half the devices) — what a restart-free membership
            # change costs instead of teardown + recompile + restore
            "reshape_s": round(reshape_s, 3),
            "reshape_moved_mb": round(reshape_moved_mb, 1),
            # host-arena reuse for the deep-verify CRC staging buffers
            # (the COLD-save fix is the threaded shm prefault, not the
            # arena — see ckpt_engine_cold_gbps above)
            "ckpt_arena_hits": arena_stats["hits"],
            "ckpt_arena_misses": arena_stats["misses"],
            "ckpt_saver_path": saver_path,
            # measured host<->device link: restore_h2d_s /
            # ckpt_background_transfer_s scale with these
            "device_link_d2h_gbps": round(d2h_gbps, 3),
            "device_link_h2d_gbps": round(h2d_gbps, 3),
            "nano_step_time_ms": round(nano_step_time * 1e3, 2),
            "sched_1f1b_pipe1_overhead_pct": round(overhead_1f1b_pct, 2),
            "fp8_vs_bf16_step_pct": round(fp8_vs_bf16_pct, 2),
            "kernel_metrics_served": kernel_metrics_served,
            "top_ops": top_ops,
            # True = the profiled remat=none window was inspected and
            # contained no checkpoint op; False = inspected and leaked
            # (_detail lists the survivors — the fused CE's intentional
            # jax.checkpoint is the one expected entry at ce_chunks>1);
            # null = gate not run (remat!=none, or no profiled ops)
            "remat_none_checkpoint_free": remat_none_checkpoint_free,
            "remat_none_checkpoint_detail": remat_none_checkpoint_detail,
            # require-ops gate (manual overlap only): True = the
            # decomposed collective-permute ring survived into the
            # profiled window; False = XLA re-serialized it (_detail
            # has the missing ops); null = gate not armed (overlap !=
            # manual or fsdp=1) or no profiled ops to inspect
            "overlap_require_ops_ok": overlap_require_ops_ok,
            "overlap_require_ops_detail": overlap_require_ops_detail,
            **sparse,
            **control_plane,
            **profiling_bench,
            "backend": jax.default_backend(),
        },
    }))


if __name__ == "__main__":
    os.environ.setdefault("JAX_TRACEBACK_FILTERING", "off")
    main()
