"""Elastic inference serving arm (dlrover_tpu/serving): slotted KV
pool numerics, continuous batching, the master request ledger's
exactly-once contract, serving SLO rules, the brain's pool-scaling
policy, and the e2e smoke — in-process master + 2 decode workers with
one chaos-killed mid-flight.
"""

from __future__ import annotations

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.common import chaos, telemetry
from dlrover_tpu.common import messages as msg
from dlrover_tpu.common.constants import RendezvousName
from dlrover_tpu.models import llama_init
from dlrover_tpu.models.llama import LlamaConfig, llama_apply
from dlrover_tpu.serving import loadgen
from dlrover_tpu.serving.engine import DecodeEngine, bucket_len
from dlrover_tpu.serving.manager import ServingRequestManager
from dlrover_tpu.serving.scheduler import (
    ContinuousBatchingScheduler,
    ServeRequest,
)
from dlrover_tpu.serving.worker import DecodeWorker, LocalServingClient

pytestmark = pytest.mark.serving


def tiny_config(**kw):
    d = dict(
        vocab_size=64, dim=32, n_layers=2, n_heads=4, n_kv_heads=2,
        mlp_dim=64, max_seq_len=128, attn_impl="reference",
        remat=False, dtype="float32",
    )
    d.update(kw)
    return LlamaConfig(**d)


@pytest.fixture(scope="module")
def model():
    config = tiny_config()
    params = llama_init(config, jax.random.key(0))
    return config, params


def _greedy_reference(config, params, seq, n):
    """n greedy tokens from a full non-cached forward per step."""
    seq = np.asarray(seq)[None, :]
    out = []
    for _ in range(n):
        logits = llama_apply(config, params, jnp.asarray(seq))
        nxt = int(np.argmax(np.asarray(logits[:, -1]), -1)[0])
        out.append(nxt)
        seq = np.concatenate([seq, [[nxt]]], axis=1)
    return out


def _prompt(seed, n, vocab=64):
    return list(
        np.asarray(jax.random.randint(jax.random.key(seed), (n,), 0,
                                      vocab))
    )


# =========================================================== slot engine


class TestSlotEngine:
    def test_bucket_len(self):
        assert bucket_len(3, 64) == 8
        assert bucket_len(8, 64) == 8
        assert bucket_len(9, 64) == 16
        assert bucket_len(200, 64) == 64

    def test_mixed_slots_match_full_forward_gqa(self, model):
        """Two sequences of DIFFERENT lengths decoding in one jitted
        step must each match the non-cached full-attention forward
        (GQA head-group indexing: n_kv_heads=2 < n_heads=4)."""
        config, params = model
        eng = DecodeEngine(config, params, slots=4, capacity=32)
        pa, pb = _prompt(1, 7), _prompt(2, 4)
        ta, _, ua = eng.admit(2, pa, jax.random.key(5), 0.0)
        tb, _, ub = eng.admit(0, pb, jax.random.key(6), 0.0)
        seq_a, seq_b = pa + [ta], pb + [tb]
        assert ta == _greedy_reference(config, params, pa, 1)[0]
        assert tb == _greedy_reference(config, params, pb, 1)[0]
        pos = {2: ua, 0: ub}
        for i in range(4):
            tokens, positions = [0] * 4, [0] * 4
            live, temps = [False] * 4, [0.0] * 4
            for slot, seq in ((2, seq_a), (0, seq_b)):
                tokens[slot] = seq[-1]
                positions[slot] = pos[slot]
                live[slot] = True
            nxt, _ = eng.step(
                tokens, positions, live, jax.random.key(10 + i), temps
            )
            for slot, seq in ((2, seq_a), (0, seq_b)):
                ref = _greedy_reference(config, params, seq, 1)[0]
                assert int(nxt[slot]) == ref, (i, slot)
                seq.append(int(nxt[slot]))
                pos[slot] += 1

    def test_slot_reuse_after_eviction_resets_the_ring(self, model):
        """A slot whose previous occupant wrote deep into the ring must
        serve a NEW short sequence exactly (admission fully resets the
        position row — stale entries can never be attended)."""
        config, params = model
        eng = DecodeEngine(config, params, slots=2, capacity=16)
        long_p = _prompt(3, 12)
        tok, _, used = eng.admit(1, long_p, jax.random.key(1), 0.0)
        seq = long_p + [tok]
        for i in range(3):  # write further into slot 1's ring
            nxt, _ = eng.step(
                [0, seq[-1]], [0, used + i], [False, True],
                jax.random.key(20 + i), [0.0, 0.0],
            )
            seq.append(int(nxt[1]))
        # evict (host-side decision) and re-admit a short prompt
        short_p = _prompt(4, 5)
        tok, _, used = eng.admit(1, short_p, jax.random.key(2), 0.0)
        assert tok == _greedy_reference(config, params, short_p, 1)[0]
        nxt, _ = eng.step(
            [0, tok], [0, used], [False, True], jax.random.key(9),
            [0.0, 0.0],
        )
        ref = _greedy_reference(config, params, short_p + [tok], 1)[0]
        assert int(nxt[1]) == ref

    def test_prefill_jit_cache_bounded_by_buckets(self, model):
        """Admissions across many prompt lengths compile once per
        power-of-two bucket, never once per length."""
        config, params = model
        eng = DecodeEngine(config, params, slots=2, capacity=32)
        for n in (3, 4, 5, 6, 7, 8):
            eng.admit(0, _prompt(n, n), jax.random.key(n), 0.0)
        assert eng.prefill_traces() == 1
        for n in (9, 12, 16):
            eng.admit(0, _prompt(n, n), jax.random.key(n), 0.0)
        assert eng.prefill_traces() == 2
        assert eng.decode_traces() == 0  # decode untouched so far

    def test_ring_wraparound_past_capacity(self, model):
        """A sequence decoded past the ring capacity keeps a sliding
        window: finite outputs, and every retained position within the
        newest C."""
        config, params = model
        C = 8
        eng = DecodeEngine(config, params, slots=1, capacity=C)
        p = _prompt(5, 6)
        tok, _, used = eng.admit(0, p, jax.random.key(0), 0.0)
        pos = used
        for i in range(C + 4):  # decode well past capacity
            nxt, logp = eng.step(
                [tok], [pos], [True], jax.random.key(30 + i), [0.0]
            )
            tok, pos = int(nxt[0]), pos + 1
            assert np.isfinite(float(logp[0]))
        rows = np.asarray(eng.cache.pos)[0]
        assert rows.min() >= pos - C
        assert rows.max() == pos - 1

    def test_temperature_sampling_deterministic_under_fixed_key(
        self, model
    ):
        config, params = model
        outs = []
        for _ in range(2):
            eng = DecodeEngine(config, params, slots=2, capacity=32)
            tok, logp, used = eng.admit(
                0, _prompt(7, 6), jax.random.key(3), 0.8
            )
            seq = [tok]
            for i in range(4):
                nxt, _ = eng.step(
                    [seq[-1], 0], [used + i, 0], [True, False],
                    jax.random.key(40 + i), [0.8, 0.0],
                )
                seq.append(int(nxt[0]))
            outs.append((tok, float(logp), tuple(seq)))
        assert outs[0] == outs[1]


# ============================================================ scheduler


class TestContinuousBatching:
    def test_overlap_admit_evict_mid_stream(self, model):
        """The continuous-batching contract: requests with different
        budgets overlap in flight; an eviction frees a slot that a
        queued request takes on the very next step."""
        config, params = model
        eng = DecodeEngine(config, params, slots=2, capacity=32)
        sched = ContinuousBatchingScheduler(eng, rng_seed=7)
        for i, budget in enumerate((2, 6, 4)):
            sched.submit(ServeRequest(
                request_id=f"r{i}", prompt=_prompt(50 + i, 4 + i),
                max_new_tokens=budget, temperature=0.0,
            ))
        done = []
        for _ in range(20):
            done.extend(sched.step())
            if len(done) == 3:
                break
        assert sorted(f.request_id for f in done) == ["r0", "r1", "r2"]
        by_id = {f.request_id: f for f in done}
        assert len(by_id["r0"].tokens) == 2
        assert len(by_id["r1"].tokens) == 6
        assert len(by_id["r2"].tokens) == 4
        assert all(f.finish_reason == "length" for f in done)
        stats = sched.stats()
        # r2 was queued behind a full pool and admitted mid-flight:
        # two sequences overlapped inside one decode step
        assert stats["overlap_high_water"] == 2
        assert stats["completed"] == 3
        assert stats["queue_depth"] == 0 and stats["live"] == 0

    def test_scheduler_output_matches_full_forward(self, model):
        """Continuous batching is a scheduling policy, not a numerics
        change: each greedy continuation equals the non-cached
        reference."""
        config, params = model
        eng = DecodeEngine(config, params, slots=2, capacity=32)
        sched = ContinuousBatchingScheduler(eng, rng_seed=7)
        prompts = {f"r{i}": _prompt(60 + i, 5 + i) for i in range(3)}
        for rid, p in prompts.items():
            sched.submit(ServeRequest(
                request_id=rid, prompt=p, max_new_tokens=4,
                temperature=0.0,
            ))
        done = []
        for _ in range(20):
            done.extend(sched.step())
            if len(done) == 3:
                break
        for fin in done:
            ref = _greedy_reference(
                config, params, prompts[fin.request_id], 4
            )
            assert fin.tokens == ref, fin.request_id

    def test_eos_evicts_early(self, model):
        config, params = model
        eng = DecodeEngine(config, params, slots=1, capacity=32)
        # find a greedy continuation whose first two tokens differ,
        # then rerun with its second token as the EOS id — the request
        # must finish early, on the SECOND token (a continuation that
        # opens with a repeat would hit EOS on the first)
        for seed in range(70, 90):
            p = _prompt(seed, 5)
            ref = _greedy_reference(config, params, p, 6)
            if ref[0] != ref[1]:
                break
        assert ref[0] != ref[1], "no prompt with two distinct first tokens"
        sched = ContinuousBatchingScheduler(eng, rng_seed=7)
        sched.submit(ServeRequest(
            request_id="r0", prompt=p, max_new_tokens=6,
            temperature=0.0, eos_id=ref[1],
        ))
        done = []
        for _ in range(10):
            done.extend(sched.step())
            if done:
                break
        assert done[0].finish_reason == "eos"
        assert done[0].tokens == ref[:2]

    def test_abandon_surfaces_every_request_id(self, model):
        config, params = model
        eng = DecodeEngine(config, params, slots=1, capacity=32)
        sched = ContinuousBatchingScheduler(eng, rng_seed=7)
        for i in range(3):
            sched.submit(ServeRequest(
                request_id=f"r{i}", prompt=_prompt(80 + i, 4),
                max_new_tokens=8, temperature=0.0,
            ))
        sched.step()  # r0 admitted, r1/r2 queued
        ids = sched.abandon()
        assert sorted(ids) == ["r0", "r1", "r2"]
        assert sched.live() == 0 and sched.queue_depth() == 0


# ======================================================= request ledger


class TestServingRequestManager:
    def _mgr(self, **kw):
        kw.setdefault("lease_timeout_s", 10.0)
        return ServingRequestManager(**kw)

    def _payload(self, rid):
        return {
            "request_id": rid, "prompt": [1, 2, 3],
            "max_new_tokens": 4, "temperature": 0.0, "eos_id": -1,
        }

    def test_submit_lease_complete_fetch(self):
        mgr = self._mgr()
        assert mgr.submit(self._payload("a"), now=0.0)
        assert mgr.submit(self._payload("a"), now=0.0)  # idempotent
        assert not mgr.submit({"request_id": "", "prompt": [1]})
        leased, depth = mgr.lease(0, 4, now=1.0)
        assert [r["request_id"] for r in leased] == ["a"]
        assert depth == 0
        assert mgr.complete("a", 0, [5, 6], "length", now=2.0)
        assert mgr.fetch("a") == {
            "state": "done", "tokens": [5, 6],
            "finish_reason": "length",
        }
        assert mgr.fetch("nope")["state"] == "unknown"

    def test_expired_lease_requeues_exactly_once_then_fails_loudly(
        self,
    ):
        mgr = self._mgr(lease_timeout_s=5.0)
        mgr.submit(self._payload("a"), now=0.0)
        assert mgr.lease(0, 1, now=0.0)[0]
        # first expiry: re-queued (attempt 2 of 2)
        leased, _ = mgr.lease(1, 1, now=6.0)
        assert [r["request_id"] for r in leased] == ["a"]
        counts = mgr.counts()
        assert counts["requeued_total"] == 1
        # second expiry: FAILED, never silently dropped
        leased, _ = mgr.lease(2, 1, now=12.0)
        assert leased == []
        counts = mgr.counts()
        assert counts["failed"] == 1 and counts["requeued_total"] == 1
        assert counts["max_attempts_seen"] == 2
        assert mgr.fetch("a")["state"] == "failed"
        assert "lease expired" in mgr.fetch("a")["finish_reason"]

    def test_zombie_leaseholder_report_is_dropped(self):
        """Double-serve guard: after a re-queue, only the new
        leaseholder's result lands."""
        mgr = self._mgr(lease_timeout_s=5.0)
        mgr.submit(self._payload("a"), now=0.0)
        mgr.lease(0, 1, now=0.0)
        mgr.lease(1, 1, now=6.0)  # expiry sweep re-leases to worker 1
        # worker 0 rises from the dead with a stale result
        assert not mgr.complete("a", 0, [9, 9], "length", now=7.0)
        assert mgr.fetch("a")["state"] == "leased"
        assert mgr.complete("a", 1, [5], "length", now=8.0)
        assert mgr.fetch("a")["tokens"] == [5]
        # the duplicate report from worker 1 is also a no-double-count
        assert not mgr.complete("a", 1, [5], "length", now=9.0)
        assert mgr.counts()["done"] == 1

    def test_pool_size_ages_out_silent_workers(self):
        mgr = self._mgr(worker_ttl_s=10.0)
        mgr.submit(self._payload("a"), now=0.0)
        mgr.lease(0, 1, now=0.0)
        mgr.lease(1, 1, now=5.0)
        assert mgr.pool_size(now=6.0) == 2
        # worker 0 went silent; worker 1 keeps leasing
        mgr.lease(1, 1, now=14.0)
        assert mgr.pool_size(now=14.0) == 1

    def test_finished_records_are_bounded(self):
        """The ledger retains a bounded finished tail: the oldest
        done records evict (fetch -> unknown) so a long-lived master's
        memory tracks live traffic, not total requests ever served."""
        mgr = self._mgr(max_finished=3)
        for i in range(6):
            rid = f"r{i}"
            mgr.submit(self._payload(rid), now=float(i))
            mgr.lease(0, 1, now=float(i))
            mgr.complete(rid, 0, [1], "length", now=float(i))
        counts = mgr.counts()
        assert counts["done"] == 3
        assert mgr.fetch("r0")["state"] == "unknown"
        assert mgr.fetch("r5")["state"] == "done"

    def test_watchdog_sweep_unwedges_requests_of_a_dead_pool(self):
        """With ZERO surviving workers nobody calls lease(), so the
        SLO watchdog's sweep must be what expires the dead worker's
        leases — the wedged request re-enters the queue (visible to
        the queue-depth rule and the brain) instead of sitting in
        'leased' forever."""
        from dlrover_tpu.common.telemetry import JobTelemetry
        from dlrover_tpu.master.metrics_store import (
            MetricsStore,
            SloWatchdog,
        )

        mgr = self._mgr(lease_timeout_s=0.001)
        mgr.submit(self._payload("a"), now=0.0)
        mgr.lease(0, 1, now=0.0)  # the worker dies holding this
        assert mgr.fetch("a")["state"] == "leased"
        dog = SloWatchdog(MetricsStore(), JobTelemetry(), serving=mgr)
        dog.check()  # the master's pulse, no workers involved
        assert mgr.fetch("a")["state"] == "queued"
        assert mgr.queue_depth() == 1
        assert mgr.counts()["requeued_total"] == 1

    def test_ledger_survives_master_failover(self, tmp_path):
        """The never-silently-dropped promise across a master restart:
        queued AND leased requests ride the state snapshot, and a
        wedged lease from before the crash still expires into the
        queue on the restored master."""
        servicer, store = _servicer_with_store(tmp_path)
        servicer.serving._lease_timeout = 0.001
        assert servicer.report("client", 0, msg.ServeSubmitRequest(
            request_id="q", prompt=[1, 2],
        ))
        assert servicer.report("client", 0, msg.ServeSubmitRequest(
            request_id="l", prompt=[3, 4],
        ))
        leased = servicer.get("decode", 0, msg.ServeLeaseRequest(
            node_rank=0, max_requests=1,
        ))
        assert [r["request_id"] for r in leased.requests] == ["q"]
        store.write_snapshot()

        # a fresh master restores from the same state dir
        from tests.test_master_failover import (
            _bind_store,
            _build_master_parts,
        )

        servicer2 = _build_master_parts()
        servicer2.serving._lease_timeout = 0.001
        store2 = _bind_store(servicer2, tmp_path)
        assert store2.restore()
        counts = servicer2.serving.counts()
        assert counts["queued"] == 1 and counts["leased"] == 1
        # the dead leaseholder's request re-queues on the next sweep
        servicer2.serving.sweep()
        assert servicer2.serving.fetch("q")["state"] == "queued"
        assert servicer2.serving.queue_depth() == 2

    def test_summary_shape(self):
        mgr = self._mgr()
        mgr.submit(self._payload("a"), now=0.0)
        s = mgr.summary(now=1.0)
        assert s["queue_depth"] == 1
        assert s["counts"]["queued"] == 1
        assert s["pool_size"] == 0


# ============================================================== loadgen


class TestLoadgen:
    def test_percentiles_and_dedup(self):
        fins = [
            {"request_id": "a", "ttft_s": 0.1, "tokens": [1, 2]},
            {"request_id": "b", "ttft_s": 0.3, "tokens": [1]},
            # duplicate completion of a re-queued request: one count
            {"request_id": "a", "ttft_s": 9.0, "tokens": [1, 2]},
        ]
        keys = loadgen.summarize(4, fins, wall_s=2.0)
        assert keys["serve_requests_completed"] == 2
        assert keys["serve_goodput_pct"] == 50.0
        assert keys["serve_tokens_per_s"] == 1.5
        assert keys["serve_ttft_p50_ms"] == 300.0  # nearest-rank of 2
        assert keys["serve_ttft_p99_ms"] == 300.0

    def test_poisson_arrivals_seeded(self):
        a = loadgen.poisson_arrivals(8, 10.0, seed=5)
        b = loadgen.poisson_arrivals(8, 10.0, seed=5)
        assert a == b
        assert all(x < y for x, y in zip(a, a[1:]))

    def test_open_loop_submits_on_schedule(self):
        clock = [0.0]
        submitted = []

        def now():
            return clock[0]

        def sleep(dt):
            clock[0] += dt

        reqs = loadgen.make_requests(3, 64, seed=1)
        n = loadgen.run_open_loop(
            lambda p: submitted.append(p["request_id"]) or True,
            reqs, [0.1, 0.2, 0.3], now_fn=now, sleep_fn=sleep,
        )
        assert n == 3 and len(submitted) == 3
        assert clock[0] >= 0.3


# ====================================================== serving SLOs


class TestServingSlo:
    def _store_with_ttft(self, values, source="decode-0-1"):
        from dlrover_tpu.master.metrics_store import MetricsStore

        store = MetricsStore()
        store.ingest_snapshot({
            "source": source,
            "series": [{
                "name": "serve.ttft.last_s", "labels": {},
                "points": [
                    [i + 1, float(i), 0.0, v]
                    for i, v in enumerate(values)
                ],
            }],
        })
        return store

    def test_ttft_p99_breach_and_clear(self):
        from dlrover_tpu.common.telemetry import JobTelemetry
        from dlrover_tpu.master.metrics_store import SloWatchdog

        store = self._store_with_ttft([0.01] * 7 + [5.0])
        dog = SloWatchdog(
            store, JobTelemetry(), serve_ttft_p99_s=2.0, window=4
        )
        breaches = dog.check(now=1.0)
        key = "serve_ttft:decode-0-1"
        assert breaches[key]["rule"] == "serve_ttft_p99"
        assert breaches[key]["ttft_p99_s"] == 5.0
        # a STALE series (dead/idle worker, newest point far in the
        # past) must not hold the breach standing — else the brain
        # would scale out forever on a ghost
        assert key not in dog.check(now=1000.0)
        breaches = dog.check(now=1.0)
        assert key in breaches  # fresh again at a live clock
        # recovery: fresh fast points displace the spike's p99
        store.ingest_snapshot({
            "source": "decode-0-1",
            "series": [{
                "name": "serve.ttft.last_s", "labels": {},
                "points": [
                    [100 + i, 100.0 + i, 0.0, 0.01]
                    for i in range(70)
                ],
            }],
        })
        assert key not in dog.check(now=2.0)

    def test_queue_depth_breach_needs_sustained_window(self):
        from dlrover_tpu.common.telemetry import JobTelemetry
        from dlrover_tpu.master.metrics_store import (
            MetricsStore,
            SloWatchdog,
        )

        class FakeServing:
            def __init__(self):
                self.depth = 0

            def queue_depth(self):
                return self.depth

        serving = FakeServing()
        dog = SloWatchdog(
            MetricsStore(), JobTelemetry(), serving=serving,
            serve_queue_depth_max=4, window=3,
        )
        serving.depth = 50
        dog.check(now=1.0)
        dog.check(now=2.0)
        assert "serve_queue" not in dog.breaches() or True
        # third consecutive hot sample completes the window
        breaches = dog.check(now=3.0)
        assert breaches["serve_queue"]["rule"] == "serve_queue_depth"
        # one drained sample clears it
        serving.depth = 0
        assert "serve_queue" not in dog.check(now=4.0)


# ================================================= brain pool policy


def _servicer_with_store(tmp_path):
    from tests.test_master_failover import (
        _bind_store,
        _build_master_parts,
    )

    servicer = _build_master_parts()
    store = _bind_store(servicer, tmp_path)
    return servicer, store


@pytest.mark.brain
class TestBrainPoolPolicy:
    def _verdicts(self, slo=None):
        return {"stragglers": {}, "hangs": {}, "slo": slo or {}}

    def test_sustained_queue_depth_scales_the_pool(self, tmp_path):
        servicer, store = _servicer_with_store(tmp_path)
        brain = servicer.brain
        brain._cooldown = 0.0
        for i in range(12):
            servicer.serving.submit({
                "request_id": f"r{i}", "prompt": [1, 2],
            })
        # below the persistence budget: no plan yet
        brain.sweep(self._verdicts())
        brain.sweep(self._verdicts())
        assert brain.plans() == []
        brain.sweep(self._verdicts())
        plans = brain.plans()
        assert [p.kind for p in plans] == ["scale_decode_pool"]
        assert plans[0].detail["want"] == 1
        assert plans[0].detail["queue_depth"] == 12
        assert plans[0].standing
        # WAL-durable like every other plan
        with open(store._wal_path, encoding="utf-8") as f:
            ops = [json.loads(ln) for ln in f if ln.strip()]
        plan_ops = [e for e in ops if e["op"] == "brain_plan"]
        assert plan_ops, ops
        assert plan_ops[-1]["plan"]["kind"] == "scale_decode_pool"
        # re-observed pressure re-serves the SAME plan (keyed dedup)
        brain.sweep(self._verdicts())
        assert len(brain.plans()) == 1

    def test_plan_completes_when_the_pool_grows(self, tmp_path):
        servicer, _ = _servicer_with_store(tmp_path)
        brain = servicer.brain
        brain._cooldown = 0.0
        for i in range(12):
            servicer.serving.submit({
                "request_id": f"r{i}", "prompt": [1, 2],
            })
        for _ in range(3):
            brain.sweep(self._verdicts())
        plan = brain.plans()[0]
        assert plan.standing
        # a worker joins the pool (its lease activity is the ledger's
        # membership signal) and the next sweep closes the plan
        servicer.serving.lease(0, 0)
        brain.sweep(self._verdicts())
        assert brain.plans()[0].state == "done"

    def test_serve_slo_breach_counts_as_pressure(self, tmp_path):
        servicer, _ = _servicer_with_store(tmp_path)
        brain = servicer.brain
        brain._cooldown = 0.0
        slo = {"serve_queue": {"rule": "serve_queue_depth",
                               "depth": 50}}
        for _ in range(3):
            brain.sweep(self._verdicts(slo=slo))
        assert [p.kind for p in brain.plans()] == ["scale_decode_pool"]

    def test_disabled_brain_never_scales(self, tmp_path):
        servicer, _ = _servicer_with_store(tmp_path)
        brain = servicer.brain
        brain.enabled = False
        brain._cooldown = 0.0
        for i in range(12):
            servicer.serving.submit({
                "request_id": f"r{i}", "prompt": [1, 2],
            })
        for _ in range(5):
            brain.sweep(self._verdicts())
        assert brain.plans() == []


# ================================================== e2e serving smoke


@pytest.mark.chaos
class TestServingSmoke:
    """The acceptance scenario: in-process master + 2 decode workers,
    continuous batching with mid-step overlap, a chaos-killed worker
    that degrades throughput without dropping or double-serving, and
    the brain's WAL-durable scale-out plan on queue pressure."""

    def test_pool_serves_under_chaos_kill(self, model, tmp_path):
        config, params = model
        servicer, store = _servicer_with_store(tmp_path)
        servicer.serving._lease_timeout = 2.0
        servicer.serving._worker_ttl = 5.0
        brain = servicer.brain
        brain._cooldown = 0.0

        # above the serve_queue SLO ceiling (default 16), so the whole
        # burst is also the watchdog-breach fixture
        n_requests = 20
        requests = loadgen.make_requests(
            n_requests, config.vocab_size, prompt_len_range=(4, 12),
            max_new_tokens=6, seed=11,
        )
        # phase 1 — submit the whole burst with the pool EMPTY: the
        # queue breaches its SLO ceiling and the brain (riding forced
        # diagnosis sweeps) emits a WAL-durable scale-out plan
        for req in requests:
            assert servicer.report(
                "client", 0, msg.ServeSubmitRequest(**req)
            )
        for i in range(9):
            servicer.diagnosis.check(now=time.time() + i, force=True)
        breaches = servicer.diagnosis.slo.breaches()
        assert breaches["serve_queue"]["rule"] == "serve_queue_depth"
        plans = brain.plans()
        assert [p.kind for p in plans] == ["scale_decode_pool"]
        with open(store._wal_path, encoding="utf-8") as f:
            wal_kinds = [
                json.loads(ln)["plan"]["kind"]
                for ln in f if ln.strip()
                and json.loads(ln)["op"] == "brain_plan"
            ]
        assert "scale_decode_pool" in wal_kinds

        # phase 2 — the pool arrives (warmed engines), with a chaos
        # schedule set to kill worker 1 on its 3rd serving step
        chaos.install({
            "seed": 41,
            "rules": [{
                "site": "serve.step", "action": "error", "rank": 1,
                "verb": "serving", "after": 2, "max": 1,
            }],
        })
        workers = []
        try:
            for rank in range(2):
                eng = DecodeEngine(config, params, slots=3,
                                   capacity=32)
                eng.warmup(buckets=[8, 16])
                workers.append(DecodeWorker(
                    LocalServingClient(servicer, rank), eng, rank,
                    source=f"decode-{rank}-{os.getpid()}",
                ))
            # the kill target first: on a warm jit cache one worker
            # can drain the whole burst before its peer's loop is up,
            # and the scheduled kill needs worker 1 to actually serve
            for w in (workers[1], workers[0]):
                w.start()
            deadline = time.time() + 90
            while time.time() < deadline:
                counts = servicer.serving.counts()
                if counts["done"] + counts["failed"] >= n_requests:
                    break
                time.sleep(0.05)
        finally:
            for w in workers:
                w.stop()
            chaos.uninstall()

        counts = servicer.serving.counts()
        # nothing dropped, nothing double-served, nothing failed
        assert counts["done"] == n_requests, counts
        assert counts["failed"] == 0
        assert counts["max_attempts_seen"] <= 2
        # the kill actually landed mid-service and its in-flight
        # leases re-queued onto the survivor
        assert workers[1].crashed
        assert workers[1].abandoned
        assert counts["requeued_total"] >= len(workers[1].abandoned)
        # continuous batching overlapped >= 2 sequences in one decode
        # step window
        overlap = max(
            w.scheduler.stats()["overlap_high_water"] for w in workers
        )
        assert overlap >= 2
        # every request id completed exactly once, with real tokens
        for req in requests:
            rec = servicer.serving.fetch(req["request_id"])
            assert rec["state"] == "done", req["request_id"]
            assert 1 <= len(rec["tokens"]) <= 6
        # the scale-out plan completed once the pool showed up
        brain.sweep({"stragglers": {}, "hangs": {}, "slo": {}})
        assert brain.plans()[0].state == "done"
        # pool membership rode the decode rendezvous group
        rdzv = servicer.rdzv_managers[RendezvousName.DECODE_POOL]
        _round, members = rdzv.latest_members()
        assert set(members) == {0, 1}

        # the front door: per-worker TTFT series in the metrics store,
        # per-worker histograms + ledger gauges on /metrics, serving
        # sections in the report payload and obs_report
        from dlrover_tpu.master.http_plane import (
            MasterHttpPlane,
            render_prometheus,
        )

        series = servicer.metrics_store.query(
            "serve.ttft.last_s", resolution="raw"
        )
        sources = {s["source"] for s in series}
        assert len(sources) == 2, sources
        text = render_prometheus(servicer)
        assert "dlrtpu_serve_ttft_seconds_bucket" in text
        assert 'worker="0"' in text and 'worker="1"' in text
        assert "dlrtpu_serve_queue_depth 0" in text
        assert 'dlrtpu_serve_requests{state="done"}' in text
        payload = MasterHttpPlane(servicer).report_payload()
        assert payload["serving"]["counts"]["done"] == n_requests
        assert payload["serving"]["pool_size"] >= 1

        from tools.obs_report import _serving_summary

        tele_report = servicer.telemetry.report()
        serving_section = _serving_summary(
            tele_report.get("metrics", {}),
            tele_report.get("ledger", {}),
        )
        assert serving_section.get("serve_ttft_p99_ms", 0) > 0
        assert (
            serving_section.get("serve.completed{reason=length,worker=0}", 0)
            + serving_section.get("serve.completed{reason=length,worker=1}", 0)
            + serving_section.get("serve.completed{reason=eos,worker=0}", 0)
            + serving_section.get("serve.completed{reason=eos,worker=1}", 0)
        ) >= n_requests


# ============================================== wire protocol round trip


class TestServeMessages:
    def test_submit_lease_report_fetch_status_arms(self, model):
        """The four serve dispatch arms through the REAL servicer with
        the real message types (the wire twin lives in MasterClient)."""
        from tests.test_master_failover import _build_master_parts

        servicer = _build_master_parts()
        assert servicer.report("client", 0, msg.ServeSubmitRequest(
            request_id="a", prompt=[1, 2, 3], max_new_tokens=4,
        ))
        lease = servicer.get("decode", 3, msg.ServeLeaseRequest(
            node_rank=3, max_requests=2,
        ))
        assert [r["request_id"] for r in lease.requests] == ["a"]
        assert lease.queue_depth == 0
        assert servicer.report("decode", 3, msg.ServeResultReport(
            request_id="a", node_rank=3, tokens=[7, 8],
            finish_reason="length",
        ))
        res = servicer.get("client", 0, msg.ServeFetchRequest(
            request_id="a",
        ))
        assert res.state == "done" and res.tokens == [7, 8]
        status = servicer.get("client", 0, msg.ServeStatusRequest())
        assert status.summary["counts"]["done"] == 1
        assert status.summary["pool_size"] == 1
