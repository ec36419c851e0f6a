"""Elastic repair brain: ScalePlan policies, the preempt.notice chaos
action, drained-departure goodput accounting, trainer cadence adoption,
and the week-in-the-life smoke.

Covers (marker ``brain``, tier-1):
- straggler eviction: N-sweep persistence, min-world floor, job-wide
  guard, cooldown, SLO breaches counting as the same suspect signal;
- predictive drain: notice -> directive through the REAL servicer,
  keyed idempotency (same plan id on re-send), completion when the
  round re-forms without the target, abandon on timeout;
- goodput-aware cadence: Young/Daly math from observed history, the
  no-evidence guards, run-config publication + deadband, and the
  Trainer's adoption of the published value;
- ``preempt.notice`` chaos action: seeded-deterministic lead, rank and
  time (``at``) anchoring, consume-once semantics, uninstall disarm;
- drained-departure accounting (satellite): an incarnation gap
  bracketed by an ``elastic.drained`` marker lands in the ledger's
  ``reshape`` bucket, an unmarked gap stays ``restart``; classify_exit
  taxonomy rows for notice-then-SIGTERM teardowns;
- surfaces: obs_report's brain section, /metrics brain gauges, the
  dashboard payload;
- the week-in-the-life smoke (also ``chaos``): one announced
  preemption against a 2-host fleet, brain ON — zero survivor
  restarts, restart bucket empty, predictive-drain plan done.
"""

from __future__ import annotations

import json
import os
import signal
import time

import pytest

from dlrover_tpu.common import chaos, telemetry
from dlrover_tpu.common import messages as msg
from dlrover_tpu.common.constants import ExitCode, RendezvousName
from dlrover_tpu.master.brain import RepairBrain, ScalePlan

pytestmark = pytest.mark.brain


def _verdicts(stragglers=None, slo=None):
    return {
        "stragglers": stragglers or {},
        "hangs": {},
        "slo": slo or {},
    }


def _servicer_with_world(ranks=(0, 1, 2)):
    from tests.test_master_failover import _build_master_parts

    servicer = _build_master_parts()
    rdzv = servicer.rdzv_managers[RendezvousName.ELASTIC_TRAINING]
    rdzv.update_rdzv_params(2, 16, 0.0, 1)
    for r in ranks:
        rdzv.join_rendezvous(r, 1, "127.0.0.1")
    rdzv.get_comm_world(ranks[0])  # form the round
    return servicer, rdzv


class TestStragglerEviction:
    def test_persistent_straggler_is_drained_and_plan_completes(self):
        servicer, rdzv = _servicer_with_world()
        brain = servicer.brain
        brain._cooldown = 0.0
        verdict = _verdicts(stragglers={2: {"phase": "compute"}})
        # below the persistence budget: no plan yet
        brain.sweep(verdict)
        brain.sweep(verdict)
        assert brain.plans() == []
        brain.sweep(verdict)
        plans = brain.plans()
        assert [p.kind for p in plans] == ["evict_straggler"]
        assert plans[0].target == 2
        assert plans[0].state == "executing"
        # the drain dissolved the round; polling re-forms it without 2
        round_, members = rdzv.latest_members()
        rdzv.get_comm_world(0)
        round2, members2 = rdzv.latest_members()
        assert round2 == round_ + 1 and members2 == [0, 1]
        brain.sweep(_verdicts())
        assert brain.plans()[0].state == "done"
        # the streak was consumed with the eviction
        assert brain._suspect_streak == {}

    def test_streak_resets_when_the_verdict_clears(self):
        servicer, _ = _servicer_with_world()
        brain = servicer.brain
        brain._cooldown = 0.0
        v = _verdicts(stragglers={2: {"phase": "compute"}})
        brain.sweep(v)
        brain.sweep(v)
        brain.sweep(_verdicts())  # cleared: streak resets
        brain.sweep(v)
        brain.sweep(v)
        assert brain.plans() == []

    def test_min_world_floor_blocks_eviction(self):
        servicer, _ = _servicer_with_world(ranks=(0, 1))
        brain = servicer.brain
        brain._cooldown = 0.0
        v = _verdicts(stragglers={1: {"phase": "compute"}})
        for _ in range(5):
            brain.sweep(v)
        # evicting 1 of 2 would leave 1 < min_world=2
        assert brain.plans() == []

    def test_job_wide_slowness_is_not_an_eviction(self):
        servicer, _ = _servicer_with_world()
        brain = servicer.brain
        brain._cooldown = 0.0
        v = _verdicts(stragglers={
            0: {"phase": "compute"},
            1: {"phase": "compute"},
            2: {"phase": "compute"},
        })
        for _ in range(5):
            brain.sweep(v)
        assert brain.plans() == []

    def test_cooldown_holds_the_second_eviction(self):
        servicer, rdzv = _servicer_with_world(ranks=(0, 1, 2, 3))
        brain = servicer.brain
        brain._cooldown = 3600.0
        v2 = _verdicts(stragglers={2: {"phase": "compute"}})
        brain.sweep(v2)
        brain.sweep(v2)
        brain.sweep(v2)
        assert len(brain.plans()) == 1
        rdzv.get_comm_world(0)  # re-form without 2
        brain.sweep(_verdicts())
        v3 = _verdicts(stragglers={3: {"phase": "data_wait"}})
        for _ in range(5):
            brain.sweep(v3)
        # still only the first eviction: the cooldown stands
        assert [p.kind for p in brain.plans()] == ["evict_straggler"]

    def test_slo_breach_names_the_same_suspect(self):
        servicer, _ = _servicer_with_world()
        brain = servicer.brain
        brain._cooldown = 0.0
        slo = {
            "step_time:worker-2-777": {
                "rule": "step_time_regression",
                "source": "worker-2-777",
            },
        }
        for _ in range(3):
            brain.sweep(_verdicts(slo=slo))
        plans = brain.plans()
        assert len(plans) == 1 and plans[0].target == 2

    def test_disabled_brain_decides_nothing(self):
        servicer, _ = _servicer_with_world()
        brain = servicer.brain
        brain.enabled = False
        brain._cooldown = 0.0
        v = _verdicts(stragglers={2: {"phase": "compute"}})
        for _ in range(5):
            brain.sweep(v)
        assert brain.plans() == []
        d = brain.handle_preempt_notice(1, time.time() + 5, 5.0)
        assert d["action"] == "none" and brain.plans() == []


class TestPredictiveDrain:
    def test_notice_through_the_servicer_drains_and_completes(self):
        servicer, rdzv = _servicer_with_world()
        deadline = time.time() + 30
        directive = servicer.get(
            "worker", 1,
            msg.PreemptNoticeRequest(
                node_rank=1, deadline=deadline, lead_s=30.0
            ),
        )
        assert directive.action == "drain"
        assert directive.plan_id
        # the drain dissolved the round: survivors re-form without 1,
        # with a "drained" departure (device-to-device shards readable)
        rdzv.get_comm_world(0)
        _round, members = rdzv.latest_members()
        assert members == [0, 2]
        _verd, departed = rdzv.round_verdicts()
        assert departed == {1: "drained"}
        servicer.brain.sweep(_verdicts())
        (plan,) = servicer.brain.plans()
        assert plan.state == "done"

    def test_resent_notice_reserves_the_same_standing_plan(self):
        servicer, _ = _servicer_with_world()
        deadline = time.time() + 30
        d1 = servicer.brain.handle_preempt_notice(1, deadline, 30.0)
        d2 = servicer.brain.handle_preempt_notice(1, deadline, 29.0)
        assert d1["plan_id"] == d2["plan_id"]
        assert len(servicer.brain.plans()) == 1

    def test_distinct_deadlines_get_distinct_plans(self):
        servicer, rdzv = _servicer_with_world()
        d1 = servicer.brain.handle_preempt_notice(1, 1000.0, 5.0)
        # first plan completes (round re-forms without 1) ...
        rdzv.get_comm_world(0)
        servicer.brain.sweep(_verdicts())
        # ... then the host comes back and a NEW notice arrives later
        rdzv.join_rendezvous(1, 1, "127.0.0.1")
        rdzv.get_comm_world(0)
        d2 = servicer.brain.handle_preempt_notice(1, 2000.0, 5.0)
        assert d1["plan_id"] != d2["plan_id"]

    def test_standing_plan_abandons_past_its_deadline(self):
        servicer, _ = _servicer_with_world()
        brain = servicer.brain
        brain._plan_timeout = 0.0
        brain.handle_preempt_notice(1, time.time() + 30, 30.0)
        # no round ever re-forms; the deadline passes
        time.sleep(0.01)
        brain.sweep(_verdicts())
        (plan,) = brain.plans()
        assert plan.state == "abandoned"
        assert plan.detail.get("reason") == "timeout"


class TestCadenceController:
    def _snap(self, events):
        return {
            "format": 1, "source": "worker-0-1", "role": "worker",
            "now": time.time(), "counters": [], "gauges": [],
            "histograms": [], "series": [],
            "events": events, "events_dropped": 0,
        }

    def test_young_daly_from_observed_history(self):
        brain = RepairBrain(cadence_bounds=(1, 10_000))
        # ckpt cost 2 s, step 1 s, 2 failures over 800 s -> MTBF 400 s
        # -> interval sqrt(2*2*400) = 40 s -> 40 steps
        events = (
            [{"kind": "ckpt.save", "dur": 2.0, "t": 100.0 + i}
             for i in range(4)]
            + [{"kind": "step.end", "dur": 1.0, "t": 200.0 + i}
               for i in range(8)]
            + [{"kind": "worker.exit", "t": 300.0},
               {"kind": "preempt.notice", "t": 600.0}]
        )
        steps = brain.compute_cadence(
            [self._snap(events)], {"total_s": 800.0}
        )
        assert steps == 40

    def test_notice_and_its_own_kill_cluster_as_one_failure(self):
        brain = RepairBrain(cadence_bounds=(1, 10_000))
        events = (
            [{"kind": "ckpt.save", "dur": 2.0, "t": 100.0}]
            + [{"kind": "step.end", "dur": 1.0, "t": 200.0}]
            + [
                {"kind": "preempt.notice", "t": 300.0},
                # the announced kill 3 s later is the SAME failure
                {"kind": "chaos.fire", "action": "kill", "t": 303.0},
            ]
        )
        steps = brain.compute_cadence(
            [self._snap(events)], {"total_s": 800.0}
        )
        # 1 failure -> MTBF 800 -> sqrt(3200) = 56.6 -> 57 steps
        assert steps == 57

    def test_no_failures_or_no_cost_means_no_move(self):
        brain = RepairBrain()
        steps_only = [{"kind": "step.end", "dur": 1.0, "t": 1.0}]
        assert brain.compute_cadence(
            [self._snap(steps_only)], {"total_s": 100.0}
        ) is None
        no_ckpt = steps_only + [{"kind": "worker.exit", "t": 2.0}]
        assert brain.compute_cadence(
            [self._snap(no_ckpt)], {"total_s": 100.0}
        ) is None

    def test_bounds_clamp(self):
        brain = RepairBrain(cadence_bounds=(5, 20))
        events = (
            [{"kind": "ckpt.save", "dur": 10.0, "t": 1.0}]
            + [{"kind": "step.end", "dur": 0.001, "t": 2.0}]
            + [{"kind": "worker.exit", "t": 3.0}]
        )
        assert brain.compute_cadence(
            [self._snap(events)], {"total_s": 10_000.0}
        ) == 20

    def test_sweep_publishes_run_config_with_deadband(self):
        servicer, _ = _servicer_with_world()
        brain = servicer.brain
        brain._cadence_interval = 0.0
        events = (
            [{"kind": "ckpt.save", "dur": 2.0, "t": 100.0}]
            + [{"kind": "step.end", "dur": 1.0, "t": 200.0 + i}
               for i in range(4)]
            + [{"kind": "worker.exit", "t": 300.0}]
        )
        servicer.telemetry.update(self._snap(events))
        brain.sweep(_verdicts())
        from dlrover_tpu.master.brain import CADENCE_CONFIG_KEY

        published = servicer.get_run_configs().get(CADENCE_CONFIG_KEY)
        assert published and published > 0
        cadence_plans = [
            p for p in brain.plans() if p.kind == "cadence"
        ]
        assert len(cadence_plans) == 1
        assert cadence_plans[0].state == "done"
        # same evidence again: inside the deadband, no second plan
        brain.sweep(_verdicts())
        assert len([
            p for p in brain.plans() if p.kind == "cadence"
        ]) == 1

    def test_restored_standing_cadence_plan_publishes_on_resweep(self):
        """Failover inside the decide->publish window: the restored
        STANDING cadence plan must still publish the run config on the
        next sweep (bailing on "not fresh" would wedge it forever)."""
        servicer, _ = _servicer_with_world()
        brain = servicer.brain
        brain._cadence_interval = 0.0
        events = (
            [{"kind": "ckpt.save", "dur": 2.0, "t": 100.0}]
            + [{"kind": "step.end", "dur": 1.0, "t": 200.0 + i}
               for i in range(4)]
            + [{"kind": "worker.exit", "t": 300.0}]
        )
        servicer.telemetry.update(self._snap(events))
        steps = brain.compute_cadence(
            servicer.telemetry.snapshots(),
            servicer.telemetry.ledger(now=time.time()),
        )
        # simulate the restored state: the plan was decided but the
        # publish never happened (the crash window)
        from dlrover_tpu.master.brain import CADENCE_CONFIG_KEY

        brain.replay_plan({
            "plan_id": "plan-7", "kind": "cadence", "target": -1,
            "state": "decided", "key": f"cadence:{steps}",
            "created": time.time(), "updated": time.time(),
            "deadline": time.time() + 60, "detail": {},
        }, seq=7)
        assert CADENCE_CONFIG_KEY not in servicer.get_run_configs()
        brain.sweep(_verdicts())
        assert servicer.get_run_configs().get(
            CADENCE_CONFIG_KEY
        ) == steps
        (plan,) = [p for p in brain.plans() if p.kind == "cadence"]
        assert plan.plan_id == "plan-7" and plan.state == "done"

    def test_trainer_adopts_published_cadence(self):
        from dlrover_tpu.trainer.trainer import Trainer, TrainingArgs

        class FakeClient:
            def get_elastic_run_config(self, retries=None):
                return {"ckpt_save_steps": 17}

        class Stub:
            args = TrainingArgs(save_steps=5)
            _engine = object()
            _advisory_client = FakeClient()
            _master = Trainer._master

        stub = Stub()
        Trainer._maybe_adopt_cadence(stub)
        assert stub.args.save_steps == 17

    def test_trainer_adoption_disabled_or_without_cadence(self):
        from dlrover_tpu.trainer.trainer import Trainer, TrainingArgs

        class ExplodingClient:
            def get_elastic_run_config(self, retries=None):
                raise AssertionError("must not be polled")

        class Stub:
            args = TrainingArgs(save_steps=0)  # cadence saving off
            _engine = object()
            _advisory_client = ExplodingClient()
            _master = Trainer._master

        Trainer._maybe_adopt_cadence(Stub())

        class Stub2:
            args = TrainingArgs(save_steps=5, adopt_cadence=False)
            _engine = object()
            _advisory_client = ExplodingClient()
            _master = Trainer._master

        Trainer._maybe_adopt_cadence(Stub2())


class TestPreemptNoticeChaos:
    def test_rank_and_time_anchored_notice_with_seeded_lead(self):
        sched = {
            "seed": 9,
            "rules": [{
                "site": "preempt.notice", "action": "notice",
                "rank": 1, "at": 5.0, "lead": [1.0, 2.0],
                "enforce": False, "max": 1,
            }],
        }
        leads = []
        for _ in range(2):
            chaos.install(sched)
            chaos.chaos_point("preempt.notice", rank=0, elapsed=9.0)
            assert chaos.take_preempt_notice() is None  # wrong rank
            chaos.chaos_point("preempt.notice", rank=1, elapsed=2.0)
            assert chaos.take_preempt_notice() is None  # too early
            chaos.chaos_point("preempt.notice", rank=1, elapsed=6.0)
            note = chaos.take_preempt_notice()
            assert note is not None
            assert 1.0 <= note["lead"] <= 2.0
            # consume-once: the same notice never serves twice
            assert chaos.take_preempt_notice() is None
            leads.append(note["lead"])
            chaos.uninstall()
        # seeded determinism: the lead replays exactly
        assert leads[0] == leads[1]

    def test_enforce_false_records_without_arming_a_timer(self):
        chaos.install({
            "seed": 3,
            "rules": [{
                "site": "preempt.notice", "action": "notice",
                "lead": 30.0, "enforce": False,
            }],
        })
        try:
            chaos.chaos_point("preempt.notice", rank=0)
            reg = chaos.active_registry()
            assert reg.pending_preempt_deadline() is not None
            assert reg._timers == []
        finally:
            chaos.uninstall()

    def test_reinstall_disarms_the_previous_schedules_kills(self):
        sched = {
            "seed": 3,
            "rules": [{
                "site": "preempt.notice", "action": "notice",
                "lead": 30.0,
            }],
        }
        chaos.install(sched)
        chaos.chaos_point("preempt.notice", rank=0)
        old = chaos.active_registry()
        assert len(old._timers) == 1
        timer = old._timers[0]
        try:
            # installing a NEW schedule directly (no uninstall) must
            # not leave the old registry's armed deadline kill behind
            chaos.install({"seed": 4, "rules": []})
            timer.join(timeout=1.0)
            assert not timer.is_alive()
            assert old._timers == []
        finally:
            chaos.uninstall()

    def test_uninstall_disarms_pending_kills(self):
        chaos.install({
            "seed": 3,
            "rules": [{
                "site": "preempt.notice", "action": "notice",
                "lead": 30.0,
            }],
        })
        chaos.chaos_point("preempt.notice", rank=0)
        reg = chaos.active_registry()
        assert len(reg._timers) == 1
        chaos.uninstall()
        assert not reg._timers[0].is_alive() if reg._timers else True
        assert chaos.take_preempt_notice() is None

    def test_week_schedule_is_registered(self):
        assert "week-in-the-life" in chaos.NAMED_SCHEDULES
        assert chaos.NAMED_SCHEDULES["week-in-the-life"].get("desc")

    def test_brain_is_in_dl003_chaos_coverage_scope(self):
        from tools.dlint.chaos_cov import _SCOPE_RE

        assert _SCOPE_RE.search("dlrover_tpu/master/brain.py")


class TestDrainedGapAccounting:
    """Satellite: a notice-then-teardown gap whose predictive drain
    succeeded accounts as ``reshape``; an unmarked gap stays
    ``restart``."""

    @staticmethod
    def _worker(source, t0, steps, dt=1.0):
        return {
            "format": 1, "source": source, "role": "worker",
            "now": t0 + steps * dt, "counters": [], "gauges": [],
            "histograms": [], "series": [], "events_dropped": 0,
            "events": [
                {"seq": i + 1, "t": t0 + (i + 1) * dt,
                 "kind": "step.end", "dur": dt}
                for i in range(steps)
            ],
        }

    def test_drained_marker_recharges_the_gap_to_reshape(self):
        t0 = 1000.0
        first = self._worker("worker-1-100", t0, 5)       # ends 1005
        second = self._worker("worker-1-200", t0 + 15, 5)  # starts 1016
        agent = {
            "format": 1, "source": "agent-1-50", "role": "agent",
            "now": t0 + 30, "counters": [], "gauges": [],
            "histograms": [], "series": [], "events_dropped": 0,
            "events": [{
                "seq": 1, "t": t0 + 6.0, "kind": "elastic.drained",
                "rank": 1, "dur": 1.0,
            }],
        }
        ledger = telemetry.goodput_ledger([first, second, agent])
        cats = ledger["categories"]
        assert cats["restart"] == 0.0
        assert cats["reshape"] >= 9.0  # the 10 s gap, drain-claimed
        assert abs(
            sum(cats.values()) - ledger["total_s"]
        ) < 1e-6

    def test_unmarked_gap_stays_restart(self):
        t0 = 1000.0
        first = self._worker("worker-1-100", t0, 5)
        second = self._worker("worker-1-200", t0 + 15, 5)
        ledger = telemetry.goodput_ledger([first, second])
        cats = ledger["categories"]
        assert cats["reshape"] == 0.0
        assert cats["restart"] >= 9.0

    def test_one_marker_claims_at_most_one_gap(self):
        # the drain at t=1006 claims ITS gap (1005 -> 1016); the later
        # unannounced gap (1026 -> 1041) must stay restart even though
        # the marker precedes it
        t0 = 1000.0
        a = self._worker("worker-1-100", t0, 5)            # ends 1005
        b = self._worker("worker-1-200", t0 + 15, 5)       # 1016-1021
        c = self._worker("worker-1-300", t0 + 40, 5)       # 1041-1046
        agent = {
            "format": 1, "source": "agent-1-50", "role": "agent",
            "now": t0 + 60, "counters": [], "gauges": [],
            "histograms": [], "series": [], "events_dropped": 0,
            "events": [{
                "seq": 1, "t": t0 + 6.0, "kind": "elastic.drained",
                "rank": 1, "dur": 1.0,
            }],
        }
        ledger = telemetry.goodput_ledger([a, b, c, agent])
        cats = ledger["categories"]
        assert cats["reshape"] >= 9.0    # the drained gap
        assert cats["restart"] >= 19.0   # the later unannounced gap

    def test_far_away_drained_marker_does_not_whitewash(self):
        t0 = 1000.0
        first = self._worker("worker-1-100", t0, 5)
        second = self._worker("worker-1-200", t0 + 120, 5)
        agent = {
            "format": 1, "source": "agent-1-50", "role": "agent",
            "now": t0 + 200, "counters": [], "gauges": [],
            "histograms": [], "series": [], "events_dropped": 0,
            # a drain from LONG after the gap closed (next event era)
            "events": [{
                "seq": 1, "t": t0 + 180.0, "kind": "elastic.drained",
                "rank": 1, "dur": 1.0,
            }],
        }
        ledger = telemetry.goodput_ledger([first, second, agent])
        assert ledger["categories"]["restart"] >= 100.0


class TestClassifyExitDraining:
    @pytest.mark.parametrize(
        ("returncode", "draining", "expected"),
        [
            # notice-then-SIGTERM teardown with a successful drain:
            # clean stop, not a software failure (the regression)
            (-signal.SIGTERM, True, "stopped"),
            (ExitCode.TERMED, True, "stopped"),
            # the platform's announced kill landing mid/post-drain
            (-signal.SIGKILL, True, "preempted"),
            (ExitCode.KILLED, True, "preempted"),
            # not draining: the existing taxonomy is untouched
            (-signal.SIGTERM, False, "software"),
            (-signal.SIGKILL, False, "oom"),
            # hardware stays hardware even during a drain
            (-signal.SIGABRT, True, "hardware"),
            (0, True, "succeeded"),
        ],
    )
    def test_table(self, returncode, draining, expected):
        from dlrover_tpu.agent.training_agent import classify_exit

        assert classify_exit(
            returncode, "", stopping=False, draining=draining
        ) == expected


class TestAgentPredrain:
    def _agent(self, client):
        from dlrover_tpu.agent.training_agent import (
            ElasticLaunchConfig,
            ElasticTrainingAgent,
            WorkerSpec,
        )

        config = ElasticLaunchConfig(
            min_nodes=1, max_nodes=1, node_rank=1,
            reshape_in_process=False,
        )
        return ElasticTrainingAgent(
            config, WorkerSpec("w.py", (), config), client
        )

    def test_notice_executes_the_directed_drain(self, monkeypatch):
        calls = []

        class FakeClient:
            master_addr = "127.0.0.1:1"
            node_id = 1

            def report_preempt_notice(self, rank, deadline, lead):
                calls.append(("notice", rank))
                return msg.PreemptNoticeDirective(
                    action="drain", plan_id="plan-9",
                    deadline=deadline,
                )

            def drain_node(self, rank):
                calls.append(("drain", rank))
                return True

        agent = self._agent(FakeClient())
        monkeypatch.setattr(
            agent, "_save_ckpt_at_breakpoint",
            lambda: calls.append(("ckpt", None)),
        )
        chaos.install({
            "seed": 1,
            "rules": [{
                "site": "preempt.notice", "action": "notice",
                "rank": 1, "lead": 30.0, "enforce": False, "max": 1,
            }],
        })
        reg = telemetry.enable("agent-1-test")
        try:
            assert agent._poll_preempt_notice() is True
        finally:
            chaos.uninstall()
        assert ("notice", 1) in calls
        assert ("drain", 1) in calls
        assert ("ckpt", None) in calls
        # the drain report precedes the checkpoint flush: survivors
        # start reshaping while this host persists its state
        assert calls.index(("drain", 1)) < calls.index(("ckpt", None))
        kinds = [e["kind"] for e in reg.snapshot()["events"]]
        assert "preempt.notice" in kinds
        assert "elastic.drained" in kinds
        assert agent._draining

    def test_unreachable_master_keeps_the_fallback_path(self):
        class DeadClient:
            master_addr = "127.0.0.1:1"
            node_id = 1

            def report_preempt_notice(self, rank, deadline, lead):
                raise ConnectionError("master gone")

        agent = self._agent(DeadClient())
        chaos.install({
            "seed": 1,
            "rules": [{
                "site": "preempt.notice", "action": "notice",
                "rank": 1, "lead": 30.0, "enforce": False, "max": 1,
            }],
        })
        try:
            assert agent._poll_preempt_notice() is False
        finally:
            chaos.uninstall()
        assert not agent._draining

    def test_none_directive_keeps_the_fallback_path(self):
        class OffBrainClient:
            master_addr = "127.0.0.1:1"
            node_id = 1

            def report_preempt_notice(self, rank, deadline, lead):
                return msg.PreemptNoticeDirective(action="none")

        agent = self._agent(OffBrainClient())
        chaos.install({
            "seed": 1,
            "rules": [{
                "site": "preempt.notice", "action": "notice",
                "rank": 1, "lead": 30.0, "enforce": False, "max": 1,
            }],
        })
        try:
            assert agent._poll_preempt_notice() is False
        finally:
            chaos.uninstall()
        assert not agent._draining


class TestBrainSurfaces:
    def test_metrics_and_report_payload_carry_the_brain(self):
        from dlrover_tpu.master.http_plane import (
            MasterHttpPlane,
            render_prometheus,
        )

        servicer, _ = _servicer_with_world()
        servicer.brain.handle_preempt_notice(1, time.time() + 30, 30.0)
        text = render_prometheus(servicer)
        assert 'dlrtpu_brain_plans{state="executing"} 1' in text
        plane = MasterHttpPlane(servicer)
        payload = plane.report_payload()
        brain = payload["brain"]
        assert brain["states"]["executing"] == 1
        assert brain["recent"][0]["kind"] == "predictive_drain"

    def test_obs_report_brain_section(self, tmp_path, monkeypatch):
        monkeypatch.setenv("DLROVER_TELEMETRY_DIR", str(tmp_path))
        reg = telemetry.enable("master-0-9999")
        reg.event(
            "brain.plan.decided", plan="plan-1",
            plan_kind="predictive_drain", target=1,
        )
        reg.event(
            "brain.plan.done", plan="plan-1",
            plan_kind="predictive_drain", target=1,
        )
        reg.counter_inc(
            "brain.plans", kind="predictive_drain", state="done"
        )
        reg.flush()
        from tools.obs_report import build_report

        report = build_report(telemetry_dir=str(tmp_path))
        brain = report["brain"]
        assert brain["plans"][-1]["transition"] == "done"
        assert brain["plans"][-1]["plan_kind"] == "predictive_drain"
        assert any(
            k.startswith("brain.plans") for k in brain["counters"]
        )


@pytest.mark.chaos
def test_week_in_the_life_smoke(tmp_path):
    """Fast brain-on smoke of the week harness: one announced
    preemption against a 2-host fleet. Zero survivor restarts, the
    whole event in the reshape bucket (restart stays empty), the
    predictive-drain plan done, the victim drained and replaced."""
    from tools.chaos_run import run_week_arm

    schedule = {
        "seed": 31,
        "rules": [{
            "site": "preempt.notice", "action": "notice", "rank": 1,
            "at": 1.5, "max": 1, "lead": [1.2, 1.6],
        }],
    }
    cfg = {
        "hosts": 2, "dt": 0.04, "duration_s": 10.0, "min_nodes": 1,
        "rdzv_wait": 0.5, "brain": True,
    }
    res = run_week_arm(str(tmp_path), "on", schedule, cfg)
    done = {
        p["kind"] for p in res["plans"]["recent"]
        if p["state"] == "done"
    }
    assert "predictive_drain" in done, res["plans"]
    assert res["drained"] == [1], res
    # zero survivor restarts: only the preempted host respawned
    assert res["respawns"][0] == 0, res
    assert res["respawns"][1] == 1, res
    # the whole announced event landed in reshape, not restart
    assert res["categories"]["restart"] < 0.2, res["categories"]
    assert res["categories"]["reshape"] > 0.0, res["categories"]
    # pre-drain checkpoint flush: the replacement resumed with zero
    # replay
    assert res["replay_by_rank"].get(1, 0) == 0, res


@pytest.mark.chaos
@pytest.mark.slow
def test_week_in_the_life_full(tmp_path):
    """The full on-vs-off comparison on one seed (slow): asserts the
    whole acceptance contract via the harness's own checks."""
    from dlrover_tpu.common.chaos import NAMED_SCHEDULES
    from tools.chaos_run import _run_week

    assert _run_week(
        NAMED_SCHEDULES["week-in-the-life"], str(tmp_path), 10
    ) == 0
