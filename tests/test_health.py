"""Hardware health plane: the join-time probe (agent/probe.py), the
master's graded gate + persistent fingerprints (master/health.py), the
continuous in-band re-probe path, and the wiring that turns sustained
degradation into ``hw`` diagnosis verdicts, straggler-set entries, and
a brain drain — plus the offline report's health section.
"""

import io
import time

import pytest

from dlrover_tpu.agent.probe import (
    ProbeScheduler,
    probe_disabled,
    run_probe,
)
from dlrover_tpu.common import chaos
from dlrover_tpu.master.health import RATIO, SLACK_MS, HostHealthManager

pytestmark = pytest.mark.health


def _report(hbm=100.0, matmul=100.0, collective=100.0, error=""):
    """A probe report at chosen per-leg ms (all well above SLACK_MS so
    ratio judgements are exercised, not the jitter floor)."""
    legs = {"hbm": hbm, "matmul": matmul, "collective": collective}
    return {
        "legs": {} if error else legs,
        "elapsed_s": 0.1,
        "host": 0,
        "backend": "host",
        "error": error,
        "t": 0.0,
    }


def _mgr(**kw):
    kw.setdefault("backoff_s", 30.0)
    kw.setdefault("backoff_cap_s", 600.0)
    return HostHealthManager(**kw)


def _seed_fleet(mgr, ranks=(0, 1, 2), ms=100.0, now=0.0):
    """Admit a healthy fleet so later reports have a median to be
    judged against."""
    for r in ranks:
        out = mgr.gate(r, _report(ms, ms, ms), now=now)
        assert out["verdict"] == "pass", out
    return mgr


# -------------------------------------------------------------------------
# agent-side probe
# -------------------------------------------------------------------------


@pytest.fixture
def disarm(monkeypatch):
    # the chaos tests bound the UN-degraded legs at 100 ms: keep the
    # numpy stand-in matmul too small for a loaded host's BLAS threads
    # to push it over that by themselves (256-wide took 130-430 ms on
    # a busy sandbox, sleep or no sleep)
    from dlrover_tpu.agent import probe

    monkeypatch.setattr(probe, "MATMUL_SIZE", 32)
    yield
    chaos.uninstall()


class TestProbe:
    def test_run_probe_smoke_under_join_budget(self):
        report = run_probe(node_rank=5)
        assert report["error"] == ""
        assert report["host"] == 5
        assert set(report["legs"]) == {"hbm", "matmul", "collective"}
        assert all(v > 0 for v in report["legs"].values())
        # the bad-host schedule's acceptance bound: the probe must not
        # meaningfully tax the join path
        assert report["elapsed_s"] < 5.0

    def test_mock_err_rank_reports_error(self, monkeypatch):
        from dlrover_tpu.common.constants import NodeEnv

        monkeypatch.setenv(NodeEnv.NODE_RANK, "2")
        monkeypatch.setenv(NodeEnv.MOCK_ERR_RANK, "2")
        report = run_probe()
        assert report["error"]
        assert report["legs"] == {}
        # ... and the gate refuses an errored probe outright
        out = _mgr().gate(2, report, now=0.0)
        assert out["verdict"] == "refuse"
        assert "probe error" in out["reason"]

    def test_probe_disabled_env(self, monkeypatch):
        assert not probe_disabled()
        monkeypatch.setenv("DLROVER_PROBE_DISABLE", "1")
        assert probe_disabled()

    def test_chaos_degrade_inflates_timed_leg(self, disarm):
        """The degrade action sleeps INSIDE the timed window, so the
        anchored host's leg reads slow — the seeded fault the bad-host
        schedule is built from."""
        chaos.install({
            "seed": 9,
            "rules": [{
                "site": "probe.degrade", "action": "degrade",
                "rank": 4, "delay": 0.2, "max": 1,
            }],
        })
        report = run_probe(node_rank=4)
        assert report["error"] == ""
        # scaled sleep is >= 0.75 * delay = 150 ms; only the first leg
        # (max: 1) pays it
        assert report["legs"]["hbm"] >= 100.0
        assert report["legs"]["matmul"] < 100.0

    def test_chaos_degrade_other_rank_untouched(self, disarm):
        chaos.install({
            "seed": 9,
            "rules": [{
                "site": "probe.degrade", "action": "degrade",
                "rank": 4, "delay": 0.2,
            }],
        })
        report = run_probe(node_rank=1)
        assert all(v < 100.0 for v in report["legs"].values())


class TestProbeScheduler:
    def test_governor_stretches_gap_to_overhead_budget(self):
        s = ProbeScheduler(interval_s=10.0, overhead_pct=2.0)
        assert s.due(now=0.0)  # never armed -> due
        # cheap probe: the interval floor holds
        s.seed({"elapsed_s": 0.1}, now=0.0)
        assert s.last_gap == 10.0
        assert not s.due(now=9.9)
        assert s.due(now=10.0)
        # expensive probe: gap stretches until cost <= 2% of the wait
        s.seed({"elapsed_s": 1.0}, now=0.0)
        assert s.last_gap == pytest.approx(50.0)
        assert not s.due(now=49.0)
        assert s.due(now=50.0)

    def test_run_reprobes_and_rearms(self):
        s = ProbeScheduler(interval_s=600.0, overhead_pct=2.0)
        report = s.run(node_rank=0)
        assert s.last_report is report
        assert not s.due()

    def test_default_scheduler_is_a_process_singleton(self):
        from dlrover_tpu.agent.probe import default_scheduler

        assert default_scheduler() is default_scheduler()


# -------------------------------------------------------------------------
# master-side gate: the decision matrix
# -------------------------------------------------------------------------


class TestGateMatrix:
    def test_bootstrap_first_host_passes(self):
        # nothing to judge against: fleet empty, no own baseline
        out = _mgr().gate(0, _report(), now=0.0)
        assert out["verdict"] == "pass"

    def test_empty_report_passes_old_agent(self):
        out = _mgr().gate(0, {}, now=0.0)
        assert out["verdict"] == "pass"
        assert out["reason"] == "no probe report"

    def test_degraded_vs_fleet_quarantined(self):
        mgr = _seed_fleet(_mgr())
        out = mgr.gate(3, _report(hbm=300.0), now=0.0)
        assert out["verdict"] == "quarantine"
        assert "hbm" in out["reason"] and "fleet" in out["reason"]
        assert out["strikes"] == 1
        assert out["retry_after_s"] == pytest.approx(30.0)
        assert 3 in mgr.quarantined()

    def test_small_absolute_excess_is_jitter_not_degradation(self):
        # 2.4x of 5 ms is scheduler noise: the SLACK_MS floor keeps
        # millisecond-scale ratios from tripping the gate
        mgr = _seed_fleet(_mgr(), ms=5.0)
        assert 5.0 * (RATIO + 1) - 5.0 < SLACK_MS  # premise
        out = mgr.gate(3, _report(12.0, 12.0, 12.0), now=0.0)
        assert out["verdict"] == "pass"

    def test_a_harness_may_widen_the_slack_of_its_own_gate(self):
        # what the bad-host chaos harness does (tools/chaos_run.py):
        # 35 -> 75 ms is a neighbour's load on a shared CPU there, and
        # its injected degradation (+400 ms a leg) still parks a host
        mgr = _seed_fleet(_mgr(), ms=35.0)
        assert mgr.gate(3, _report(75.0, 35.0, 35.0), now=0.0)[
            "verdict"] == "quarantine"
        wide = _seed_fleet(_mgr(), ms=35.0)
        wide._slack_ms = 200.0
        assert wide.gate(3, _report(75.0, 35.0, 35.0), now=0.0)[
            "verdict"] == "pass"
        assert wide.gate(4, _report(435.0, 35.0, 35.0), now=0.0)[
            "verdict"] == "refuse"

    def test_severe_degradation_refused_with_longer_backoff(self):
        mgr = _seed_fleet(_mgr())
        out = mgr.gate(3, _report(matmul=100.0 * 5 * RATIO), now=0.0)
        assert out["verdict"] == "refuse"
        # refusals wait 4 backoff doublings before a re-judge
        assert out["retry_after_s"] == pytest.approx(120.0)

    def test_strikes_harden_quarantine_into_refuse(self):
        mgr = _seed_fleet(_mgr(refuse_strikes=3))
        now = 0.0
        for expected_strike, expected_verdict in (
            (1, "quarantine"), (2, "quarantine"), (3, "refuse"),
        ):
            out = mgr.gate(3, _report(hbm=300.0), now=now)
            assert out["verdict"] == expected_verdict, out
            assert out["strikes"] == expected_strike
            now += out["retry_after_s"] + 1.0  # wait out the backoff

    def test_standing_verdict_reserved_even_for_a_clean_retry(self):
        """While the backoff runs the gate re-serves the SAME verdict
        without re-judging — a parked host cannot extract a fresh
        judgement by re-rolling its probe, and cannot flap the round."""
        mgr = _seed_fleet(_mgr())
        first = mgr.gate(3, _report(hbm=300.0), now=0.0)
        assert first["verdict"] == "quarantine"
        retry = mgr.gate(3, _report(), now=10.0)  # clean report, early
        assert retry["verdict"] == "quarantine"
        assert retry["strikes"] == first["strikes"]
        assert retry["retry_after_s"] == pytest.approx(20.0)

    def test_readmit_after_backoff_with_clean_probe(self):
        mgr = _seed_fleet(_mgr())
        out = mgr.gate(3, _report(hbm=300.0), now=0.0)
        assert out["verdict"] == "quarantine"
        out = mgr.gate(3, _report(), now=31.0)
        assert out["verdict"] == "pass"
        # "cleared" marks the recovery so the servicer can emit the
        # health.readmit timeline event
        assert out.get("cleared") is True
        assert 3 not in mgr.quarantined()
        assert mgr.verdict(3)["verdict"] == "pass"

    def test_verdict_poll_is_read_only(self):
        mgr = _seed_fleet(_mgr())
        mgr.gate(3, _report(hbm=300.0), now=0.0)
        v1 = mgr.verdict(3, now=5.0)
        v2 = mgr.verdict(3, now=6.0)
        assert v1["verdict"] == v2["verdict"] == "quarantine"
        assert v1["strikes"] == v2["strikes"] == 1
        assert mgr.verdict(99)["verdict"] == "unknown"


class TestFingerprints:
    def test_healthy_samples_fold_into_ewma(self):
        mgr = _seed_fleet(_mgr(), ranks=(0,), ms=100.0)
        mgr.gate(0, _report(120.0, 120.0, 120.0), now=1.0)
        legs = mgr.summary()["hosts"]["0"]["legs"]
        # EWMA 0.25: 0.75*100 + 0.25*120 = 105
        assert legs["hbm"] == pytest.approx(105.0)

    def test_degraded_sample_freezes_ewma_but_rides_history(self):
        """Freeze-on-regression: a dying host cannot normalize its own
        decay, but the sparkline still shows the anomaly."""
        mgr = _seed_fleet(_mgr())
        before = mgr.summary()["hosts"]["0"]["legs"]["hbm"]
        out = mgr.gate(0, _report(hbm=400.0), now=1.0)
        assert out["verdict"] != "pass"
        host = mgr.summary(now=1.0)["hosts"]["0"]
        assert host["legs"]["hbm"] == pytest.approx(before)
        assert host["history"]["hbm"][-1] == pytest.approx(400.0)

    def test_judged_against_own_baseline_without_a_fleet(self):
        # fleet-of-one: the fleet median excludes the host itself, so
        # the only basis is its own persisted fingerprint
        mgr = _mgr()
        mgr.gate(0, _report(), now=0.0)
        out = mgr.gate(0, _report(collective=300.0), now=1.0)
        assert out["verdict"] == "quarantine"
        assert "self" in out["reason"]

    def test_export_restore_round_trip(self):
        mgr = _seed_fleet(_mgr())
        mgr.gate(3, _report(hbm=300.0), now=0.0)
        for _ in range(3):
            mgr.observe(1, _report(matmul=300.0), now=1.0)
        state = mgr.export_state()
        fresh = _mgr()
        fresh.restore_state(state)
        assert fresh.quarantined().keys() == mgr.quarantined().keys()
        assert fresh.verdict(3, now=1.0) == mgr.verdict(3, now=1.0)
        assert fresh.hw_degraded() == mgr.hw_degraded()
        assert (
            fresh.summary(now=1.0)["hosts"]["0"]["legs"]
            == mgr.summary(now=1.0)["hosts"]["0"]["legs"]
        )


# -------------------------------------------------------------------------
# continuous in-band checks -> hw_degraded
# -------------------------------------------------------------------------


class TestContinuousChecks:
    def test_sustained_degradation_surfaces_after_persist_obs(self):
        mgr = _seed_fleet(_mgr(persist_obs=3))
        for i in range(2):
            mgr.observe(1, _report(hbm=300.0), now=float(i))
            assert mgr.hw_degraded() == {}  # still debouncing
        mgr.observe(1, _report(hbm=300.0), now=2.0)
        hw = mgr.hw_degraded()
        assert 1 in hw
        assert hw[1]["leg"] == "hbm"
        assert hw[1]["streak"] == 3
        assert hw[1]["ratio"] == pytest.approx(3.0, rel=0.1)

    def test_one_healthy_observation_resets_the_streak(self):
        mgr = _seed_fleet(_mgr(persist_obs=3))
        mgr.observe(1, _report(hbm=300.0), now=0.0)
        mgr.observe(1, _report(hbm=300.0), now=1.0)
        mgr.observe(1, _report(), now=2.0)  # transient, not a trend
        mgr.observe(1, _report(hbm=300.0), now=3.0)
        assert mgr.hw_degraded() == {}

    def test_brain_enters_hw_verdicts_at_eviction_strength(self):
        """hw verdicts were already debounced by the health manager's
        persistence streak, so one brain sweep is enough to drain."""
        from dlrover_tpu.master.brain import RepairBrain

        brain = RepairBrain(cadence_bounds=(1, 10_000))
        brain._update_suspects({"hw": {1: {"streak": 3}}})
        assert brain._suspect_streak[1] >= brain._persist_sweeps


# -------------------------------------------------------------------------
# servicer wiring: gate at join, poll, in-band report, verdict merge
# -------------------------------------------------------------------------


def _servicer():
    from dlrover_tpu.common.constants import RendezvousName
    from dlrover_tpu.master.rendezvous import (
        ElasticTrainingRendezvousManager,
        NetworkCheckRendezvousManager,
    )
    from dlrover_tpu.master.servicer import MasterServicer

    mgr = ElasticTrainingRendezvousManager()
    mgr.update_rdzv_params(3, 8, 0.0, 1)
    servicer = MasterServicer(rdzv_managers={
        RendezvousName.ELASTIC_TRAINING: mgr,
        RendezvousName.NETWORK_CHECK: NetworkCheckRendezvousManager(),
    })
    servicer.health._backoff = 0.2  # harness-speed backoff
    return servicer


def _join(servicer, rank, report):
    from dlrover_tpu.common import messages as msg
    from dlrover_tpu.common.constants import RendezvousName

    return servicer.report("worker", rank, msg.JoinRendezvousRequest(
        node_id=rank, node_rank=rank, local_world_size=1,
        rdzv_name=RendezvousName.ELASTIC_TRAINING,
        node_ip=f"10.0.0.{rank}",
        probe_report=report,
    ))


class TestServicerWiring:
    def test_degraded_join_parked_not_in_world(self):
        from dlrover_tpu.common import messages as msg
        from dlrover_tpu.common.constants import RendezvousName

        servicer = _servicer()
        for r in range(3):
            assert _join(servicer, r, _report())
        assert _join(servicer, 3, _report(hbm=400.0))  # ack != admit
        world = servicer.get("worker", 0, msg.CommWorldRequest(
            node_id=0, rdzv_name=RendezvousName.ELASTIC_TRAINING,
        ))
        assert sorted(world.world) == [0, 1, 2]
        assert 3 in servicer.health.quarantined()
        # the parked host polls its standing verdict to learn it is
        # quarantined (vs merely waiting for a round to fill)
        verdict = servicer.get(
            "worker", 3, msg.NodeHealthRequest(node_rank=3)
        )
        assert verdict.verdict in ("quarantine", "refuse")
        assert verdict.retry_after_s > 0

    def test_in_band_reports_become_hw_diagnosis_verdicts(self):
        from dlrover_tpu.common import messages as msg

        servicer = _servicer()
        for r in range(3):
            assert _join(servicer, r, _report())
        for _ in range(3):
            assert servicer.report("worker", 1, msg.HostProbeReport(
                node_rank=1, report=_report(collective=350.0),
            ))
        verdicts = servicer.diagnosis.check(force=True)
        assert 1 in verdicts["hw"]
        diag = servicer.get("worker", 0, msg.DiagnosisRequest())
        assert 1 in diag.hw
        assert diag.hw[1]["leg"] == "collective"

    def test_straggler_exist_merges_health_verdicts(self):
        from dlrover_tpu.common import messages as msg

        servicer = _servicer()
        for r in range(3):
            assert _join(servicer, r, _report())
        assert _join(servicer, 3, _report(hbm=400.0))
        res = servicer.get("worker", 0, msg.StragglerExistRequest())
        assert 3 in res.nodes
        assert "3:hw" in res.reason

    def test_old_agent_join_without_report_still_admitted(self):
        """Wire compat: a pre-health-plane join (no probe_report field
        in the pickle) must pass the gate untouched."""
        from dlrover_tpu.common import messages as msg
        from dlrover_tpu.common.constants import RendezvousName

        servicer = _servicer()
        for r in range(3):
            req = msg.JoinRendezvousRequest(
                node_id=r, node_rank=r, local_world_size=1,
                rdzv_name=RendezvousName.ELASTIC_TRAINING,
            )
            del req.__dict__["probe_report"]  # old pickle shape
            assert servicer.report("worker", r, req)
        world = servicer.get("worker", 0, msg.CommWorldRequest(
            node_id=0, rdzv_name=RendezvousName.ELASTIC_TRAINING,
        ))
        assert sorted(world.world) == [0, 1, 2]


# -------------------------------------------------------------------------
# surfaces: dashboard payload + offline report
# -------------------------------------------------------------------------


class TestSurfaces:
    def test_report_payload_carries_health_summary(self):
        from dlrover_tpu.master.http_plane import MasterHttpPlane

        servicer = _servicer()
        for r in range(3):
            assert _join(servicer, r, _report())
        assert _join(servicer, 3, _report(hbm=400.0))
        plane = MasterHttpPlane(servicer)
        payload = plane.report_payload()
        assert "3" in payload["health"]["hosts"]
        assert payload["health"]["hosts"]["3"]["verdict"] in (
            "quarantine", "refuse",
        )
        assert payload["health"]["quarantined"] == [3]
        assert payload["health"]["hosts"]["0"]["legs"]["hbm"] > 0

    def test_obs_report_health_summary_replays_gate_events(self):
        from tools.obs_report import _health_summary

        timeline = [
            {"kind": "health.quarantine", "rank": 3,
             "reason": "hbm 4.0x fleet baseline", "t": 1.0},
            {"kind": "diagnosis.hw_degraded", "rank": 1,
             "leg": "collective", "t": 2.0},
            {"kind": "health.readmit", "rank": 3, "t": 3.0},
        ]
        health = _health_summary(timeline)
        # readmit cleared the standing entry; the events trail remains
        assert health["quarantined"] == {}
        assert len(health["events"]) == 3
        assert _health_summary([]) == {}

    def test_quarantine_banner_fires_loudly(self):
        from tools.obs_report import warn_hosts_quarantined

        report = {"health": {"quarantined": {
            3: {"verdict": "refuse", "reason": "hbm 4.0x fleet"},
        }}}
        out = io.StringIO()
        assert warn_hosts_quarantined(report, out=out)
        text = out.getvalue()
        assert "!!" in text and "host 3: refuse" in text
        assert not warn_hosts_quarantined({"health": {}}, out=out)
