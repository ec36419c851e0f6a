"""Tests for the hang detector, CRD schema layer, and ray backend
gating — reference coverage analogue: atorch fault_tolerance tests and
operator controller tests.
"""

import time

import pytest

from dlrover_tpu.scheduler.crd import (
    ElasticJobSpec,
    ReplicaSpec,
    ScalePlanSpec,
)
from dlrover_tpu.trainer.fault_tolerance import HangingDetector


class TestHangingDetector:
    def test_no_hang_with_progress(self):
        det = HangingDetector(timeout=0.3, check_interval=0.05)
        det.report_progress(1)
        assert not det.is_hanging()

    def test_detects_stall_and_fires_callback(self):
        fired = []
        det = HangingDetector(
            timeout=0.15, check_interval=0.05,
            on_hang=lambda: fired.append(1),
        )
        det.start()
        try:
            time.sleep(0.5)
            assert fired, "hang callback never fired"
            # callback fires once per stall, not every interval
            assert len(fired) == 1
        finally:
            det.stop()

    def test_progress_resets_hang_state(self):
        fired = []
        det = HangingDetector(
            timeout=0.15, check_interval=0.05,
            on_hang=lambda: fired.append(1),
        )
        det.start()
        try:
            time.sleep(0.4)
            n = len(fired)
            assert n >= 1
            det.report_progress(2)
            time.sleep(0.4)
            assert len(fired) >= n + 1  # stalls again -> fires again
        finally:
            det.stop()

    def test_same_step_does_not_count_as_progress(self):
        det = HangingDetector(timeout=0.2)
        det.report_progress(5)
        time.sleep(0.3)
        det.report_progress(5)  # stuck at same step
        assert det.is_hanging()

    def test_reset_progress_clears_stale_clock(self):
        """A restart right after a long checkpoint restore must not be
        misclassified as a hang: reset_progress restarts the stall
        clock without claiming a training step."""
        det = HangingDetector(timeout=0.2)
        det.report_progress(5)
        time.sleep(0.3)
        assert det.is_hanging()
        det.reset_progress("checkpoint-restore")
        assert not det.is_hanging()
        # the step counter is untouched: the NEXT step still counts as
        # progress even though it is > last reported step
        assert det._last_step == 5
        det.report_progress(6)
        assert not det.is_hanging()

    def test_notify_progress_reset_reaches_active_detectors(self):
        from dlrover_tpu.trainer.fault_tolerance import (
            notify_progress_reset,
        )

        fired = []
        det = HangingDetector(
            timeout=0.25, check_interval=0.05,
            on_hang=lambda: fired.append(1),
        )
        det.start()
        try:
            for _ in range(4):
                time.sleep(0.15)
                notify_progress_reset("rendezvous-resume")
            assert not det.is_hanging()
            assert not fired, "resume resets did not suppress the hang"
        finally:
            det.stop()

    def test_stopped_detector_not_resettable_via_registry(self):
        from dlrover_tpu.trainer import fault_tolerance as ft

        det = HangingDetector(timeout=0.2)
        det.start()
        det.stop()
        assert det not in ft._ACTIVE

    def test_trainer_restore_resets_hang_clock(self, monkeypatch):
        """maybe_resume's restore path must call notify_progress_reset
        (wired via the module hook) — asserted through a started
        detector whose clock predates the 'restore'."""
        det = HangingDetector(timeout=0.2)
        det.start()
        try:
            det._last_progress -= 10.0  # simulate a long restore
            assert det.is_hanging()
            from dlrover_tpu.trainer.fault_tolerance import (
                notify_progress_reset,
            )

            notify_progress_reset("checkpoint-restore")
            assert not det.is_hanging()
        finally:
            det.stop()

    def test_reports_to_master(self, local_master):
        from dlrover_tpu.agent.master_client import MasterClient
        from dlrover_tpu.common.constants import NodeType

        client = MasterClient(local_master.addr, 0, NodeType.WORKER)
        det = HangingDetector(
            timeout=0.1, check_interval=0.05, master_client=client
        )
        det.start()
        try:
            time.sleep(0.4)
            node = local_master.job_manager.get_node(NodeType.WORKER, 0)
            assert node is not None
        finally:
            det.stop()


class TestCrdSchemas:
    def make_job(self):
        return ElasticJobSpec(
            job_name="llama-train",
            distribution_strategy="AllreduceStrategy",
            replica_specs={
                "worker": ReplicaSpec(
                    replicas=8, cpu=8, memory_mb=32768, tpu_chips=4,
                    image="dlrover-tpu:latest",
                    command=["tpu-run", "train.py"],
                )
            },
        )

    def test_elasticjob_roundtrip(self):
        job = self.make_job()
        manifest = job.to_manifest()
        assert manifest["kind"] == "ElasticJob"
        back = ElasticJobSpec.from_manifest(manifest)
        assert back.job_name == "llama-train"
        w = back.replica_specs["worker"]
        assert w.replicas == 8
        assert w.memory_mb == 32768
        assert w.tpu_chips == 4
        assert w.command == ["tpu-run", "train.py"]

    def test_yaml_emission(self):
        y = self.make_job().to_yaml()
        assert 'kind: "ElasticJob"' in y
        assert '"llama-train"' in y
        assert "replicas: 8" in y
        # yaml must be indentation-consistent (spot check nesting)
        assert '\n  name: "llama-train"' in y

    def test_scaleplan_roundtrip(self):
        plan = ScalePlanSpec(
            job_name="llama-train",
            replica_counts={"worker": 12},
            node_resources={"worker-3": {"memory": "64Gi"}},
        )
        back = ScalePlanSpec.from_manifest(plan.to_manifest())
        assert back.job_name == "llama-train"
        assert back.replica_counts["worker"] == 12
        assert back.node_resources["worker-3"]["memory"] == "64Gi"
        assert back.manual


class TestQuantityParsing:
    def test_cpu(self):
        from dlrover_tpu.scheduler.crd import parse_cpu_quantity

        assert parse_cpu_quantity("500m") == 0.5
        assert parse_cpu_quantity("2") == 2.0
        assert parse_cpu_quantity(4) == 4.0
        assert parse_cpu_quantity("") == 0.0

    def test_memory(self):
        from dlrover_tpu.scheduler.crd import parse_memory_quantity_mb

        assert parse_memory_quantity_mb("32Gi") == 32 * 1024
        assert parse_memory_quantity_mb("512Mi") == 512
        assert parse_memory_quantity_mb("2048Ki") == 2
        assert parse_memory_quantity_mb(1 << 30) == 1024  # bytes
        assert parse_memory_quantity_mb("") == 0

    def test_real_cr_parses(self):
        from dlrover_tpu.scheduler.crd import ReplicaSpec

        spec = ReplicaSpec.from_dict({
            "replicas": 2,
            "template": {"spec": {"containers": [{
                "image": "x",
                "resources": {"requests": {
                    "cpu": "500m", "memory": "32Gi",
                }},
            }]}},
        })
        assert spec.cpu == 0.5
        assert spec.memory_mb == 32 * 1024


class TestRayGating:
    def test_availability_probe(self):
        from dlrover_tpu.scheduler import ray as ray_backend

        # image has no ray: the probe must say so without raising
        avail = ray_backend.ray_available()
        assert isinstance(avail, bool)
        if not avail:
            with pytest.raises(ImportError, match="ray"):
                ray_backend.RayClient()
