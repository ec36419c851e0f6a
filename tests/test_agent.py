"""Elastic agent tests: echo-entrypoint workers against a real local
master (the reference pattern: test_elastic_training_agent.py drives the
agent with entrypoint="echo")."""

import os
import sys
import time

import pytest

from dlrover_tpu.agent.master_client import MasterClient
from dlrover_tpu.agent.node_check import run_node_check
from dlrover_tpu.agent.training_agent import (
    ElasticLaunchConfig,
    ElasticTrainingAgent,
    MasterRendezvousHandler,
    NodeCheckElasticAgent,
    WorkerSpec,
    classify_exit,
)
from dlrover_tpu.common.constants import (
    ExitCode,
    NodeEnv,
    NodeType,
    RendezvousName,
)


def make_client(master, node_id=0):
    return MasterClient(master.addr, node_id, NodeType.WORKER)


class TestClassifyExit:
    def test_success(self):
        assert classify_exit(0) == "succeeded"

    def test_software(self):
        assert classify_exit(1) == "software"

    def test_hardware_codes(self):
        assert classify_exit(ExitCode.DEVICE_ERROR) == "hardware"
        assert classify_exit(ExitCode.CORE_DUMP) == "hardware"

    def test_xla_log_pattern(self):
        assert (
            classify_exit(1, "jax XlaRuntimeError: INTERNAL something")
            == "hardware"
        )

    def test_oom(self):
        assert classify_exit(ExitCode.OOM) == "oom"
        assert classify_exit(-9) == "oom"


class TestRendezvousHandler:
    def test_single_node_rendezvous(self, local_master):
        client = make_client(local_master)
        try:
            handler = MasterRendezvousHandler(
                RendezvousName.ELASTIC_TRAINING, 0, client, 2, timeout=30
            )
            rnd, world, rank_offset, total, coordinator = (
                handler.next_rendezvous()
            )
            assert world == {0: 2}
            assert rank_offset == 0 and total == 2
            assert coordinator
        finally:
            client.close()

    def test_timeout(self, local_master_2nodes):
        client = make_client(local_master_2nodes)
        try:
            handler = MasterRendezvousHandler(
                RendezvousName.ELASTIC_TRAINING, 0, client, 1, timeout=3
            )
            with pytest.raises(TimeoutError):
                handler.next_rendezvous()  # second node never joins
        finally:
            client.close()


class TestElasticTrainingAgent:
    def _agent(self, master, entrypoint, args=(), **cfg_kw):
        config = ElasticLaunchConfig(
            min_nodes=1,
            max_nodes=1,
            nproc_per_node=cfg_kw.pop("nproc", 1),
            monitor_interval=0.3,
            rdzv_timeout=30,
            **cfg_kw,
        )
        client = make_client(master)
        spec = WorkerSpec(entrypoint, args, config)
        return ElasticTrainingAgent(config, spec, client), client

    def test_successful_run(self, local_master, tmp_path):
        script = tmp_path / "ok.py"
        script.write_text("print('hello from worker')\n")
        agent, client = self._agent(
            local_master, str(script), log_dir=str(tmp_path)
        )
        try:
            assert agent.run() == 0
            assert local_master.servicer.job_ended
        finally:
            client.close()

    def test_worker_env_contract(self, local_master, tmp_path):
        script = tmp_path / "env.py"
        script.write_text(
            "import os, json\n"
            "print(json.dumps({k: os.environ.get(k) for k in "
            "['RANK','WORLD_SIZE','LOCAL_RANK',"
            "'DLROVER_JAX_COORDINATOR_ADDR','DLROVER_JAX_NUM_PROCESSES']}))\n"
        )
        agent, client = self._agent(
            local_master, str(script), nproc=2, log_dir=str(tmp_path)
        )
        try:
            assert agent.run() == 0
            logs = sorted(p for p in os.listdir(tmp_path) if p.endswith(".log"))
            assert len(logs) == 2
            import json

            ranks = set()
            for log in logs:
                data = json.loads((tmp_path / log).read_text().strip())
                ranks.add(data["RANK"])
                assert data["WORLD_SIZE"] == "2"
                assert data["DLROVER_JAX_NUM_PROCESSES"] == "2"
            assert ranks == {"0", "1"}
        finally:
            client.close()

    def test_restart_on_software_failure(self, local_master, tmp_path):
        # fails on first attempt, succeeds after restart (state file)
        marker = tmp_path / "marker"
        script = tmp_path / "flaky.py"
        script.write_text(
            f"import os, sys\n"
            f"m = {str(marker)!r}\n"
            f"if not os.path.exists(m):\n"
            f"    open(m, 'w').close()\n"
            f"    sys.exit(1)\n"
            f"print('recovered')\n"
        )
        agent, client = self._agent(
            local_master, str(script), max_restarts=2, log_dir=str(tmp_path)
        )
        try:
            assert agent.run() == 0
            assert agent._restart_count == 1
        finally:
            client.close()

    def test_restarts_exhausted(self, local_master, tmp_path):
        script = tmp_path / "bad.py"
        script.write_text("import sys; sys.exit(1)\n")
        agent, client = self._agent(
            local_master, str(script), max_restarts=1, log_dir=str(tmp_path)
        )
        try:
            assert agent.run() == 1
            assert local_master.servicer.job_ended
            assert not local_master.servicer.job_success
        finally:
            client.close()

    def test_hardware_error_exits_agent(self, local_master, tmp_path):
        script = tmp_path / "hw.py"
        script.write_text(f"import sys; sys.exit({ExitCode.DEVICE_ERROR})\n")
        agent, client = self._agent(
            local_master, str(script), max_restarts=3, log_dir=str(tmp_path)
        )
        try:
            assert agent.run() == ExitCode.DEVICE_ERROR
            # no restart was attempted for a hardware fault
            assert agent._restart_count == 0
        finally:
            client.close()


class TestNodeCheck:
    def test_probe_runs_on_cpu_devices(self):
        normal, elapsed = run_node_check()
        assert normal
        assert elapsed > 0

    def test_mock_error_injection(self, monkeypatch):
        monkeypatch.setenv(NodeEnv.MOCK_ERR_RANK, "0")
        monkeypatch.setenv(NodeEnv.NODE_RANK, "0")
        normal, _ = run_node_check()
        assert not normal

    def test_node_check_agent_single_node(self, local_master):
        client = make_client(local_master)
        try:
            config = ElasticLaunchConfig(
                min_nodes=1, max_nodes=1, rdzv_timeout=30
            )
            checker = NodeCheckElasticAgent(config, client, rounds=2)
            assert checker.run()
        finally:
            client.close()


_POISONED = (
    "import sys; sys.modules['jax'] = None; "  # every `import jax` raises
    "from {module} import main; sys.exit(main({argv!r}))"
)


class TestAgentStaysOffJax:
    """One process per chip: a process that initialises a JAX backend
    owns the chip, so the agent — which must hand it to its workers —
    and the master may never import jax. The probe and node-check
    payloads run as child processes that have exited before a worker
    is spawned."""

    def test_join_network_check_and_monitor_never_import_jax(
        self, tmp_path
    ):
        """A real master and a real tpu-run agent, each in a fresh
        interpreter where ``import jax`` raises: network check (payload
        child), join (probe child), worker spawn, monitor ticks with
        the resource/heartbeat reporters beside them, clean finish."""
        import subprocess

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env = {
            **os.environ, "PYTHONPATH": repo, "JAX_PLATFORMS": "cpu",
            "DLROVER_TPU_SOCKET_DIR": str(tmp_path / "socks"),
            "ELASTIC_JOB_NAME": f"offjax{os.getpid()}",
        }
        env.pop("DLROVER_MASTER_ADDR", None)
        master = subprocess.Popen(
            [sys.executable, "-c", _POISONED.format(
                module="dlrover_tpu.master.main",
                argv=["--platform", "local", "--node_num", "1",
                      "--port", "0"],
            )],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
            text=True,
        )
        try:
            addr = ""
            for line in master.stdout:
                if line.startswith("DLROVER_MASTER_ADDR="):
                    addr = line.strip().partition("=")[2]
                    break
            assert addr, "poisoned master never came up"
            script = tmp_path / "ok.py"
            # outlive a few 5 s monitor ticks' worth of reporters
            script.write_text("import time; time.sleep(1.0)\n")
            agent = subprocess.run(
                [sys.executable, "-c", _POISONED.format(
                    module="dlrover_tpu.trainer.run",
                    argv=["--nnodes", "1", "--network-check",
                          "--auto-config", "--log-dir",
                          str(tmp_path / "logs"), str(script)],
                )],
                env={**env, "DLROVER_MASTER_ADDR": addr},
                capture_output=True, text=True, timeout=240,
            )
            assert agent.returncode == 0, agent.stderr[-3000:]
            # both payloads really ran — as children, not in the agent
            assert "hardware probe (child)" in agent.stderr
            assert "all workers succeeded" in agent.stderr
        finally:
            master.terminate()
            master.wait(timeout=30)


class TestNoSilentCpuFallback:
    def test_unpinned_worker_without_accelerator_fails_at_start(self):
        """JAX drops to the CPU with a warning when it cannot take the
        chip. A worker started WITHOUT ``JAX_PLATFORMS=cpu`` that finds
        no accelerator must fail at start-up with one message instead
        of training there in interpret mode."""
        import subprocess

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env = {**os.environ, "PYTHONPATH": repo}
        for key in ("JAX_PLATFORMS", "XLA_FLAGS"):
            env.pop(key, None)
        worker = subprocess.run(
            [sys.executable, "-c",
             "from dlrover_tpu.trainer import init_distributed; "
             "init_distributed(); print('TRAINING ON THE CPU')"],
            env=env, capture_output=True, text=True, timeout=240,
        )
        if worker.returncode == 0 and "fell back" not in worker.stderr:
            pytest.skip("an accelerator is attached to this machine")
        assert worker.returncode != 0
        assert "TRAINING ON THE CPU" not in worker.stdout
        assert "no accelerator" in worker.stderr
        # the same worker, pinned: the CPU is where it was asked to run
        pinned = subprocess.run(
            [sys.executable, "-c",
             "from dlrover_tpu.trainer import init_distributed; "
             "init_distributed(); print('pinned ok')"],
            env={**env, "JAX_PLATFORMS": "cpu"},
            capture_output=True, text=True, timeout=240,
        )
        assert pinned.returncode == 0, pinned.stderr[-2000:]


class TestRunConfigSharing:
    def test_late_joiner_adopts_rank0_flags(self, local_master):
        """Rank 0 publishes launch flags; a MISCONFIGURED later joiner's
        config object is rewritten by the adoption logic itself."""
        from dlrover_tpu.agent.training_agent import (
            ElasticLaunchConfig,
            _share_run_config,
        )

        client0 = make_client(local_master, 0)
        rank0_cfg = ElasticLaunchConfig(
            node_rank=0, nproc_per_node=4, network_check=True,
            node_unit=2,
        )
        _share_run_config(client0, rank0_cfg)

        client1 = make_client(local_master, 1)
        fat_fingered = ElasticLaunchConfig(
            node_rank=1, nproc_per_node=8, network_check=False,
            node_unit=1,
        )
        _share_run_config(client1, fat_fingered, wait=10)
        assert fat_fingered.nproc_per_node == 4
        assert fat_fingered.network_check is True
        assert fat_fingered.node_unit == 2
        client0.close()
        client1.close()

    def test_unpublished_config_keeps_local_flags(self, local_master):
        from dlrover_tpu.agent.training_agent import (
            ElasticLaunchConfig,
            _share_run_config,
        )

        client = make_client(local_master, 1)
        cfg = ElasticLaunchConfig(node_rank=1, nproc_per_node=3)
        _share_run_config(client, cfg, wait=1.0)
        assert cfg.nproc_per_node == 3
        client.close()
