"""run.py end to end on the CPU at toy size: control flow only.

The toy cells are files under ``benchmark/tests``; the four-device one
shows that a sharded cell is a configuration file and a workload file
and no change to ``run.py`` (PERF.md, Open questions, cell 1)."""

import json
import os
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def _run(workload, trace=0, devices=1, pin=True, seconds=1.5):
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    if pin:
        env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         workload, "--seed", "2147483659", "--seconds", str(seconds),
         "--trace", str(trace)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600,
    )
    lines = proc.stdout.strip().splitlines()
    return proc, (json.loads(lines[-1]) if lines else None)


@pytest.mark.parametrize("workload,devices", [
    ("toy.steady", 1), ("toy.save", 1), ("toy-gpt2.save", 1),
    ("toy-fsdp4.steady", 4),
])
def test_toy_cell(workload, devices):
    proc, result = _run(workload, devices=devices)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert set(result) >= {"correct", "attempted", "failed", "metrics",
                           "device"}
    assert result["correct"], result
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["device"]["platform"] == "cpu"
    assert result["device"]["count"] == devices
    # a CPU run prints no device metric
    assert result["metrics"] == {}
    assert not os.path.exists(os.path.join(ROOT, ".bench_run", workload))
    assert not [f for f in os.listdir("/dev/shm") if "_bench" in f]
    # what the run read is on standard error, under the names the
    # workload file gives
    read = _not_printed(proc)
    assert set(read) == set(_workload(workload)["reports"]) | {"setup_s"}
    assert all(m["value"] > 0 for m in read.values())


def _workload(name):
    with open(os.path.join(BENCH, "tests", "workloads", name + ".json")) as f:
        return json.load(f)


def _not_printed(proc):
    line = [x for x in proc.stderr.splitlines()
            if "rehearsal, not printed:" in x][-1]
    return json.loads(line.split("not printed:", 1)[1])


@pytest.mark.parametrize("workload,devices", [
    ("toy.save", 1), ("toy-fsdp4.steady", 4),
])
def test_traced_toy_cell_prints_no_number(workload, devices):
    """The per-layer metrics of a cell are those its workload file
    lists: the four-device toy needs no line of code for its own."""
    proc, result = _run(workload, trace=1, devices=devices, seconds=5)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert result["correct"] and result["metrics"] == {}
    assert "busy_s" not in result["device"]
    # the readers that need no device trace found their numbers; the
    # CPU has no device plane, so the trace's readers return nothing
    read, listed = set(_not_printed(proc)), _workload(workload)["per_layer"]
    assert read <= set(listed)
    assert read >= {n for n in listed
                    if n.startswith(("save_", "step_p95", "peak_hbm"))} \
        - {"peak_hbm_gb", "peak_hbm_gb.saving"}


def test_real_cells_refuse_the_cpu():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cell = json.load(f)["workloads"][0]["name"]
    proc, result = _run(cell)
    assert proc.returncode != 0 and result is None


def test_fewer_devices_than_the_cell_needs():
    proc, result = _run("toy-fsdp4.steady", devices=2)
    assert proc.returncode != 0 and result is None
