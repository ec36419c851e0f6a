"""Every program a document tells a user to run starts.

``--help`` imports the program's module-level dependencies and builds
its argument parser, so a dangling import of a deleted module, or a
parser that no longer builds, fails here and not in front of a user.
"""

import os
import subprocess
import sys

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

ENTRY_POINTS = [
    ["benchmark/run.py"],
    ["chip_smoke.py"],
    ["tools/parse_profile.py"],
    ["tools/obs_report.py"],
    ["tools/chaos_run.py"],
    ["tools/lint.py"],
    ["tools/race_run.py"],
    ["-m", "dlrover_tpu.trainer.run"],
    ["-m", "dlrover_tpu.master.main"],
]


@pytest.mark.parametrize("program", ENTRY_POINTS, ids=lambda p: p[-1])
def test_help_exits_zero(program):
    done = subprocess.run(
        [sys.executable, *program, "--help"],
        cwd=REPO_ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True,
        text=True,
        timeout=60,
    )
    assert done.returncode == 0, done.stderr[-2000:]
    assert "usage" in done.stdout.lower()
