"""The legs of set-up and of a resume, as fixtures beside the metrics
they will be: data files for the ``counter_value`` reader
(``reader/counter_value.py``), read from the counters ``<leg>_s`` that
the program's start-up legs leave (``dlrover_tpu/common/tracing.py``,
``Legs``). They enter the manifest with the ``benchmark`` issue that
moves them out of ``tests/`` (PERF.md section 7)."""

import json
import os

import lookup
from test_rehearsal import BENCH, _not_printed, _run, _workload

SETUP_LEGS = ("setup_exec_s", "setup_imports_s", "setup_backend_s",
              "setup_trainer_init_s", "setup_compile_s",
              "setup_first_step_s")
RESUME_LEGS = ("resume_detect_s", "resume_start_s", "restore_s",
               "resume_first_step_s")


def _metric(name):
    with open(os.path.join(BENCH, "tests", "layer_metrics",
                           name + ".json")) as f:
        return json.load(f)


def test_fixture_files_are_metric_files():
    for name in (*SETUP_LEGS, "compile_cache_hits", *RESUME_LEGS):
        data = _metric(name)
        assert set(data) == {"layer", "unit", "better", "source", "moves",
                             "what", "reader"}
        assert data["source"] == "program_counter"
        assert data["reader"]["name"] == "counter_value"
        assert data["moves"] == (
            "resume_s" if name in RESUME_LEGS else "setup_s"
        )


def test_counter_value_reads_one_counter_or_a_sum():
    read = lookup.module("reader", "counter_value").read
    record = {"counters": {"start.compile_s": 2.0, "start.first_step_s": 0.5}}
    assert read(record, {"counter": "start.compile_s"}) == 2.0
    assert read(record, {"counter": "start.compile_s", "scale": 1e3}) == 2e3
    assert read(record, _metric("resume_first_step_s")["reader"]) == 2.5
    # a program without the counter (the parent of the PR that brought
    # it): nothing, and no error
    assert read(record, {"counter": "start.backend_s"}) is None
    assert read({}, {"counter": "start.backend_s"}) is None
    assert read(record, _metric("resume_detect_s")["reader"]) is None


def test_toy_cell_reads_every_leg_of_its_set_up():
    # (the first run also fills the compile cache the second one hits)
    proc, _result = _run("toy.startup", seconds=1.5)
    assert proc.returncode == 0, proc.stderr[-2000:]
    setup_s = _not_printed(proc)["setup_s"]["value"]
    proc, result = _run("toy.startup", trace=1, seconds=3)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert result["correct"] and result["metrics"] == {}
    read = _not_printed(proc)
    assert set(read) == set(_workload("toy.startup")["per_layer"])
    assert all(m["value"] > 0 for m in read.values()), read
    # the legs are parts of set-up: the agreement check and the
    # warm-up steps make up the rest of it
    legs = sum(read[name]["value"] for name in SETUP_LEGS)
    assert legs < setup_s, (legs, setup_s, read)
