"""The save path's own breakdown, as fixtures beside the four save
metrics: data files for the existing ``stats_key`` reader, read from
``engine.last_save_stats`` through the save's record. They enter the
manifest with the save cell (PERF.md section 7, cell 3)."""

import json
import os

from test_rehearsal import BENCH, _not_printed, _run, _workload

FIXTURES = ("save_reserve_s", "save_first_leaf_s", "save_slowest_leaf_s",
            "save_large_leaf_gbps")


def test_fixture_files_are_metric_files():
    for name in FIXTURES:
        with open(os.path.join(BENCH, "tests", "layer_metrics",
                               name + ".json")) as f:
            data = json.load(f)
        assert set(data) == {"layer", "unit", "better", "source", "moves",
                             "what", "reader"}
        assert data["source"] == "program_span"
        assert data["reader"]["name"] == "stats_key"


def test_toy_save_cell_reads_the_drain():
    proc, result = _run("toy.save-drain", trace=1, seconds=5)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert result["correct"] and result["metrics"] == {}
    read = _not_printed(proc)
    listed = set(_workload("toy.save-drain")["per_layer"])
    # a toy state holds no shard of 64 MiB: that one reads nothing
    assert set(read) == listed - {"save_large_leaf_gbps"}
    assert read["save_first_leaf_s"]["value"] <= \
        read["save_slowest_leaf_s"]["value"] <= \
        read["save_materialize_s"]["value"]
    assert read["save_reserve_s"]["value"] < read["save_stall_s"]["value"]
