"""The readers a per-layer metric file may name.

A per-layer metric is a data file under ``layer_metrics/``: ``layer``,
``unit``, ``better``, ``source``, ``moves``, ``what`` and a ``reader``
with its parameters. A workload file lists the metrics its cell reports.
The small fixed set of readers lives here, once; a later PR that adds
a span or a counter to the program adds a file there and no code. Each
reader takes the run's record (``run.py`` fills it) and its own
parameters, and returns a number or ``None``: a reader that finds
nothing to read returns nothing and the metric is left out of the line.

The record, a dict:
  trace         the reduction of trace_reduce.reduce(), or None
  traced_steps  train steps inside the traced stretch
  saves         one dict per save of the window: stall_s, ok, and the
                keys of engine.last_save_stats (bytes, materialize_s,
                fill_s)
  memory_stats  device.memory_stats() of the fullest chip, after the
                window
  step_seconds  per-step seconds of the window's sync intervals
                (none holds a save) outside the profiler's stretch
  tokens_per_s  tokens per second of the median of those intervals
                (None on a CPU)
  flops_per_token, peak_flops, chips
"""

from __future__ import annotations

import statistics

import trace_reduce


def _scaled(value, params):
    return None if value is None else value * params.get("scale", 1.0)


def trace_op_ms(record, params):
    """Device milliseconds per train step of the events on one line of
    the device plane whose name matches ``pattern``."""
    trace, steps = record.get("trace"), record.get("traced_steps")
    if not trace or not steps:
        return None
    table = trace["modules_s" if params.get("line") == "modules" else "ops_s"]
    seconds = trace_reduce.matching_seconds(table, params["pattern"])
    return None if seconds is None else seconds * 1e3 / steps


def trace_idle(record, params):
    """Idle share of the traced stretch, percent: 1 - busy / window."""
    trace = record.get("trace")
    if not trace or trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])


def stats_key(record, params):
    """A key of ``device.memory_stats()`` (source ``memory``) or the
    median over the window's saves of a key of the save's record
    (source ``saves``), optionally divided by another key of the same
    save (``per``), times ``scale``."""
    if params["source"] == "memory":
        return _scaled((record.get("memory_stats") or {}).get(params["key"]),
                       params)
    values = []
    for save in record.get("saves") or []:
        value = save.get(params["key"])
        if value is None:
            continue
        if "per" in params:
            if not save.get(params["per"]):
                continue
            value = value / save[params["per"]]
        values.append(value)
    return _scaled(statistics.median(values), params) if values else None


def step_quantile_ms(record, params):
    """Quantile ``q`` (of 20, inclusive method: 19 is the 95th
    percentile) of the per-step milliseconds of the run's sync
    intervals."""
    steps = record.get("step_seconds") or []
    if len(steps) < 2:
        return None
    return 1e3 * statistics.quantiles(
        steps, n=20, method="inclusive"
    )[params["q"] - 1]


def step_rate(record, params):
    """Tokens per second of the run's median sync interval."""
    return record.get("tokens_per_s")


def derived_mfu(record, params):
    """Required FLOPs per token x this run's tokens per second over
    chips x the table's peak, percent."""
    rate, peak = record.get("tokens_per_s"), record.get("peak_flops")
    if not rate or not peak:
        return None
    return 100.0 * record["flops_per_token"] * rate / (record["chips"] * peak)


READERS = {
    f.__name__: f for f in (
        trace_op_ms, trace_idle, stats_key, step_quantile_ms, step_rate,
        derived_mfu,
    )
}


def read(metric: dict, record: dict):
    reader = dict(metric["reader"])
    name = reader.pop("name")
    if name not in READERS:
        raise ValueError(f"unknown reader {name!r} (has {sorted(READERS)})")
    return READERS[name](record, reader)
