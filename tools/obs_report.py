"""Operator-facing observability report: goodput ledger, merged event
timeline, and metrics — from telemetry snapshot files and/or a live
master.

Usage:
    # from a snapshot directory (DLROVER_TELEMETRY_DIR of the run)
    python tools/obs_report.py --dir /path/to/telemetry

    # from a live master (the servicer's telemetry query)
    python tools/obs_report.py --master 127.0.0.1:12345

    # render the cross-host span trees (rendezvous rounds, restores,
    # shard dispatches — parent/child nesting across processes)
    python tools/obs_report.py --dir ... --trace

    # embed the XPlane per-category breakdown of a capture's trace
    python tools/obs_report.py --dir ... --trace-dir <artifact>/trace --steps 2

    # live view: poll a running master every 2 s — compact goodput /
    # step-time / MFU tiles with text sparklines from the master's
    # tiered metrics store, breaches and recent events underneath
    python tools/obs_report.py --master 127.0.0.1:12345 --live

    # machine-readable
    python tools/obs_report.py --dir ... --json
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def build_report(
    telemetry_dir: str | None = None,
    master_addr: str | None = None,
    trace_dir: str | None = None,
    steps: int = 1,
    now: float | None = None,
) -> dict:
    """Merge snapshots from a directory and/or a live master into one
    report dict: {sources, ledger, timeline, metrics[, profile]}."""
    from dlrover_tpu.common.telemetry import JobTelemetry

    jt = JobTelemetry() if telemetry_dir is None else JobTelemetry.from_dir(
        telemetry_dir
    )
    if master_addr:
        from dlrover_tpu.agent.master_client import MasterClient

        client = MasterClient(master_addr, 0, "tool")
        try:
            remote = client.get_telemetry_report()
        finally:
            client.close()
        for snap in (remote.get("snapshots") or {}).values():
            jt.update(snap)
    report = jt.report(now=now)
    # raw snapshots are an input detail, not operator output
    report.pop("snapshots", None)
    report["restore"] = _restore_summary(report.get("metrics", {}))
    report["reshape"] = _reshape_summary(
        report.get("metrics", {}), report.get("ledger", {})
    )
    report["control_plane"] = _control_plane_summary(
        report.get("metrics", {}), report.get("ledger", {})
    )
    report["brain"] = _brain_summary(
        report.get("metrics", {}), report.get("timeline", [])
    )
    report["serving"] = _serving_summary(
        report.get("metrics", {}), report.get("ledger", {})
    )
    report["profiling"] = _profiling_summary(
        report.get("metrics", {}), report.get("timeline", [])
    )
    report["health"] = _health_summary(report.get("timeline", []))
    from dlrover_tpu.common.tracing import root_legs

    report["resume_legs"] = root_legs(report.get("timeline", []))
    if trace_dir:
        try:
            from dlrover_tpu.common.trace_summary import summarize

            report["profile"] = summarize(trace_dir, steps=steps)
        except ImportError as e:
            report["profile_error"] = f"xprof toolchain unavailable: {e}"
        except Exception as e:  # noqa: BLE001 - a broken trace must not
            # take the goodput report down with it
            report["profile_error"] = f"trace parse failed: {e}"
    return report


def _control_plane_summary(metrics: dict, ledger: dict) -> dict:
    """The master's control-plane latency surface: per-verb servicer
    histograms (``master.rpc.seconds``) collapsed into the headline
    keys — ``master_rpc_p99_ms`` and ``joins_per_sec`` — the baseline
    future swarm-scale work regresses against."""
    from dlrover_tpu.common.telemetry import (
        hist_quantile,
        sum_bucket_counts,
    )

    hists = [
        h for h in metrics.get("histograms", ())
        if h["name"] == "master.rpc.seconds"
    ]
    bounds, overall = sum_bucket_counts(hists)
    if bounds is None:
        return {}
    per_verb: dict = {}
    joins = 0
    for h in hists:
        if h["bounds"] != bounds:
            continue
        per_verb.setdefault(h["labels"].get("verb", "?"), []).append(h)
        if h["labels"].get("msg") == "JoinRendezvousRequest":
            joins += h["count"]
    per_verb = {
        verb: sum_bucket_counts(series)[1]
        for verb, series in per_verb.items()
    }
    total_s = float(ledger.get("total_s") or 0.0)
    out = {
        "master_rpc_calls": sum(overall),
        "master_rpc_p50_ms": round(
            hist_quantile(bounds, overall, 0.50) * 1e3, 3
        ),
        "master_rpc_p99_ms": round(
            hist_quantile(bounds, overall, 0.99) * 1e3, 3
        ),
        "joins_total": joins,
        "joins_per_sec": round(joins / total_s, 3) if total_s > 0 else 0.0,
    }
    for verb, counts in sorted(per_verb.items()):
        out[f"rpc_{verb}_p99_ms"] = round(
            hist_quantile(bounds, counts, 0.99) * 1e3, 3
        )
    return out


def _reshape_summary(metrics: dict, ledger: dict) -> dict:
    """In-process mesh reshapes (restart-free elasticity) at a glance:
    the ledger's ``reshape`` bucket plus the per-event counters/gauges
    the elastic trainer publishes (count, shards moved vs. pulled from
    checkpoint, last event wall-clock)."""
    out: dict = {}
    for c in metrics.get("counters", ()):
        if c["name"].startswith("elastic.reshape"):
            out[c["name"]] = c["value"]
    for g in metrics.get("gauges", ()):
        if g["name"].startswith("elastic.reshape"):
            out[g["name"]] = g["value"]
    reshape_s = (ledger.get("categories") or {}).get("reshape", 0.0)
    if reshape_s or out:
        out["ledger_reshape_s"] = round(float(reshape_s), 3)
    return out


def _brain_summary(metrics: dict, timeline: list) -> dict:
    """Repair-brain actions at a glance: plan counters (decided /
    executing / done / abandoned per kind), the published checkpoint
    cadence, and the recent ``brain.plan.*`` transition tail with
    outcomes — the offline twin of the dashboard's brain panel."""
    out: dict = {"counters": {}, "plans": []}
    for c in metrics.get("counters", ()):
        if c["name"].startswith("brain."):
            labels = c.get("labels") or {}
            label_s = ",".join(
                f"{k}={v}" for k, v in sorted(labels.items())
            )
            key = c["name"] + (f"{{{label_s}}}" if label_s else "")
            out["counters"][key] = c["value"]
    for g in metrics.get("gauges", ()):
        if g["name"].startswith("brain."):
            out["counters"][g["name"]] = g["value"]
    for ev in timeline:
        kind = str(ev.get("kind", ""))
        if not kind.startswith("brain.plan."):
            continue
        out["plans"].append({
            "t": ev.get("t"),
            "plan": ev.get("plan"),
            "plan_kind": ev.get("plan_kind", ""),
            "transition": kind.rsplit(".", 1)[-1],
            "target": ev.get("target"),
        })
    # keep the tail: the dashboards show the last K, so does the report
    out["plans"] = out["plans"][-16:]
    if not out["counters"] and not out["plans"]:
        return {}
    return out


def _serving_summary(metrics: dict, ledger: dict) -> dict:
    """The serving arm at a glance: decode-pool counters/gauges
    (queue depth, requests by state, per-worker TTFT), merged TTFT
    percentiles from the ``serve.ttft.seconds`` histograms, and the
    throughput headline (``serve_tokens_per_s``) — the offline twin of
    the dashboard's serving panel."""
    from dlrover_tpu.common.telemetry import (
        hist_quantile,
        sum_bucket_counts,
    )

    out: dict = {}
    tokens_total = 0.0
    for c in metrics.get("counters", ()):
        if not c["name"].startswith("serve."):
            continue
        labels = c.get("labels") or {}
        label_s = ",".join(
            f"{k}={v}" for k, v in sorted(labels.items())
        )
        out[c["name"] + (f"{{{label_s}}}" if label_s else "")] = (
            c["value"]
        )
        if c["name"] == "serve.tokens":
            tokens_total += float(c["value"])
    for g in metrics.get("gauges", ()):
        if g["name"].startswith(("serve.", "brain.serve.")):
            out[g["name"]] = g["value"]
    hists = [
        h for h in metrics.get("histograms", ())
        if h["name"] == "serve.ttft.seconds"
    ]
    bounds, overall = sum_bucket_counts(hists)
    if bounds is not None:
        out["serve_ttft_p50_ms"] = round(
            hist_quantile(bounds, overall, 0.50) * 1e3, 3
        )
        out["serve_ttft_p99_ms"] = round(
            hist_quantile(bounds, overall, 0.99) * 1e3, 3
        )
    total_s = float(ledger.get("total_s") or 0.0)
    if tokens_total and total_s > 0:
        out["serve_tokens_per_s"] = round(tokens_total / total_s, 3)
    return out


def _profiling_summary(metrics: dict, timeline: list) -> dict:
    """The deep-profiling plane at a glance: per-category device time
    from the always-on sampler (``device.optime_ms{category=...}``),
    sample/capture counters, and the recent ``device.optime.
    regression`` / ``prof.capture.*`` event tail — the offline twin of
    the dashboard's captures panel."""
    out: dict = {}
    for g in metrics.get("gauges", ()):
        if not g["name"].startswith("device.optime"):
            continue
        cat = (g.get("labels") or {}).get("category")
        key = g["name"] + (f"{{category={cat}}}" if cat else "")
        out[key] = g["value"]
    for c in metrics.get("counters", ()):
        if c["name"].startswith("prof."):
            labels = c.get("labels") or {}
            label_s = ",".join(
                f"{k}={v}" for k, v in sorted(labels.items())
            )
            out[c["name"] + (f"{{{label_s}}}" if label_s else "")] = (
                c["value"]
            )
    events = [
        {
            "t": ev.get("t"),
            "kind": ev.get("kind"),
            "capture": ev.get("capture"),
            "category": ev.get("category"),
            "delta_pct": ev.get("delta_pct"),
        }
        for ev in timeline
        if str(ev.get("kind", "")).startswith(
            ("device.optime.regression", "prof.capture.")
        )
    ][-16:]
    if not out and not events:
        return {}
    return {"metrics": out, "events": events}


def _health_summary(timeline: list) -> dict:
    """The hardware health plane from the timeline: per-host standing
    verdict replayed from ``health.quarantine`` / ``health.refuse`` /
    ``health.readmit`` gate events plus ``diagnosis.hw_degraded``
    verdicts — the offline twin of the dashboard's host-health panel
    (live fingerprints/sparklines ride ``/report.json`` instead)."""
    standing: dict[int, dict] = {}
    events = []
    for ev in timeline:
        kind = str(ev.get("kind", ""))
        if not kind.startswith(("health.", "diagnosis.hw_degraded")):
            continue
        rank = ev.get("rank")
        events.append({
            "t": ev.get("t"), "kind": kind, "rank": rank,
            "reason": ev.get("reason") or ev.get("leg"),
        })
        if rank is None:
            continue
        rank = int(rank)
        if kind in ("health.quarantine", "health.refuse"):
            standing[rank] = {
                "verdict": kind.split(".", 1)[1],
                "reason": ev.get("reason", ""),
            }
        elif kind == "health.readmit":
            standing.pop(rank, None)
    if not standing and not events:
        return {}
    return {"quarantined": standing, "events": events[-16:]}


def warn_hosts_quarantined(report: dict, out=None) -> bool:
    """LOUD banner when any host stands quarantined/refused at the
    health gate: the job is running without it, and a report that
    buries that reads as a healthy fleet. Returns True when it
    fired."""
    standing = (report.get("health") or {}).get("quarantined") or {}
    if not standing:
        return False
    out = sys.stderr if out is None else out
    print("!" * 66, file=out)
    print(
        "!! WARNING: host(s) parked at the hardware health gate "
        "(probe\n!! timings vs fleet/own baseline) — the job is "
        "running without:", file=out,
    )
    for rank, info in sorted(standing.items()):
        print(
            f"!!   host {rank}: {info['verdict']} ({info['reason']})",
            file=out,
        )
    print("!" * 66, file=out)
    return True


def _restore_summary(metrics: dict) -> dict:
    """Checkpoint data-path health at a glance: the staged restore
    pipeline's per-leg throughput gauges (read / verify / h2d), the
    save fill leg, and host-arena reuse counters."""
    out: dict = {}
    for g in metrics.get("gauges", ()):
        if g["name"].startswith(("ckpt.restore.", "ckpt.save.fill",
                                 "ckpt.arena.")):
            out[g["name"]] = g["value"]
    for c in metrics.get("counters", ()):
        if c["name"].startswith("ckpt.arena."):
            out[c["name"]] = c["value"]
    return out


def warn_events_dropped(report: dict, out=None) -> bool:
    """LOUD warning when any source's bounded timeline ring overwrote
    its tail: the merged timeline (and everything derived from it —
    the ledger's event intervals, the trace forest) is silently
    missing that source's oldest events, and a truncated report must
    never read as a complete one. Returns True when it fired."""
    dropped = report.get("events_dropped") or {}
    if not dropped:
        return False
    out = sys.stderr if out is None else out
    print("!" * 66, file=out)
    print(
        "!! WARNING: timeline events were DROPPED (bounded ring "
        "overflow);\n!! the merged timeline and ledger intervals are "
        "INCOMPLETE for:", file=out,
    )
    for source, n in sorted(dropped.items()):
        print(f"!!   {source}: {n} event(s) lost", file=out)
    print("!" * 66, file=out)
    return True


# -------------------------------------------------------- capture trigger


def run_capture(
    master_addr: str, node_rank: int, steps: int = 0,
    wait: float = 120.0, out=None, poll: float = 1.0,
) -> int:
    """Operator front door of the deep-capture plane: ask the master's
    CaptureManager to profile ``node_rank``, then poll the ledger until
    the artifact lands (or the wait expires). Prints the record incl.
    the attribution diff vs the stored op-cost baseline."""
    from dlrover_tpu.agent.master_client import MasterClient

    out = sys.stdout if out is None else out
    client = MasterClient(master_addr, 0, "tool")
    try:
        ack = client.request_capture(
            node_rank, steps=steps, reason="operator:obs_report"
        )
        if not ack.accepted:
            print(f"capture refused: {ack.reason}", file=sys.stderr)
            return 1
        cid = ack.capture_id
        print(f"capture {cid} accepted for host {node_rank}; "
              f"waiting for the artifact...", file=out)
        deadline = time.time() + wait
        rec = None
        while time.time() < deadline:
            rec = next(
                (r for r in client.list_captures() if r["id"] == cid),
                None,
            )
            if rec is not None and rec["state"] in ("done", "failed"):
                break
            time.sleep(poll)
        if rec is None or rec["state"] not in ("done", "failed"):
            print(f"capture {cid} still "
                  f"{rec['state'] if rec else 'unknown'} after "
                  f"{wait:.0f}s", file=sys.stderr)
            return 1
        print(json.dumps(rec, indent=2), file=out)
        if rec["state"] != "done":
            return 1
        attribution = (rec.get("summary") or {}).get("attribution") or []
        for a in attribution[:5]:
            delta = a.get("delta_pct")
            print(
                f"  {a['category']:<20} {a['current_ms']:9.3f} ms/step"
                f"  vs baseline {a['baseline_ms']:9.3f}"
                + (f"  ({delta:+.1f}%)" if delta is not None else
                   "  (new)"),
                file=out,
            )
        return 0
    finally:
        client.close()


def write_perfetto(report: dict, out_path: str,
                   trace_dir: str | None = None) -> str:
    """Merge the report's host timeline (span forest included) with
    the device side — the ``--trace-dir`` XPlane capture when given —
    into one Perfetto/Chrome-trace JSON file."""
    from dlrover_tpu.common import profiling

    device_categories = None
    device_trace = None
    if trace_dir:
        device_trace = profiling.device_trace_from_xplane(trace_dir)
        profile = report.get("profile") or {}
        device_categories = profile.get("by_canonical_category")
    merged = profiling.merge_perfetto(
        report.get("timeline", []),
        device_categories=device_categories,
        device_trace_events=device_trace,
    )
    with open(out_path, "w") as f:
        json.dump(merged, f)
    return out_path


# ---------------------------------------------------------------- live mode

_SPARK_CHARS = "▁▂▃▄▅▆▇█"


def sparkline(values, width: int = 48) -> str:
    """Text sparkline of the newest ``width`` values."""
    vals = [float(v) for v in values][-width:]
    if not vals:
        return ""
    lo, hi = min(vals), max(vals)
    if hi <= lo:
        return _SPARK_CHARS[0] * len(vals)
    top = len(_SPARK_CHARS) - 1
    return "".join(
        _SPARK_CHARS[min(int((v - lo) / (hi - lo) * top + 0.5), top)]
        for v in vals
    )


_LIVE_EVENT_KINDS = (
    "elastic.reshape", "master.restart", "master.lost", "ckpt.restore",
    "rdzv.join", "rdzv.complete", "slo.breach", "slo.clear",
    "diagnosis.straggler", "diagnosis.hang", "diagnosis.clear",
    "chaos.fire", "serve.request.requeued", "serve.request.failed",
    "serve.worker.start",
)


def render_live(report: dict, series: dict, slo: dict,
                events_tail: int = 8) -> str:
    """One compact live frame: goodput mix, per-source step-time and
    MFU sparklines (from the master's tiered store), standing SLO
    breaches and the notable-event tail."""
    lines = [time.strftime("== dlrover_tpu live == %H:%M:%S")]
    ledger = report.get("ledger", {})
    total = ledger.get("total_s", 0.0)
    cats = ledger.get("categories", {})
    mix = "  ".join(
        f"{cat}={secs / total * 100:.1f}%"
        for cat, secs in cats.items() if total > 0 and secs > 0
    )
    lines.append(
        f"goodput {ledger.get('goodput', 0.0) * 100:5.1f}%  "
        f"wall {total:8.1f}s  {mix}"
    )
    for name, label, fmt in (
        ("train.step.last_s", "step", lambda v: f"{v * 1e3:8.1f}ms"),
        ("train.mfu", "mfu ", lambda v: f"{v * 100:8.2f}% "),
        ("serve.ttft.last_s", "ttft",
         lambda v: f"{v * 1e3:8.1f}ms"),
        ("serve.queue.depth", "qdep", lambda v: f"{v:8.0f}  "),
    ):
        for s in series.get(name, ()):
            vals = [p[-1] for p in s["points"]]
            if not vals:
                continue
            lines.append(
                f"{label} {s['source']:<24} {fmt(vals[-1])} "
                f"{sparkline(vals)}"
            )
    if slo:
        lines.append("SLO BREACHES:")
        for key, info in sorted(slo.items()):
            detail = " ".join(
                f"{k}={v}" for k, v in info.items() if k != "rule"
            )
            lines.append(f"  !! {key}: {detail}")
    else:
        lines.append("SLO: ok")
    notable = [
        ev for ev in report.get("timeline", ())
        if ev.get("kind") in _LIVE_EVENT_KINDS
    ][-events_tail:]
    for ev in notable:
        lines.append(
            f"  {time.strftime('%H:%M:%S', time.localtime(ev['t']))} "
            f"{ev.get('source', '?'):<24} {ev['kind']}"
        )
    return "\n".join(lines)


def live_loop(master_addr: str, interval: float = 2.0,
              iterations: int | None = None, out=None) -> int:
    """Poll the live master and redraw; Ctrl-C exits. ``iterations``
    bounds the loop for tests."""
    from dlrover_tpu.agent.master_client import MasterClient

    out = sys.stdout if out is None else out
    client = MasterClient(master_addr, 0, "tool")
    n = 0
    try:
        while iterations is None or n < iterations:
            n += 1
            report = client.get_telemetry_report()
            series = {
                name: client.query_metrics(name, resolution="raw")
                for name in (
                    "train.step.last_s", "train.mfu",
                    "serve.ttft.last_s", "serve.queue.depth",
                )
            }
            slo = dict(client.get_diagnosis().slo or {})
            frame = render_live(report, series, slo)
            # ANSI clear between frames so the view reads as a
            # dashboard, not a scroll; harmless on dumb terminals
            if n > 1 and out is sys.stdout:
                print("\033[H\033[2J", end="", file=out)
            print(frame, file=out, flush=True)
            warn_events_dropped(report)
            warn_hosts_quarantined(report)
            if iterations is None or n < iterations:
                time.sleep(interval)
    except (KeyboardInterrupt, BrokenPipeError):
        # Ctrl-C, or stdout piped into a pager/head that closed first
        pass
    finally:
        client.close()
    return 0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument(
        "--dir", dest="telemetry_dir",
        help="telemetry snapshot directory (DLROVER_TELEMETRY_DIR)",
    )
    parser.add_argument(
        "--master", dest="master_addr",
        help="live master address host:port (telemetry servicer query)",
    )
    parser.add_argument(
        "--trace", action="store_true",
        help="render the cross-host span trees (causal trace view)",
    )
    parser.add_argument(
        "--trace-dir", help="XPlane trace dir to embed a profile summary"
    )
    parser.add_argument(
        "--steps", type=int, default=1,
        help="profiled step count for --trace-dir normalization",
    )
    parser.add_argument(
        "--timeline", type=int, default=40,
        help="how many trailing timeline events to print",
    )
    parser.add_argument("--json", action="store_true")
    parser.add_argument(
        "--capture", type=int, default=None, metavar="RANK",
        help="trigger a deep capture of host RANK on a live master "
        "(--master) and wait for the artifact + attribution diff",
    )
    parser.add_argument(
        "--capture-steps", type=int, default=0,
        help="steps of device trace for --capture (0 = master default)",
    )
    parser.add_argument(
        "--capture-wait", type=float, default=120.0,
        help="seconds to wait for the --capture artifact",
    )
    parser.add_argument(
        "--perfetto", metavar="OUT.json",
        help="write the merged host+device Perfetto/Chrome-trace "
        "timeline (host spans from --dir/--master; device side from "
        "--trace-dir when given)",
    )
    parser.add_argument(
        "--live", action="store_true",
        help="poll a running master (--master) and redraw a compact "
        "live view with text sparklines from its metrics store",
    )
    parser.add_argument(
        "--interval", type=float, default=2.0,
        help="--live polling interval in seconds",
    )
    args = parser.parse_args(argv)
    if not args.telemetry_dir and not args.master_addr:
        parser.error("need --dir and/or --master")
    if args.capture is not None:
        if not args.master_addr:
            parser.error("--capture needs --master (a running job)")
        return run_capture(
            args.master_addr, args.capture, steps=args.capture_steps,
            wait=args.capture_wait,
        )
    if args.live:
        if not args.master_addr:
            parser.error("--live needs --master (a running job)")
        return live_loop(args.master_addr, interval=args.interval)

    report = build_report(
        telemetry_dir=args.telemetry_dir,
        master_addr=args.master_addr,
        trace_dir=args.trace_dir,
        steps=args.steps,
    )
    if not report.get("sources"):
        print("no telemetry snapshots found", file=sys.stderr)
        return 1
    warn_events_dropped(report)
    warn_hosts_quarantined(report)
    if args.perfetto:
        path = write_perfetto(
            report, args.perfetto, trace_dir=args.trace_dir,
        )
        print(f"merged Perfetto timeline written to {path}",
              file=sys.stderr)
    if args.json:
        print(json.dumps(report, indent=2))
    elif args.trace:
        from dlrover_tpu.common.tracing import format_trace

        print("=== span traces (cross-host, parent/child nested) ===")
        print(format_trace(report.get("timeline", [])))
    else:
        from dlrover_tpu.common.telemetry import format_report

        print(format_report(report, timeline_tail=args.timeline))
        restore = report.get("restore") or {}
        if restore:
            print("\n=== checkpoint data path ===")
            for name in sorted(restore):
                print(f"{restore[name]:14.3f}  {name}")
        reshape = report.get("reshape") or {}
        if reshape:
            print("\n=== elastic reshape (restart-free scale events) ===")
            for name in sorted(reshape):
                print(f"{reshape[name]:14.3f}  {name}")
        brain = report.get("brain") or {}
        if brain:
            print("\n=== brain actions (repair plans) ===")
            for name in sorted(brain.get("counters", {})):
                print(f"{brain['counters'][name]:14.3f}  {name}")
            plans = brain.get("plans") or []
            if plans:
                t0 = plans[0].get("t") or 0.0
                for p in plans:
                    target = (
                        f" rank={p['target']}"
                        if p.get("target", -1) is not None
                        and p.get("target", -1) >= 0 else ""
                    )
                    print(
                        f"+{(p.get('t') or 0.0) - t0:9.3f}s  "
                        f"{p.get('plan', '?'):<10} "
                        f"{p.get('plan_kind', ''):<18}"
                        f"{target:<10} -> {p.get('transition', '')}"
                    )
        serving = report.get("serving") or {}
        if serving:
            print("\n=== serving (decode pool) ===")
            for name in sorted(serving):
                print(f"{serving[name]:14.3f}  {name}")
        profiling = report.get("profiling") or {}
        if profiling:
            print("\n=== deep profiling (device-time accounting) ===")
            for name in sorted(profiling.get("metrics", {})):
                print(f"{profiling['metrics'][name]:14.3f}  {name}")
            for ev in profiling.get("events") or []:
                extra = " ".join(
                    f"{k}={v}" for k, v in ev.items()
                    if k not in ("t", "kind") and v is not None
                )
                print(f"  {ev['kind']:<28} {extra}")
        health = report.get("health") or {}
        if health:
            print("\n=== host health (probe gate) ===")
            for rank, info in sorted(
                (health.get("quarantined") or {}).items()
            ):
                print(f"  host {rank}: {info['verdict']} "
                      f"({info['reason']})")
            for ev in health.get("events") or []:
                extra = " ".join(
                    f"{k}={v}" for k, v in ev.items()
                    if k not in ("t", "kind") and v is not None
                )
                print(f"  {ev['kind']:<24} {extra}")
        control = report.get("control_plane") or {}
        if control:
            print("\n=== control plane (master RPC surface) ===")
            for name in sorted(control):
                v = control[name]
                print(
                    f"{v:14.3f}  {name}" if isinstance(v, float)
                    else f"{v:14d}  {name}"
                )
        if report.get("profile_error"):
            print(f"\n[profile skipped: {report['profile_error']}]",
                  file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
