"""Shared XPlane trace summarizer: ONE trace-walking implementation.

Two consumers read the xprof ``hlo_stats`` walk from here:
``tools/parse_profile.py`` (the operator's CLI) and
``common/profiling.py`` (the sampler parses a trace on every sampled
step, and a deep capture summarizes its window). This module is the
only place that knows the xprof table layout, so a format drift breaks
in ONE spot with ONE fix.

Also the one place that knows the **canonical op-category buckets** the
always-on accounting publishes (``device.optime_ms{category=...}``):
matmul, collective-permute, all-gather, reduce-scatter, all-reduce,
all-to-all, fusion, convolution, infeed-outfeed, copy, host, other —
stable names a baseline can be keyed on across xprof versions whose raw
category strings drift.

xprof is optional (CPU smoke environments ship without it):
:func:`toolchain_available` probes once, and every consumer degrades —
the CLI prints a clear message, the sampler disables itself.
"""

from __future__ import annotations

import glob
import json
import os

# canonical category buckets, coarsest-useful granularity for per-step
# accounting and baselines (raw xprof category strings vary by version)
CANONICAL_CATEGORIES = (
    "matmul",
    "collective-permute",
    "all-gather",
    "reduce-scatter",
    "all-reduce",
    "all-to-all",
    "fusion",
    "convolution",
    "infeed-outfeed",
    "copy",
    "host",
    "other",
)

# substring -> canonical bucket, checked in order (first match wins:
# "all-gather-fusion" must land in all-gather, not fusion)
_CATEGORY_RULES = (
    (("collective-permute", "collective permute"), "collective-permute"),
    (("all-gather", "all gather"), "all-gather"),
    (("reduce-scatter", "reduce scatter"), "reduce-scatter"),
    (("all-reduce", "all reduce", "cross-replica-sum"), "all-reduce"),
    (("all-to-all", "all to all", "alltoall"), "all-to-all"),
    (("dot", "matmul", "gemm", "einsum"), "matmul"),
    (("conv",), "convolution"),
    (("infeed", "outfeed"), "infeed-outfeed"),
    (("copy", "transpose", "reshape"), "copy"),
    (("host", "callback", "stall", "idle"), "host"),
    (("fusion", "loop", "elementwise", "reduce"), "fusion"),
)


def canonical_category(raw: str) -> str:
    """Map a raw HLO op-category string to its canonical bucket."""
    low = (raw or "").lower()
    for needles, bucket in _CATEGORY_RULES:
        if any(n in low for n in needles):
            return bucket
    return "other"


def canonical_breakdown(by_category: dict) -> dict:
    """Collapse a raw ``{category: ms}`` map onto the canonical
    buckets (summing raw categories that share a bucket)."""
    out: dict[str, float] = {}
    for raw, ms in (by_category or {}).items():
        bucket = canonical_category(raw)
        out[bucket] = out.get(bucket, 0.0) + float(ms)
    return out


_TOOLCHAIN: bool | None = None


def toolchain_available() -> bool:
    """Whether the xprof conversion toolchain imports (probed once)."""
    global _TOOLCHAIN
    if _TOOLCHAIN is None:
        try:
            from xprof.convert import raw_to_tool_data  # noqa: F401

            _TOOLCHAIN = True
        except Exception:  # noqa: BLE001 - absent OR broken both mean
            # "no offline parse here"; the sampler must not crash a
            # training step over a half-installed profiler package
            _TOOLCHAIN = False
    return _TOOLCHAIN


def xplane_paths(trace_dir: str) -> list[str]:
    """Every ``*.xplane.pb`` under ``trace_dir``, oldest-first."""
    return sorted(glob.glob(
        os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True
    ))


def hlo_stats_rows(paths) -> tuple[list[str], list[list]]:
    """The xprof ``hlo_stats`` table for ``paths`` as ``(cols, rows)``.

    Raises ImportError when the toolchain is missing and ValueError on
    a table whose layout this walker does not understand — callers
    choose whether that is fatal (CLI) or a degrade (sampler).
    """
    from xprof.convert import raw_to_tool_data as rtd

    data, _ = rtd.xspace_to_tool_data(list(paths), "hlo_stats", {})
    if isinstance(data, bytes):
        data = data.decode()
    obj = json.loads(data)
    cols = [c["label"] for c in obj["cols"]]
    rows = [[c["v"] for c in r["c"]] for r in obj["rows"]]
    return cols, rows


def op_table(paths) -> list[dict]:
    """Per-(category, op) totals from the hlo_stats table:
    ``[{category, op, self_us, occurrences}]`` (aggregated)."""
    cols, rows = hlo_stats_rows(paths)
    try:
        icat = cols.index("HLO op category")
        iname = cols.index("HLO op name")
        itime = cols.index("Total self time (us)")
    except ValueError as e:
        raise ValueError(
            f"unrecognized hlo_stats layout (cols={cols})"
        ) from e
    iocc = cols.index("#Occurrences") if "#Occurrences" in cols else None
    agg: dict[tuple, list] = {}
    for r in rows:
        t = float(r[itime] or 0)
        key = (str(r[icat]), str(r[iname]))
        entry = agg.setdefault(key, [0.0, 0])
        entry[0] += t
        if iocc is not None:
            entry[1] += int(r[iocc] or 0)
    return [
        {
            "category": cat,
            "op": name,
            "self_us": t,
            "occurrences": occ,
        }
        for (cat, name), (t, occ) in sorted(
            agg.items(), key=lambda kv: -kv[1][0]
        )
    ]


def summarize(trace_dir: str, steps: int = 1, top: int = 45) -> dict | None:
    """Per-category/per-op self-time summary of every ``*.xplane.pb``
    under ``trace_dir``. Returns None when no trace files exist.
    Raises ImportError when the xprof toolchain is unavailable —
    callers that merely *embed* the summary should catch it."""
    paths = xplane_paths(trace_dir)
    if not paths:
        return None
    ops = op_table(paths)
    steps = max(int(steps), 1)
    bycat: dict[str, float] = {}
    for o in ops:
        bycat[o["category"]] = bycat.get(o["category"], 0.0) + o["self_us"]
    tot = sum(bycat.values())
    return {
        "trace_dir": trace_dir,
        "steps": steps,
        "num_traces": len(paths),
        "total_ms_per_step": tot / steps / 1e3,
        "by_category": {
            cat: t / steps / 1e3 for cat, t in bycat.items()
        },
        "by_canonical_category": canonical_breakdown({
            cat: t / steps / 1e3 for cat, t in bycat.items()
        }),
        "top_ops": [
            {
                "category": o["category"],
                "op": o["op"],
                "ms_per_step": o["self_us"] / steps / 1e3,
                "occurrences": o["occurrences"],
            }
            for o in ops[:top]
        ],
    }


def render(summary: dict) -> str:
    """Human rendering of a :func:`summarize` payload (the CLI view)."""
    lines = [
        f"total self time {summary['total_ms_per_step']:.1f} ms/step "
        f"({summary['num_traces']} trace file(s), "
        f"{summary['steps']} step(s))",
        "",
        "=== by category ===",
    ]
    for cat, ms in sorted(
        summary["by_category"].items(), key=lambda kv: -kv[1]
    ):
        lines.append(f"{ms:8.2f} ms/step  {cat}")
    lines.append("")
    lines.append(f"=== top {len(summary['top_ops'])} ops ===")
    for op in summary["top_ops"]:
        lines.append(
            f"{op['ms_per_step']:8.3f} ms/step  x{op['occurrences']:4d} "
            f"{op['category']:22s} {op['op'][:80]}"
        )
    return "\n".join(lines)
