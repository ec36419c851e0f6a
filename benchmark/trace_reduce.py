"""From a profiler trace to numbers: device busy union, idle gaps by
what the host was doing, per-operation time.

Reads the ``.xplane.pb`` the JAX profiler writes through
``jax.profiler.ProfileData`` alone (no xprof, no TensorFlow). The
reduction itself works on a plain dict (``load_xplane`` makes it, the
tests' fixture is one, trimmed), so what a number means is fixed here
and checked against a trace whose answers were worked out by hand:

    {"devices": {"/device:TPU:0": {"XLA Ops": [[name, start_ns, dur_ns],
                                               ...],
                                   "XLA Modules": [...]}},
     "host": [[name, start_ns, dur_ns], ...]}      # bench.* annotations

(an operation's name is its ``label()``: the instruction's name and, for
a custom call, its target)

- busy: the union of the intervals in which an operation ran on the
  device's ``XLA Ops`` line (nested operations, such as a loop and its
  body, count once), clipped to the window;
- window: the ``bench.window`` annotation the runner keeps open over
  the traced stretch (it opens and closes at edges of the run's
  window, when the device has drained); without it, first operation
  to last;
- idle gaps: the window minus busy, each gap shared out among the
  runner's other ``bench.*`` annotations (what the host was doing) by
  overlap, the rest to ``other``;
- an operation's time: its self time, the duration minus that of the
  operations nested directly inside it, summed by name.

Run as a script it describes a trace file: planes, lines, the largest
events and their stats. Look at one by hand before trusting a pattern.
"""

from __future__ import annotations

import glob
import os
import re
import sys

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIX = "bench."
WINDOW_NAME = "bench.window"
TOP = 10


CUSTOM_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def label(name: str) -> str:
    """The device plane prints an operation as its whole HLO text.
    Keep the instruction's name, and for a custom call its target (a
    Pallas kernel is ``tpu_custom_call``), so that a pattern can tell
    kernels from the compiler's own custom calls."""
    short = name.split(" = ", 1)[0].lstrip("%")
    target = CUSTOM_TARGET.search(name)
    return f"{short} [{target.group(1)}]" if target else short


def find_xplane(trace_dir: str) -> str | None:
    files = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    ))
    return files[-1] if files else None


def load_xplane(path: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    raw = {"devices": {}, "host": []}
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {}
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    lines[line.name] = [
                        [label(e.name), float(e.start_ns),
                         float(e.duration_ns)]
                        for e in line.events
                    ]
            raw["devices"][plane.name] = lines
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                raw["host"].extend(
                    [e.name, float(e.start_ns), float(e.duration_ns)]
                    for e in line.events
                    if e.name.startswith(HOST_PREFIX)
                )
    return raw


def _union(intervals):
    """Sorted, merged [start, end) intervals."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def _self_times(events):
    """{name: self ns}: duration minus directly nested events'."""
    order = sorted(events, key=lambda e: (e[1], -e[2]))
    self_ns = [e[2] for e in order]
    stack = []  # indexes into order, innermost last
    for i, (_name, start, dur) in enumerate(order):
        while stack and order[stack[-1]][1] + order[stack[-1]][2] <= start:
            stack.pop()
        if stack:
            self_ns[stack[-1]] -= dur
        stack.append(i)
    out = {}
    for (name, _s, _d), own in zip(order, self_ns):
        out[name] = out.get(name, 0.0) + max(own, 0.0)
    return out


def _share_out(gap, spans):
    """Split one idle gap among host spans by overlap -> {label: ns}.
    Where spans nest, the innermost (the latest to start) has the
    stretch; what no span covers goes to ``other``."""
    start, end = gap
    inside = [s for s in spans if s[1] > start and s[0] < end]
    cuts = sorted({start, end} | {
        t for lo, hi, _ in inside for t in (lo, hi) if start < t < end
    })
    shares = {}
    for a, b in zip(cuts, cuts[1:]):
        covering = [s for s in inside if s[0] <= a and s[1] >= b]
        label = max(covering)[2] if covering else "other"
        shares[label] = shares.get(label, 0.0) + (b - a)
    return shares


def reduce(raw: dict) -> dict | None:
    """The reduction. None when no operation ran on a device plane."""
    devices = {
        name: lines for name, lines in raw.get("devices", {}).items()
        if lines.get(OPS_LINE)
    }
    if not devices:
        return None
    host = raw.get("host", [])
    windows = [(s, s + d) for n, s, d in host if n == WINDOW_NAME]
    if windows:
        w_start, w_end = min(w[0] for w in windows), max(w[1] for w in windows)
    else:
        ops = [e for lines in devices.values() for e in lines[OPS_LINE]]
        w_start = min(e[1] for e in ops)
        w_end = max(e[1] + e[2] for e in ops)
    spans = sorted(
        (s, s + d, n[len(HOST_PREFIX):]) for n, s, d in host
        if n.startswith(HOST_PREFIX) and n != WINDOW_NAME
    )
    busy_ns, op_ns, module_ns, module_count, gaps_ns = [], {}, {}, {}, {}
    for lines in devices.values():
        clipped = [
            (max(s, w_start), min(s + d, w_end))
            for _n, s, d in lines[OPS_LINE]
            if s + d > w_start and s < w_end
        ]
        merged = _union(clipped)
        busy_ns.append(sum(end - start for start, end in merged))
        edges = [w_start] + [t for iv in merged for t in iv] + [w_end]
        for gap in zip(edges[0::2], edges[1::2]):
            if gap[1] > gap[0]:
                for label, ns in _share_out(gap, spans).items():
                    gaps_ns[label] = gaps_ns.get(label, 0.0) + ns
        inside = [e for e in lines[OPS_LINE] if w_start <= e[1] < w_end]
        for name, ns in _self_times(inside).items():
            op_ns[name] = op_ns.get(name, 0.0) + ns
        for name, start, dur in lines.get(MODULES_LINE, []):
            if w_start <= start < w_end:
                module_ns[name] = module_ns.get(name, 0.0) + dur
                module_count[name] = module_count.get(name, 0) + 1
    n = len(devices)

    def ranked(table):
        top = sorted(table.items(), key=lambda kv: -kv[1])[:TOP]
        return [[name, ns / n / 1e9] for name, ns in top]

    return {
        "devices": n,
        "window_s": (w_end - w_start) / 1e9,
        "busy_s": sum(busy_ns) / n / 1e9,
        # per device, seconds by name over the whole window
        "ops_s": {k: v / n / 1e9 for k, v in op_ns.items()},
        "modules_s": {k: v / n / 1e9 for k, v in module_ns.items()},
        "modules_count": {k: v / n for k, v in module_count.items()},
        "device_ops": ranked(op_ns),
        "idle_gaps": ranked(gaps_ns),
    }


def matching_seconds(table: dict, pattern: str) -> float | None:
    """Summed seconds of the entries whose name matches ``pattern``;
    None when nothing matches (the reader then reports nothing)."""
    rx = re.compile(pattern)
    hits = [v for k, v in table.items() if rx.search(k)]
    return sum(hits) if hits else None


def describe(path: str, top: int = 25) -> None:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    for plane in data.planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            events = list(line.events)
            total = sum(e.duration_ns for e in events)
            print(f"  LINE {line.name!r}: {len(events)} events, "
                  f"{total / 1e6:.3f} ms")
            if not (DEVICE_PLANE.match(plane.name)
                    or any(e.name.startswith(HOST_PREFIX) for e in events)):
                continue
            by_name = {}
            for e in events:
                entry = by_name.setdefault(e.name, [0, 0.0, e])
                entry[0] += 1
                entry[1] += e.duration_ns
            for name, (count, ns, ev) in sorted(
                by_name.items(), key=lambda kv: -kv[1][1]
            )[:top]:
                stats = {k: str(v)[:80] for k, v in list(ev.stats)[:8]}
                print(f"    {ns / 1e6:10.3f} ms x{count:<5d} {name[:90]}"
                      f"  start={ev.start_ns:.0f} {stats}")


if __name__ == "__main__":
    target = sys.argv[1]
    if os.path.isdir(target):
        target = find_xplane(target) or sys.exit(f"no xplane under {target}")
    describe(target)
    print("REDUCED", {
        k: v for k, v in (reduce(load_xplane(target)) or {}).items()
        if k not in ("ops_s",)
    })
