"""Parse an XPlane trace into per-op/category self-times.

Usage:
    python tools/parse_profile.py /path/to/trace_dir --steps 3
    python tools/parse_profile.py /path/to/trace_dir --steps 3 --json

A thin CLI over the ONE shared trace walker
(``dlrover_tpu/common/trace_summary.py``), which the deep-profiling
sampler consumes too.

Exit codes: 0 parsed, 1 no traces under the directory, 2 the xprof
toolchain is unavailable or the trace would not parse — always a clear
one-line message, never a stack trace.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

from dlrover_tpu.common.trace_summary import (  # noqa: E402
    render,
    summarize,
)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument(
        "trace_dir", help="directory searched recursively for *.xplane.pb"
    )
    parser.add_argument(
        "--steps", type=int, default=1,
        help="number of profiled steps the trace covers (per-step "
        "normalization; default 1)",
    )
    parser.add_argument("--top", type=int, default=45)
    parser.add_argument(
        "--json", action="store_true", help="emit the summary as JSON"
    )
    args = parser.parse_args(argv)
    if not os.path.isdir(args.trace_dir):
        print(
            f"trace dir does not exist: {args.trace_dir}",
            file=sys.stderr,
        )
        return 1
    try:
        summary = summarize(args.trace_dir, steps=args.steps, top=args.top)
    except ImportError as e:
        print(f"xprof toolchain unavailable: {e}", file=sys.stderr)
        return 2
    except Exception as e:  # noqa: BLE001 - CLI contract: a clear
        # message for a broken/drifted trace, never a stack trace
        print(
            f"could not parse trace under {args.trace_dir}: "
            f"{type(e).__name__}: {e}",
            file=sys.stderr,
        )
        return 2
    if summary is None:
        print(f"no *.xplane.pb traces under {args.trace_dir}",
              file=sys.stderr)
        return 1
    print(json.dumps(summary, indent=2) if args.json else render(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
