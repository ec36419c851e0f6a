#!/usr/bin/env python
"""dlint CLI: run the project-invariant static-analysis suite.

Usage::

    python tools/lint.py [--json] [--update-baseline] [paths...]

- default paths: ``dlrover_tpu tools`` (what the tier-1 gate checks)
- exit 0: every finding is baselined (or there are none)
- exit 1: unbaselined findings — fix them, add a
  ``# dlint: allow-<checker>(reason)``, or (false positives only)
  ``--update-baseline`` and write a justification into
  ``tools/dlint/baseline.json``
- exit 2: the baseline itself is unjustified (entries without a note)

Suitable as a pre-commit hook: it is pure stdlib-``ast``, touches no
network, and runs the full package in well under 5 seconds.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

_REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))
)
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)

from tools.dlint import Baseline, run_checks  # noqa: E402

DEFAULT_PATHS = ("dlrover_tpu", "tools")
BASELINE_PATH = os.path.join(_REPO_ROOT, "tools", "dlint", "baseline.json")

# --checker accepts either form: the stable code or the checker name
CODE_TO_CHECKER = {
    "DL001": "lock-order",
    "DL002": "blocking-under-lock",
    "DL003": "chaos-coverage",
    "DL004": "signal-safety",
    "DL005": "jit-purity",
    "DL006": "message-drift",
    "DL007": "metric-drift",
    "DL008": "shared-mut",
}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="dlrover_tpu project-invariant static analysis"
    )
    ap.add_argument("paths", nargs="*", default=None)
    ap.add_argument("--json", action="store_true",
                    help="machine-readable output")
    ap.add_argument("--update-baseline", action="store_true",
                    help="absorb current findings into the baseline "
                         "(new entries still need a justification)")
    ap.add_argument("--baseline", default=BASELINE_PATH)
    ap.add_argument("--checker", action="append", default=None,
                    help="run only the named checker(s) — by name "
                         "('shared-mut') or code ('DL008')")
    ap.add_argument("--lock-inventory", action="store_true",
                    help="print the lock catalog (keys, reentrancy, "
                         "ordering edges) from the DL001 model and "
                         "exit")
    args = ap.parse_args(argv)

    if args.checker is not None:
        args.checker = [
            CODE_TO_CHECKER.get(c.upper(), c) for c in args.checker
        ]
    paths = [
        os.path.join(_REPO_ROOT, p) if not os.path.isabs(p) else p
        for p in (args.paths or DEFAULT_PATHS)
    ]

    if args.lock_inventory:
        from tools.dlint.core import collect_sources
        from tools.dlint.locks import lock_inventory

        inv = lock_inventory(collect_sources(paths, _REPO_ROOT))
        if args.json:
            print(json.dumps(inv, indent=2))
        else:
            print(f"locks ({len(inv['locks'])}):")
            for key, entry in inv["locks"].items():
                kind = "rlock/cond" if entry["reentrant"] else "lock"
                print(f"  {key}  [{kind}]  "
                      f"{len(entry['sites'])} acquisition site(s)")
            print(f"\nordering edges ({len(inv['edges'])}), "
                  f"outer -> inner:")
            for e in inv["edges"]:
                print(f"  {e['outer']} -> {e['inner']}  "
                      f"({e['witness']})")
        return 0
    t0 = time.monotonic()
    try:
        findings = run_checks(paths, repo_root=_REPO_ROOT,
                              checkers=args.checker)
    except ValueError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    elapsed = time.monotonic() - t0

    baseline = Baseline.load(args.baseline)
    if args.update_baseline:
        # a partial run (subset of checkers or paths) must not wipe
        # entries it never had a chance to observe
        full_run = args.checker is None and not args.paths
        baseline.update(findings, prune=full_run)
        baseline.save()
        print(
            f"baseline updated: {len(baseline.entries)} entries -> "
            f"{os.path.relpath(args.baseline, _REPO_ROOT)}"
            + ("" if full_run else "  (partial run: stale entries kept)")
        )
        missing = baseline.unjustified()
        if missing:
            print(
                f"NOTE: {len(missing)} entries still carry the "
                f"placeholder note — write real justifications."
            )
        return 0

    new, stale = baseline.diff(findings)
    unjustified = baseline.unjustified()
    if args.json:
        print(json.dumps({
            "elapsed_s": round(elapsed, 3),
            "total": len(findings),
            "baselined": len(findings) - len(new),
            "new": [f.to_dict() for f in new],
            "stale_baseline": stale,
            "unjustified_baseline": unjustified,
        }, indent=2))
    else:
        for f in new:
            print(f"{f.file}:{f.line}: [{f.code} {f.checker}] "
                  f"{f.message}  (fingerprint {f.fingerprint})")
        if stale:
            print(
                f"\n{len(stale)} stale baseline entr"
                f"{'y' if len(stale) == 1 else 'ies'} (code fixed — "
                f"run --update-baseline to prune):"
            )
            for e in stale:
                print(f"  {e.get('file', '?')}: {e['fingerprint']} "
                      f"[{e.get('code', '?')}] {e.get('note', '')}")
        print(
            f"\ndlint: {len(findings)} findings "
            f"({len(findings) - len(new)} baselined, {len(new)} new) "
            f"in {elapsed:.2f}s"
        )
    if unjustified and not new:
        for e in unjustified:
            # stderr: --json consumers must keep a parseable stdout
            print(
                f"baseline entry {e['fingerprint']} "
                f"({e.get('file', '?')}) has no justification",
                file=sys.stderr,
            )
        return 2
    return 1 if new else 0


if __name__ == "__main__":
    sys.exit(main())
