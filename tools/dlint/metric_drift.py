"""DL007 metric-name drift.

Invariant: every metric/gauge/counter/event name the operator surfaces
QUERY (``tools/obs_report.py`` summaries) must actually be EMITTED
somewhere in the package. The emit and query sides are plain string
literals with no shared constant, so a renamed
gauge (``ckpt.restore.read_gbps`` -> ``ckpt.read_gbps``) silently
turns the consumer's section empty — the report keeps "working" while
the number the ROADMAP tracks quietly disappears. This is the DL006
message-drift idea applied to telemetry names.

Detection (lexical, like every dlint checker):

- **emitted**: the literal first argument of any
  ``counter_inc/gauge_set/observe/event`` call anywhere in the scanned
  tree (the module-level helpers and registry methods share those
  names).
- **queried**: in the consumer files, ``x["name"] == "lit"``
  comparisons and ``x["name"].startswith("lit" | ("a", "b"))`` calls —
  the two idioms the summaries use to select series.

A queried exact name missing from the emitted set, or a queried prefix
that no emitted name starts with, is a finding. Names emitted with a
computed first argument are invisible to the emitted set; if a
consumer queries such a name exactly, allow it in code with
``# dlint: allow-metric-drift(reason)`` or baseline it.
"""

from __future__ import annotations

import ast

from tools.dlint.core import Finding

_EMIT_FUNCS = {"counter_inc", "gauge_set", "observe", "event"}

# consumer seams: the operator-facing summaries whose queried names
# must stay live (relpath suffix match, forward slashes)
_CONSUMER_SUFFIXES = ("tools/obs_report.py",)


def _is_consumer(relpath: str) -> bool:
    rel = relpath.replace("\\", "/")
    return any(
        rel == suf or rel.endswith("/" + suf)
        for suf in _CONSUMER_SUFFIXES
    )


def _emitted_names(sources) -> set[str]:
    from tools.dlint.astutil import index_for, last_attr

    out: set[str] = set()
    for src in sources:
        index = index_for(src)
        for call in index.all_calls:
            from tools.dlint.astutil import call_name

            name = call_name(call)
            if not name or last_attr(name) not in _EMIT_FUNCS:
                continue
            if not call.args:
                continue
            first = call.args[0]
            if isinstance(first, ast.Constant) and isinstance(
                first.value, str
            ):
                out.add(first.value)
    return out


def _is_name_subscript(node) -> bool:
    """``<expr>["name"]`` — the snapshot-entry access idiom."""
    if not isinstance(node, ast.Subscript):
        return False
    sl = node.slice
    # py<3.9 wraps the index in ast.Index; handle both shapes
    if isinstance(sl, ast.Index):  # pragma: no cover - legacy ast
        sl = sl.value
    return isinstance(sl, ast.Constant) and sl.value == "name"


def _queried_names(src) -> list[tuple[str, bool, int]]:
    """-> [(literal, is_prefix, lineno)] for one consumer file."""
    out: list[tuple[str, bool, int]] = []
    for node in ast.walk(src.tree):
        if isinstance(node, ast.Compare):
            if len(node.ops) != 1 or not isinstance(
                node.ops[0], ast.Eq
            ):
                continue
            sides = (node.left, node.comparators[0])
            if not any(_is_name_subscript(s) for s in sides):
                continue
            for s in sides:
                if isinstance(s, ast.Constant) and isinstance(
                    s.value, str
                ):
                    out.append((s.value, False, node.lineno))
        elif isinstance(node, ast.Call):
            func = node.func
            if not (
                isinstance(func, ast.Attribute)
                and func.attr == "startswith"
                and _is_name_subscript(func.value)
                and node.args
            ):
                continue
            arg = node.args[0]
            elts = (
                arg.elts if isinstance(arg, ast.Tuple) else [arg]
            )
            for e in elts:
                if isinstance(e, ast.Constant) and isinstance(
                    e.value, str
                ):
                    out.append((e.value, True, node.lineno))
    return out


def check_metric_drift(sources) -> list[Finding]:
    consumers = [s for s in sources if _is_consumer(s.relpath)]
    emitting_in_scope = any(
        s.relpath.replace("\\", "/").startswith("dlrover_tpu/")
        for s in sources
    )
    if not consumers or not emitting_in_scope:
        # partial run (pre-commit on a path subset): without both the
        # emitting package and a consumer in scope every queried name
        # would look dead — skip rather than spray false positives
        return []
    emitted = _emitted_names(sources)
    findings = []
    for src in consumers:
        seen: set[tuple[str, bool]] = set()
        for literal, is_prefix, lineno in _queried_names(src):
            if (literal, is_prefix) in seen:
                continue
            seen.add((literal, is_prefix))
            if is_prefix:
                live = any(n.startswith(literal) for n in emitted)
            else:
                live = literal in emitted
            if live:
                continue
            if src.allowed("metric-drift", lineno):
                continue
            kind = "prefix" if is_prefix else "name"
            findings.append(Finding(
                checker="metric-drift", code="DL007",
                file=src.relpath, line=lineno,
                message=(
                    f"queried metric {kind} {literal!r} is emitted "
                    f"nowhere in the package — the consumer section "
                    f"reads as empty instead of failing"
                ),
                detail=f"{kind}|{literal}",
            ))
    return findings
