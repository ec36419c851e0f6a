"""Causal trace spans over the telemetry timeline (Dapper-style).

Equivalent capability: the reference diagnoses "why is host 3 slow"
with the xpu_timer stack (in-process timing hooks -> shm -> exporter)
plus ad-hoc master-side logs; what it never had is a CAUSAL view — one
rendezvous round, checkpoint restore, or master-failover ride-through
rendered as a single cross-host tree. This module adds exactly that on
top of :mod:`dlrover_tpu.common.telemetry`:

- ``span(name, **labels)`` — a context manager that emits a ``span``
  timeline event on exit, carrying ``trace`` / ``span`` / ``parent``
  IDs. Spans nest through a thread-local ambient context, so a child
  opened inside a parent is parented automatically.
- **Cross-process propagation**: :func:`wire_context` snapshots the
  ambient context for an RPC envelope (the :class:`~dlrover_tpu.common.
  rpc.RpcClient` injects it into every call) and :func:`attach` adopts
  it on the server side (the RPC handler wraps dispatch in it), so a
  span opened in the master while serving an agent's request is a child
  of the agent's span — one trace across processes and hosts.
- **Rendering**: :func:`trace_trees` / :func:`format_trace` rebuild and
  print the parent/child forest from a merged job timeline
  (``tools/obs_report.py --trace``).

Span events ride the same bounded per-process event ring as everything
else, which doubles as the flight recorder's payload
(:mod:`dlrover_tpu.common.flight`): the last ~4096 spans/events of a
crashing process are exactly its post-mortem.

**On the device's clock**: in a process that has imported JAX every
span also opens a ``jax.profiler.TraceAnnotation("dlrover." + name)``
for its lifetime, so whenever a profiler session is on (a benchmark's,
the device-time sampler's, a deep capture's) the program's spans lie
in the ``.xplane.pb`` on the profiler's own clock, next to the device
plane. :func:`annotation` is the trace-only form for spans that occur
every step and must not fill the ring. A process that never imports
JAX (agent, master) pays one ``sys.modules`` lookup.

**Legs**: :class:`Legs` is a root span cut into consecutive children —
each leg begins where the one before it ended, so the children cover
the root without a gap, and each leg's seconds also land on a counter
``<leg name>_s`` (what a reader sees that only has the process's
counters). The agent roots one where a worker dies (``resume``) or is
first launched (``launch``) and hands it over the ``Popen`` in
``telemetry.ENV_TRACE``; the spawned process adopts it at import
(:func:`startup`), adds its own ``start.*`` legs and closes the root
at its first completed step. A process nobody launched that way roots
a ``launch`` of its own at the instant the kernel started it.

Cost model: the ambient context is a thread-local assignment; the event
emission is the usual telemetry hook (one lock + one deque append), and
a no-op when telemetry is disabled. Propagation survives RPC retries
and reconnects for free — the context is captured once per logical
call, not per attempt — and master failover cannot orphan children
because the context lives in the caller, never in master state.

Reserved span-event fields: ``name``, ``trace``, ``span``, ``parent``
(empty string = root), ``dur``, ``status`` ("ok" | "error").
"""

from __future__ import annotations

import contextlib
import json
import os
import sys
import threading
import time

from dlrover_tpu.common import telemetry

SPAN_EVENT = "span"
# prefix of the program's spans in a profiler trace
ANNOTATION_PREFIX = "dlrover."

_tls = threading.local()
_NO_ANNOTATION = contextlib.nullcontext()
# the open leg of an ADOPTED start-up trace: every thread's ambient
# parent while the process starts (what ``attach`` is to one RPC),
# None again once the first step has completed
_process_ctx: dict | None = None


def annotation(name: str, **stats):
    """A span in the profiler's own trace and nowhere else: a
    ``TraceAnnotation`` named ``dlrover.<name>`` (``stats`` become the
    event's stats). With no profiler session on it costs the
    annotation's disabled check; it never imports JAX itself."""
    jax = sys.modules.get("jax")
    profiler = getattr(jax, "profiler", None)
    if profiler is None:
        return _NO_ANNOTATION
    return profiler.TraceAnnotation(ANNOTATION_PREFIX + name, **stats)


def _new_id(nbytes: int = 8) -> str:
    return os.urandom(nbytes).hex()


def current() -> dict | None:
    """The ambient trace context of this thread:
    ``{"trace": ..., "span": ...}`` or None outside any span (while a
    process its launcher handed a trace starts up: the open leg)."""
    ctx = getattr(_tls, "ctx", None)
    return ctx if ctx is not None else _process_ctx


def wire_context() -> dict | None:
    """Context to inject into an outgoing RPC envelope (a COPY — the
    receiver may hold it past this span's exit)."""
    ctx = current()
    return dict(ctx) if ctx else None


@contextlib.contextmanager
def attach(ctx: dict | None):
    """Adopt a propagated wire context as this thread's ambient parent
    WITHOUT emitting a span event (the server-side half of propagation).
    Malformed/absent contexts are ignored — an old client's 4-field
    envelope must not break dispatch."""
    if not (
        isinstance(ctx, dict) and ctx.get("trace") and ctx.get("span")
    ):
        yield None
        return
    prev = getattr(_tls, "ctx", None)
    _tls.ctx = {"trace": str(ctx["trace"]), "span": str(ctx["span"])}
    try:
        yield _tls.ctx
    finally:
        _tls.ctx = prev


class Span:
    """Handle yielded by :func:`span` — mostly for tests/labels."""

    __slots__ = ("name", "trace", "span", "parent", "labels", "start")

    def __init__(self, name, trace, span_id, parent, labels):
        self.name = name
        self.trace = trace
        self.span = span_id
        self.parent = parent
        self.labels = labels
        self.start = time.monotonic()

    def annotate(self, **labels):
        self.labels.update(labels)


@contextlib.contextmanager
def span(name: str, **labels):
    """Open a span: child of the ambient span (same trace), or the root
    of a fresh trace. Emits one ``span`` timeline event on exit with
    the measured duration; an exception marks ``status=error`` and
    propagates."""
    parent = current()
    trace = parent["trace"] if parent else _new_id()
    sid = _new_id()
    prev = getattr(_tls, "ctx", None)
    _tls.ctx = {"trace": trace, "span": sid}
    sp = Span(name, trace, sid, parent["span"] if parent else "", labels)
    status = "ok"
    try:
        # numbers only: a string could hold the separators of the
        # annotation's own metadata encoding
        with annotation(name, **{
            k: v for k, v in labels.items() if isinstance(v, (int, float))
        }):
            yield sp
    except BaseException:
        status = "error"
        raise
    finally:
        _tls.ctx = prev
        telemetry.event(
            SPAN_EVENT,
            name=name,
            trace=trace,
            span=sid,
            parent=sp.parent,
            dur=time.monotonic() - sp.start,
            status=status,
            **sp.labels,
        )


# -------------------------------------------------------------------------
# legs: a root span cut into consecutive children
# -------------------------------------------------------------------------


def _emit_leg(name, trace, sid, parent, end_t, dur, status="ok", **labels):
    """One finished span whose end may lie in the past (``t`` is given,
    not stamped), and its seconds onto the counter ``<name>_s``: the
    one place a leg becomes an event and a counter."""
    telemetry.event(
        SPAN_EVENT, t=end_t, name=name, trace=trace, span=sid,
        parent=parent, dur=dur, status=status, **labels,
    )
    telemetry.counter_inc(name + "_s", dur)


class Legs:
    """A root span whose direct children are consecutive legs on the
    wall clock: :meth:`advance` ends the open leg and begins the next
    at the same instant, and a leg begun after a pause begins where
    the last one ended, so the legs tile the root. A leg is emitted
    when it ends (``span`` event + ``<name>_s`` counter), the root by
    :meth:`close` in the process that ``closes_root``.

    ``order``: names that may only be reached in this order, each once
    (a second ``require_backend()`` must not reopen ``start.backend``).
    ``filler``: the leg that takes the time between two block legs.
    ``ambient``: the open leg is every thread's fallback parent.
    """

    def __init__(self, name, t0=None, ctx=None, labels=None,
                 closes_root=True, order=(), filler=None, ambient=False):
        self.name = name
        self.t0 = time.time() if t0 is None else float(t0)
        self.trace = str(ctx["trace"]) if ctx else _new_id()
        self.span = str(ctx["span"]) if ctx else _new_id()
        self.labels = dict(labels or {})
        self.closes_root = closes_root
        self.closed = False
        self._order, self._reached = tuple(order), -1
        self.filler, self._ambient = filler, ambient
        self._cursor = self.t0  # where the last leg ended
        self._open = None       # [name, span id, start, labels]

    @property
    def open_name(self) -> str | None:
        return self._open[0] if self._open else None

    def _may(self, name) -> bool:
        if self.closed:
            return False
        if name in self._order:
            index = self._order.index(name)
            if index <= self._reached:
                return False
            self._reached = index
        return True

    def _end_open(self, t, status="ok"):
        if self._open is None:
            return
        name, sid, start, labels = self._open
        self._set_open(None)
        self._cursor = max(t, start)
        _emit_leg(name, self.trace, sid, self.span, self._cursor,
                  self._cursor - start, status, **labels)

    def _set_open(self, leg):
        global _process_ctx
        self._open = leg
        if self._ambient:
            _process_ctx = None if leg is None else {
                "trace": self.trace, "span": leg[1],
            }

    def advance(self, name, t=None, **labels) -> bool:
        """End the open leg at ``t`` (now) and begin ``name`` there.
        False, and nothing done, where ``order`` refuses the name."""
        if not self._may(name):
            return False
        self._end_open(time.time() if t is None else float(t))
        self._set_open([name, _new_id(), self._cursor, labels])
        return True

    def annotate(self, **labels):
        """Labels for the open leg."""
        if self._open is not None:
            self._open[3].update(labels)

    @contextlib.contextmanager
    def leg(self, name, **labels):
        """A leg that is a block of code: the ambient parent of the
        spans opened inside it, a ``TraceAnnotation`` like any span,
        ended where the block ends (the filler takes over)."""
        if not self.advance(name, **labels):
            yield None
            return
        sid = self._open[1]
        prev = getattr(_tls, "ctx", None)
        _tls.ctx = {"trace": self.trace, "span": sid}
        status = "ok"
        try:
            with annotation(name):
                yield self
        except BaseException:
            status = "error"
            raise
        finally:
            _tls.ctx = prev
            if self._open is not None and self._open[1] == sid:
                self._end_open(time.time(), status)
                if self.filler:
                    self.advance(self.filler)

    def close(self, t=None, status="ok", **labels):
        """End the open leg and the whole: the root's ``dur`` runs from
        ``t0`` (a death, a launch) to here."""
        if self.closed:
            return
        t = time.time() if t is None else float(t)
        self._end_open(t)
        self.closed = True
        if self.closes_root:
            _emit_leg(self.name, self.trace, self.span, "", self._cursor,
                      self._cursor - self.t0, status,
                      **{**self.labels, **labels})

    def export(self, closes_root: bool) -> str:
        """What ``telemetry.ENV_TRACE`` carries to a spawned process:
        the root (its ids, name, start and labels) and the instant of
        the spawn, now. ``closes_root`` names the one process that
        emits the root."""
        return json.dumps({
            "trace": self.trace, "span": self.span, "name": self.name,
            "t0": self.t0, "labels": self.labels,
            "spawn_t": time.time(), "closes_root": closes_root,
        })

    @classmethod
    def adopted(cls, ctx: dict, **kw) -> "Legs":
        """``export``'s other side, in the spawned process: the same
        root, its first leg beginning at the spawn's instant."""
        legs = cls(
            str(ctx.get("name") or "launch"), ctx.get("t0"), ctx,
            ctx.get("labels"), bool(ctx.get("closes_root")), **kw,
        )
        legs._cursor = float(ctx.get("spawn_t") or legs.t0)
        return legs


def export_ambient(env: dict) -> dict:
    """Hand this thread's ambient span to a process about to be spawned
    with ``env`` (a payload child: the probe): its start-up legs become
    that span's children. No ambient span, no trace: a variable this
    process inherited itself is not passed on."""
    env.pop(telemetry.ENV_TRACE, None)
    ctx = current()
    if ctx:
        env[telemetry.ENV_TRACE] = json.dumps({
            **ctx, "spawn_t": time.time(), "closes_root": False,
        })
    return env


# a process's own start, in order; the filler is what the script does
# between them (its own imports and set-up, a benchmark's checks)
START_LEGS = (
    "start.exec", "start.imports", "start.backend", "start.trainer_init",
    "start.restore", "start.compile", "start.first_step",
)
START_FILLER = "start.script"


def _process_legs() -> Legs | None:
    """This process's start-up legs, begun in retrospect at import:
    under the root its launcher exported (``start.exec`` from the
    spawn's instant), or under a ``launch`` of its own from the instant
    the kernel started it. ``start.exec`` ends at the package's first
    import line."""
    if telemetry.active_registry() is None:
        return None
    ctx = telemetry.INHERITED_TRACE
    if ctx:
        legs = Legs.adopted(
            ctx, order=START_LEGS, filler=START_FILLER, ambient=True
        )
    else:
        legs = Legs(
            "launch", telemetry.process_start_time(),
            order=START_LEGS, filler=START_FILLER,
        )
    legs.advance("start.exec")
    legs.advance("start.imports", t=telemetry.IMPORT_T)
    return legs


_startup: Legs | None = _process_legs()


def startup() -> Legs | None:
    """The process's start-up legs (None with telemetry off, or in a
    process that is no launch: a test runner)."""
    return _startup


def reset_startup(legs: Legs | None) -> Legs | None:
    """Replace the process's start-up legs (tests; a test runner drops
    its own with None). Returns the previous ones."""
    global _startup, _process_ctx
    prev, _startup, _process_ctx = _startup, legs, None
    return prev


def start_advance(name: str) -> bool:
    legs = _startup
    return legs is not None and legs.advance(name)


@contextlib.contextmanager
def start_leg(name: str, **labels):
    """``Legs.leg`` of the process's start-up legs; a plain block once
    they are closed. Also a decorator."""
    legs = _startup
    if legs is None:
        yield None
        return
    with legs.leg(name, **labels) as leg:
        yield leg


def start_end(name: str):
    """End the start-up leg ``name`` where it is the open one (a leg
    that began at one call and ends at another's return)."""
    legs = _startup
    if legs is not None and legs.open_name == name:
        legs.advance(START_FILLER)


def start_done():
    """The start is over (a worker's first completed step, a master
    that serves): close the legs, and the root where this process is
    the one to."""
    legs = _startup
    if legs is not None:
        legs.close()


def spans_from_xplane(path: str) -> list[dict]:
    """The program's spans as a profiler session recorded them: every
    ``dlrover.*`` event of the host planes of one ``.xplane.pb``, as
    ``{"name" (without the prefix), "start_ns", "dur_ns", "thread",
    "stats"}`` on the profiler's clock, the one the device planes of
    the same file are on. Spans of one thread nest; ``thread`` tells
    the loop's from the parse thread's."""
    from jax.profiler import ProfileData

    spans = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for index, line in enumerate(plane.lines):
            thread = f"{plane.name}/{index}:{line.name}"
            for event in line.events:
                if event.name.startswith(ANNOTATION_PREFIX):
                    spans.append({
                        "name": event.name[len(ANNOTATION_PREFIX):],
                        "start_ns": float(event.start_ns),
                        "dur_ns": float(event.duration_ns),
                        "thread": thread,
                        "stats": dict(event.stats),
                    })
    return spans


# -------------------------------------------------------------------------
# rendering (obs_report --trace)
# -------------------------------------------------------------------------


def span_events(events) -> list[dict]:
    return [e for e in events if e.get("kind") == SPAN_EVENT]


def trace_trees(events) -> list[dict]:
    """Rebuild the span forest from (merged) timeline events.

    Returns one dict per trace, newest-rooted-first::

        {"trace": id, "roots": [node...], "spans": n}
        node = {"event": span_event, "children": [node...]}

    A span whose parent never made it into the ring (evicted, or the
    parent process never flushed) is promoted to a root rather than
    dropped — a partial trace is still evidence.
    """
    by_trace: dict[str, list[dict]] = {}
    for ev in span_events(events):
        if ev.get("trace") and ev.get("span"):
            by_trace.setdefault(ev["trace"], []).append(ev)
    out = []
    for trace, evs in by_trace.items():
        nodes = {
            e["span"]: {"event": e, "children": []} for e in evs
        }
        roots = []
        for e in evs:
            node = nodes[e["span"]]
            parent = nodes.get(e.get("parent") or "")
            if parent is not None and parent is not node:
                parent["children"].append(node)
            else:
                roots.append(node)

        def start_of(node):
            e = node["event"]
            return e.get("t", 0.0) - (e.get("dur") or 0.0)

        def sort_rec(children):
            children.sort(key=start_of)
            for c in children:
                sort_rec(c["children"])

        sort_rec(roots)
        out.append({"trace": trace, "roots": roots, "spans": len(evs)})
    out.sort(
        key=lambda t: max(
            (n["event"].get("t", 0.0) for n in t["roots"]), default=0.0
        ),
        reverse=True,
    )
    return out


def root_legs(events, names=("resume",)) -> list[dict]:
    """The ``Legs`` roots called one of ``names`` in a merged timeline,
    oldest first, each with its direct children in order:
    ``{"root": span_event, "legs": [span_event...]}`` (what the goodput
    ledger's one ``restart`` number is made of, leg by leg)."""
    out = []
    for tree in trace_trees(events):
        for root in tree["roots"]:
            if root["event"].get("name") in names:
                out.append({
                    "root": root["event"],
                    "legs": [c["event"] for c in root["children"]],
                })
    out.sort(key=lambda r: r["root"].get("t", 0.0))
    return out


def format_trace(events, limit: int = 10) -> str:
    """Text rendering of the span forest: one indented tree per trace,
    each line ``+rel_start  dur  source  name  labels``."""
    trees = trace_trees(events)
    if not trees:
        return "no spans recorded"
    lines = []
    for tree in trees[:limit]:
        t0 = min(
            (
                n["event"].get("t", 0.0) - (n["event"].get("dur") or 0.0)
                for n in tree["roots"]
            ),
            default=0.0,
        )
        lines.append(
            f"trace {tree['trace']}  ({tree['spans']} span"
            f"{'s' if tree['spans'] != 1 else ''})"
        )

        def render(node, depth):
            e = node["event"]
            dur = e.get("dur") or 0.0
            start = e.get("t", 0.0) - dur
            extras = {
                k: v for k, v in e.items()
                if k not in (
                    "seq", "t", "mono", "kind", "source", "name",
                    "trace", "span", "parent", "dur", "status",
                )
            }
            extra_s = " ".join(
                f"{k}={v:.3f}" if isinstance(v, float) else f"{k}={v}"
                for k, v in extras.items()
            )
            flag = "" if e.get("status", "ok") == "ok" else " [ERROR]"
            lines.append(
                f"  +{start - t0:8.3f}s {dur * 1e3:9.2f}ms  "
                f"{'  ' * depth}{e.get('name', '?')}"
                f"  <{e.get('source', '?')}>{flag}"
                + (f"  {extra_s}" if extra_s else "")
            )
            for c in node["children"]:
                render(c, depth + 1)

        for root in tree["roots"]:
            render(root, 0)
        lines.append("")
    if len(trees) > limit:
        lines.append(f"... {len(trees) - limit} more trace(s) omitted")
    return "\n".join(lines)
