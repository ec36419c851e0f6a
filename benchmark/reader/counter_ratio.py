"""One of the program's counts over another, inside the window (``over``
/ ``under``, times ``scale``): a share or a ratio of what the program
counted of itself. Its counters are cumulative since the process
started, the warm-up included, so the increments are read where the
program leaves them: as the fields ``over`` and ``under`` of the
window's events of ``kind`` (one a step whose counts it brought back;
the profiler's stretch counts too). Nothing where the window holds no
such event (a program that does not count this) or the divisor is 0."""


def read(record: dict, params: dict):
    events = [e for e in record.get("events") or []
              if e.get("kind") == params["kind"]]
    over = sum(e.get(params["over"], 0.0) for e in events)
    under = sum(e.get(params["under"], 0.0) for e in events)
    return params.get("scale", 1.0) * over / under if under else None
