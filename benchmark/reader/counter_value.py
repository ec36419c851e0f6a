"""One of the program's counters, or several of them summed, as it
stands when the run's record is taken (``counter``: a name or a list of
names, times ``scale``). The counters are cumulative since the process
started, so this reads what happened before the window as well as in
it: the legs of set-up, which the program books to ``<leg>_s`` counters
(``start.backend_s``, ``start.compile_s``, ...), and the persistent
compile cache's verdicts. Nothing where the program has no such counter
(a program older than the counter; a leg that did not run): of several
names those that are there are summed, and nothing where none is."""


def read(record: dict, params: dict):
    names = params["counter"]
    if isinstance(names, str):
        names = [names]
    counters = record.get("counters") or {}
    values = [counters[name] for name in names if name in counters]
    return params.get("scale", 1.0) * sum(values) if values else None
