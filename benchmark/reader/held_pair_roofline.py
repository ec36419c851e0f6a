"""A routed layer's grouped matmuls' share of their roofline, percent,
counted over the token-expert pairs the step really sent to the experts
held here: the program's own count of them (``held``, a field of its
events of ``kind``, one a step whose counts it brought back; the event
inside the profiler's stretch where there is one, else the mean of the
window's) is handed to the family's count of the kernel's work
(``work``, as ``sizes["held_pairs"]``), and the least time one chip
could take over that work is divided by the device time a step of the
events that match ``pattern``. Nothing where the program counts no held
pair, the kernels did not run or the family has no count: never 0."""

import families
import readers


def read(record: dict, params: dict):
    ms = readers.trace_op_ms(record, {"pattern": params["pattern"]})
    peak_flops = record.get("peak_flops")
    peak_bytes = record.get("peak_hbm_bytes_per_s")
    if not ms or not peak_flops or not peak_bytes:
        return None
    events = [e for e in record.get("events") or []
              if e.get("kind") == params["kind"] and params["held"] in e]
    traced = record.get("traced_wall")
    inside = [e for e in events
              if traced and traced[0] <= e["t"] <= traced[1]]
    held = [e[params["held"]] for e in inside or events]
    if not held or not sum(held):
        return None
    sizes = dict(record["sizes"], held_pairs=sum(held) / len(held))
    work = families.kernel_work(record["family"], sizes, params["work"])
    if work is None:
        return None
    flops, hbm_bytes = work
    least_s = max(flops / peak_flops, hbm_bytes / peak_bytes)
    return 100.0 * least_s / record["chips"] / (ms / 1e3)
