"""Memory-roofline share of the traversal programs of the `khop-par22`
cell taken together (the stacked pb.recurse_fused_multi and the solo
pb.recurse_fused), by khop.recurse_roofline's arithmetic: the least time
the chip could take for the operations completed in the traced interval
(the compared sample's mean needed_bytes for one operation — 4 B an edge
the plain reference reads, 8 B a node it visits, whatever implements the
launch — times the operations completed in the interval, over the HBM
peak) over the device-busy seconds of that interval. The bytes are the
reference's, so the share cannot pass 100. Bound: memory. Nothing without
a trace."""

from harness import stats
from harness.roofline import peaks


def read(run):
    tr = run.trace
    if not tr or not tr.get("busy_s") or run.trace_span is None:
        return None
    lo, hi = run.trace_span
    done = sum(1 for r in run.reqs
               if stats.good(r) and lo <= r["t_done"] <= hi)
    mean = stats.mean_of_compared(run.reqs, "needed_bytes")
    if not done or not mean:
        return None
    least_s = done * mean / peaks(run.device["kind"])["hbm_bytes_per_s"]
    return 100.0 * least_s / tr["busy_s"]
