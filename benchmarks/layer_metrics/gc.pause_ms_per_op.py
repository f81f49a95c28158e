"""Milliseconds of the interpreter's collector a request of the window:
growth of `dgraph_gc_pause_us_total` over all three generations / requests
closed / 1000. Every thread stands still for a pause, whoever set it off.
A program without the series: None."""

from harness import stage_cpu, stages


def read(run):
    if stage_cpu.GC_PAUSE % "2" not in run.after["prom"]:
        return None
    n = stages.closed_requests(run)
    if n is None:
        return None
    return sum(run.grown(stage_cpu.GC_PAUSE % g) for g in "012") / n / 1000.0
