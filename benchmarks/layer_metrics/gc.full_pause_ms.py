"""Milliseconds a full collection (generation 2) of the window took: growth
of `dgraph_gc_pause_us_total{generation="2"}` / growth of
`dgraph_gc_collections_total{generation="2"}` / 1000; 0.0 when no full
collection ran in the window. The stall a tail sees where `p95_ms` cannot.
A program without the series: None."""

from harness import stage_cpu


def read(run):
    if stage_cpu.GC_PAUSE % "2" not in run.after["prom"]:
        return None
    n = run.grown(stage_cpu.GC_COUNT % "2")
    return run.grown(stage_cpu.GC_PAUSE % "2") / n / 1000.0 if n else 0.0
