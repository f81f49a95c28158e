"""Milliseconds a request of the `khop-par22` window spent waiting on the
batcher: stage `batch.wait` — a follower until its leader's launch handed
it its slices (queue for the gate, the device run and the fetch
included), a leader while it held its batch open for companions. Program
counter: harness/stages.py. The stage shows on /metrics from start-up, at
0; a program without it (before PR 36 these waits were part of `exec`):
None."""

from harness import stages


def read(run):
    if stages.SERIES % "batch.wait" not in run.after["prom"]:
        return None
    return stages.per_op_ms(run, "batch.wait")
