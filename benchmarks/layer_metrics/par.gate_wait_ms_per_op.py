"""Milliseconds a request of the `khop-par22` window spent queued for a
slot of the dispatch gate (width 4): stage `gate.wait`; a batch's queueing
is on its leader's clock only. Program counter: harness/stages.py. The
stage shows on /metrics from start-up, at 0; a program without it (before
PR 36 the queue was part of `exec`): None."""

from harness import stages


def read(run):
    if stages.SERIES % "gate.wait" not in run.after["prom"]:
        return None
    return stages.per_op_ms(run, "gate.wait")
