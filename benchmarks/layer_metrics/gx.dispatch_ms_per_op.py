"""Milliseconds a Graphalytics request spent handing its program to the
device: stage `dev.dispatch` — the padded probe ranks and the jitted
pb.analytics_pr / pb.analytics_wcc call, until it returned its futures.
Program counter: harness/stages.py."""

from harness import stages


def read(run):
    return stages.per_op_ms(run, "dev.dispatch")
