"""Milliseconds a request of the window spent before the executor: stage
`parse` (DQL text to tree, through the plan cache) plus `plan` (read view,
schema view, fold prefetch, result-cache key and probe, plan cache or
build_plan, residency prefetch, and Node.query's own bookkeeping).
Program counter: harness/stages.py."""

from harness import stages


def read(run):
    return stages.per_op_ms(run, "parse", "plan")
