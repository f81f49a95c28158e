"""Milliseconds a request of the window spent making its answer: stage
`encode` (the result tree's build in Executor.execute and json.dumps of the
envelope). Program counter: harness/stages.py."""

from harness import stages


def read(run):
    return stages.per_op_ms(run, "encode")
