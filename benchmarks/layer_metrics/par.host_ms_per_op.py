"""Milliseconds a request of the `khop-par22` window spent in the executor
on the host, outside every device stage and every wait: `exec` (block
waves, the batcher's seam, the `uid(v)` block and its count) plus
`exec.prep`. In a program without the `batch.wait` and `gate.wait` stages
both waits are in here. Program counter: harness/stages.py."""

from harness import stages


def read(run):
    return stages.per_op_ms(run, "exec", "exec.prep")
