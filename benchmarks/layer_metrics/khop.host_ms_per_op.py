"""Milliseconds a `khop` request spent in the executor on the host, outside
every device stage: `exec` (block waves, the gate and the batcher's seam,
the `uid(v)` block and its count) plus `exec.prep` (pull_graph_for:
microseconds on a hit). Program counter: harness/stages.py."""

from harness import stages


def read(run):
    return stages.per_op_ms(run, "exec", "exec.prep")
