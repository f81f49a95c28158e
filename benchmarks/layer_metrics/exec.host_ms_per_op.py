"""Milliseconds a request of the window spent in the executor on the host,
outside every device window: stage `exec` (block waves, task dispatch,
host tiers) plus `exec.prep` (a device layout built on the request path:
pull_graph_for — microseconds on a hit). Program counter:
harness/stages.py."""

from harness import stages


def read(run):
    return stages.per_op_ms(run, "exec", "exec.prep")
