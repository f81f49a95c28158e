"""Milliseconds an LCC request spent on the host outside every device
stage: `plan` (read view, snapshot, the tablet's layout lookup) + `exec`
(the host oracle, where it runs) + `exec.prep` (pull_graph_for and the
degree-ordered rows, on a snapshot's first request). Program counter:
harness/stages.py."""

from harness import stages


def read(run):
    return stages.per_op_ms(run, "plan", "exec", "exec.prep")
