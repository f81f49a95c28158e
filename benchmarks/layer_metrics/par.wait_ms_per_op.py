"""Milliseconds a request of the `khop-par22` window spent blocked in the
fetch of a launch it led (or ran alone): stages `dev.wait` + `dev.window`,
over ALL the window's requests; a follower's share of the same device run
is under par.batch_wait_ms_per_op. Program counter: harness/stages.py."""

from harness import stages


def read(run):
    return stages.per_op_ms(run, "dev.wait", "dev.window")
