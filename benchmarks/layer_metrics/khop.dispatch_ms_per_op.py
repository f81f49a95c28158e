"""Milliseconds a `khop` request spent handing its traversal to the device:
stage `dev.dispatch` — the seed mask's eager programs (a zeros and a
scatter a request) and the jitted pb.recurse_fused call, until it returned
its futures. Program counter: harness/stages.py. A program that does not
split the recurse window reads 0 here and the whole window under
khop.wait_ms_per_op."""

from harness import stages


def read(run):
    return stages.per_op_ms(run, "dev.dispatch")
