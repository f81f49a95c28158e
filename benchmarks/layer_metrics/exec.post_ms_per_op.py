"""Milliseconds a request of the window spent between the return of the
fetch and the end of the device window: stage `dev.post` (bit-plane
unpack, predecessor walk). Program counter: harness/stages.py."""

from harness import stages


def read(run):
    return stages.per_op_ms(run, "dev.post")
