"""Milliseconds a request of the `khop-par22` window spent on the host
with its fetched level mask: stage `dev.post` — the unpack, ranks to uids,
the variable; every request has it, leader or follower. Program counter:
harness/stages.py."""

from harness import stages


def read(run):
    return stages.per_op_ms(run, "dev.post")
