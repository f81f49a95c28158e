"""Milliseconds a `khop` request spent on the host with the fetched level
masks: stage `dev.post` — their OR, the unpack, ranks to uids, the
variable. Program counter: harness/stages.py."""

from harness import stages


def read(run):
    return stages.per_op_ms(run, "dev.post")
