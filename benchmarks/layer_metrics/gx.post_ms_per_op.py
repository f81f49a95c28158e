"""Milliseconds a Graphalytics request spent after the fetch: stage
`dev.post` — ranks to uids and the answer dict. Program counter:
harness/stages.py."""

from harness import stages


def read(run):
    return stages.per_op_ms(run, "dev.post")
