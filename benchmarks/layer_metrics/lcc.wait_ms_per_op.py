"""Milliseconds an LCC request spent blocked in the fetch: stage
`dev.wait` — the device running the whole job, then the probes' counts
and ratios and the two sums coming back. Program counter:
harness/stages.py."""

from harness import stages


def read(run):
    return stages.per_op_ms(run, "dev.wait")
