"""Milliseconds a `khop` request spent blocked on the device: stage
`dev.wait` (in the fetch of the level masks: the device running all
`depth` levels, then device-to-host) plus `dev.window` (the windows of
sites that are not split; the whole pb.recurse_fused window in a program
from before the split). Program counter: harness/stages.py."""

from harness import stages


def read(run):
    return stages.per_op_ms(run, "dev.wait", "dev.window")
