"""Milliseconds a request of the window spent blocked on the device: stage
`dev.wait` (in the fetch: the device running, then device-to-host) plus
`dev.window` (the device windows of the sites that are not split into
dispatch / wait / post). Program counter: harness/stages.py."""

from harness import stages


def read(run):
    return stages.per_op_ms(run, "dev.wait", "dev.window")
