"""Milliseconds a request of the window spent between the start of a device
window and the return of the jitted call with its futures: stage
`dev.dispatch` (argument build, eager jnp programs, host-to-device,
enqueue). Program counter: harness/stages.py."""

from harness import stages


def read(run):
    return stages.per_op_ms(run, "dev.dispatch")
