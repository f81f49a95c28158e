"""CPU milliseconds a request's thread worked in stage `dev.dispatch`: the
argument build and the jitted call, its C++ included (the wall readers are
`exec.dispatch_ms_per_op`, `khop.dispatch_ms_per_op`,
`par.dispatch_ms_per_op`). Program counter: harness/stage_cpu.py."""

from harness import stage_cpu


def read(run):
    return stage_cpu.cpu_per_op_ms(run, "dev.dispatch")
