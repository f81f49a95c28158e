"""CPU milliseconds the handler thread of a request worked, over every
stage of its clock: growth of every `dgraph_stage_cpu_us_total{stage=}` /
growth of `dgraph_stage_cpu_requests_total` (the closed requests that read
the CPU clock: one in `costs.CPU_EVERY`) / 1000. Times `ops_per_s` it is the cores the handler
threads used; CPU burnt in C with the interpreter released (numpy, the jit
call) is in it, so that product can pass 1. A program without the series:
None."""

from harness import stage_cpu


def read(run):
    return stage_cpu.cpu_per_op_ms(run)
