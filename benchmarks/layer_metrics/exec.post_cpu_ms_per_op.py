"""CPU milliseconds a request's thread worked in stage `dev.post`: what the
host does with the fetched result (the wall readers are
`exec.post_ms_per_op`, `khop.post_ms_per_op`, `par.post_ms_per_op`).
Program counter: harness/stage_cpu.py."""

from harness import stage_cpu


def read(run):
    return stage_cpu.cpu_per_op_ms(run, "dev.post")
