"""CPU milliseconds a request's thread worked in the executor on the host:
stages `exec` + `exec.prep` (`exec.host_ms_per_op`, `khop.host_ms_per_op`
and `par.host_ms_per_op` are their wall time, which under 22 threads is
mostly the queue for the interpreter). Program counter:
harness/stage_cpu.py."""

from harness import stage_cpu


def read(run):
    return stage_cpu.cpu_per_op_ms(run, "exec", "exec.prep")
