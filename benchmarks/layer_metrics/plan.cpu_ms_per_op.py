"""CPU milliseconds a request's thread worked in stages `parse` + `plan`
(`plan.ms_per_op` is their wall time). Program counter:
harness/stage_cpu.py."""

from harness import stage_cpu


def read(run):
    return stage_cpu.cpu_per_op_ms(run, "parse", "plan")
