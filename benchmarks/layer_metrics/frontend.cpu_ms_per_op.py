"""CPU milliseconds a request's thread worked in the HTTP front end: stages
`http.head` + `http.read` + `http.write` of `dgraph_stage_cpu_us_total`
(`frontend.ms_per_op` is the wall time of the last two). Program counter:
harness/stage_cpu.py."""

from harness import stage_cpu


def read(run):
    return stage_cpu.cpu_per_op_ms(run, "http.head", "http.read",
                                   "http.write")
