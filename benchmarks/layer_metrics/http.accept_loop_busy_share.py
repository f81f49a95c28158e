"""Share of the window the server's accept loop could not be accepting:
100 x growth of `dgraph_http_accept_loop_us_total` (microseconds from
`accept()` returned to `process_request` returned: a `Thread` made and
started, which waits until the new thread has had the interpreter once) /
the window's microseconds. Near 100 the kernel's listen backlog is where
requests queue, which no stage can see. A program without the counter:
None."""

from harness import stage_cpu

SERIES = "dgraph_http_accept_loop_us_total"


def read(run):
    if SERIES not in run.after["prom"]:
        return None
    return 100.0 * run.grown(SERIES) / (stage_cpu.window_s(run) * 1e6)
