"""Cores the whole server process used over the window: growth of
`dgraph_process_cpu_seconds_total` (`time.process_time()`, read when
/metrics is rendered) / the window's seconds — handler threads, the accept
loop, the batcher's timer, the runtime's own threads. About 1 with the
handler threads' share (`stage.cpu_ms_per_op` x `ops_per_s`) near it says
one interpreter is the limit. A program without the counter: None."""

from harness import stage_cpu

SERIES = "dgraph_process_cpu_seconds_total"


def read(run):
    if SERIES not in run.after["prom"]:
        return None
    return run.grown(SERIES) / stage_cpu.window_s(run)
