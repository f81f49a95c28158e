"""Tasks a launch of the device batcher (query/batch.py) answered in the
window, on average: growth of `dgraph_batch_tasks_total` / growth of
`dgraph_batch_formed_total` (/metrics). 1 when every request ran alone, up
to the batcher's capacity (16). 0 when no task reached the batcher (a CPU
rehearsal serves the traversal from the host mirror). A program without
the counters: None."""

TASKS = "dgraph_batch_tasks_total"
LAUNCHES = "dgraph_batch_formed_total"


def read(run):
    if TASKS not in run.after["prom"] or LAUNCHES not in run.after["prom"]:
        return None
    launches = run.grown(LAUNCHES)
    return run.grown(TASKS) / launches if launches else 0.0
