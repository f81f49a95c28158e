"""Per-level uid matrices the program materialised on the host, a request
of the window: growth of `dgraph_recurse_materialized_total` over the
requests whose clock closed. A `var` block renders nothing, so the cell
should read 0. A program without the counter: None."""

from harness import stages

SERIES = "dgraph_recurse_materialized_total"


def read(run):
    n = stages.closed_requests(run)
    if n is None or SERIES not in run.after["prom"]:
        return None
    return run.grown(SERIES) / n
