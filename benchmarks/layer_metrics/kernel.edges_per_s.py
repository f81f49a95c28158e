"""Edges the plain reference reads for the operations completed in the
window, a second: the mean over the compared sample of the edges the plain
BFS reads for one operation, times the correct operations completed a
second (BASELINE's north-star rate). Per-layer and not end to end: it is
`ops_per_s` weighted by a sample mean whose spread is the pairs' own (a
search reads anything from a few edges to all of them)."""

from harness import stats


def read(run):
    mean = stats.mean_of_compared(run.reqs, "edges")
    if mean is None:
        return None
    return mean * stats.rate(run.reqs, run.t0, run.seconds)
