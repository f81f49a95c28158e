"""Correct operations completed inside the window, over the window's
seconds. A failed or wrong request adds nothing."""

from harness import stats


def read(run):
    return stats.rate(run.reqs, run.t0, run.seconds)
