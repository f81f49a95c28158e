"""Median client latency of the window's `shortest` requests."""

from harness import stats


def read(run):
    return stats.median_ms(run.reqs, "shortest")
