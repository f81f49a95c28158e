"""Median client latency of the window's `khop1` requests: the k-hop
neighbour count at k = 1."""

from harness import stats


def read(run):
    return stats.median_ms(run.reqs, "khop1")
