"""Median client latency of the window's `khop6` requests: the k-hop
neighbour count at k = 6."""

from harness import stats


def read(run):
    return stats.median_ms(run.reqs, "khop6")
