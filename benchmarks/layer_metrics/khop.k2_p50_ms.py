"""Median client latency of the window's `khop2` requests: the k-hop
neighbour count at k = 2."""

from harness import stats


def read(run):
    return stats.median_ms(run.reqs, "khop2")
