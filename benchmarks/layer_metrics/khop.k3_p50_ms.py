"""Median client latency of the window's `khop3` requests: the k-hop
neighbour count at k = 3."""

from harness import stats


def read(run):
    return stats.median_ms(run.reqs, "khop3")
