"""Median client latency of the window's `khop1` requests in the
`khop-par22` cell: the 1-hop neighbour count with 21 other requests in
flight (`khop.k1_p50_ms` is the same request alone)."""

from harness import stats


def read(run):
    return stats.median_ms(run.reqs, "khop1")
