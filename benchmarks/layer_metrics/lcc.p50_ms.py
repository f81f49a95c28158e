"""Median client latency of the window's `gx_lcc` requests: Graphalytics
LCC over the whole graph, 64 probes, the triangle count and the sum."""

from harness import stats


def read(run):
    return stats.median_ms(run.reqs, "gx_lcc")
