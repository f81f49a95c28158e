"""Median client latency of the window's `gx_wcc` requests: Graphalytics
WCC over the whole graph, 64 probes, the count and the largest."""

from harness import stats


def read(run):
    return stats.median_ms(run.reqs, "gx_wcc")
