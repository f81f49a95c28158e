"""Median client latency of the window's `gx_pr` requests: Graphalytics PR,
10 iterations over the whole graph, 64 probes and the top 20."""

from harness import stats


def read(run):
    return stats.median_ms(run.reqs, "gx_pr")
