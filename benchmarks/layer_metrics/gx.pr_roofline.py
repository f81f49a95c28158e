"""Memory-roofline share of pb.analytics_pr (program `jit_analytics_pr`):
the compared `gx_pr` requests' mean needed_bytes (10 x (4 B an edge + 8 B a
vertex): every edge read, every vertex written, each iteration) x the
`gx_pr` requests completed in the traced interval, over the HBM peak, over
the program's own device seconds from the trace reduction. Nothing without
a trace, or with the program not listed."""

from harness import graphalytics


def read(run):
    return graphalytics.roofline(run, "gx_pr")
