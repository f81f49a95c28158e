"""Memory-roofline share of pb.analytics_wcc (program `jit_analytics_wcc`):
the compared `gx_wcc` requests' mean needed_bytes (one pass: 4 B an edge +
8 B a vertex, the least any WCC moves) x the `gx_wcc` requests completed in
the traced interval, over the HBM peak, over the program's own device
seconds from the trace reduction. Nothing without a trace, or with the
program not listed."""

from harness import graphalytics


def read(run):
    return graphalytics.roofline(run, "gx_wcc")
