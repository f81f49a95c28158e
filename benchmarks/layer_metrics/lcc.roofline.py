"""Memory-roofline share of the LCC program (`jit_analytics_lcc`): the
compared `gx_lcc` requests' mean needed_bytes (4 B × Σ |R(u)| + |R(v)|
over the degree-ordered edges + 8 B a vertex: both rows of every edge read
once, a count out a vertex) x the `gx_lcc` requests completed in the
traced interval, over the HBM peak, over the program's own device seconds
from the trace reduction. Nothing without a trace, or with the program not
listed."""

from harness import lcc


def read(run):
    return lcc.roofline(run)
