"""Of the oriented edges the LCC program ran over, the share inside the
dense core whose triangles the MXU product counts in place of compares:
100 x growth of `dgraph_analytics_lcc_core_edges_total` / growth of
`dgraph_analytics_lcc_oriented_edges_total` (/metrics). A program without
the counters, or no device run: None."""

CORE = "dgraph_analytics_lcc_core_edges_total"
ORIENTED = "dgraph_analytics_lcc_oriented_edges_total"


def read(run):
    prom = run.after["prom"]
    if CORE not in prom or ORIENTED not in prom:
        return None
    oriented = run.grown(ORIENTED)
    return 100.0 * run.grown(CORE) / oriented if oriented else None
