"""Of the element pairs the LCC program compared (padding included), the
share a merge would need: 100 x growth of
`dgraph_analytics_lcc_merge_total` (Σ |R(u)| + |R(v)| over the oriented
edges it intersected) / growth of `dgraph_analytics_lcc_compares_total`
(/metrics). A program without the counters, or no device run: None."""

from harness import lcc


def read(run):
    prom = run.after["prom"]
    if lcc.COMPARES not in prom or lcc.MERGE not in prom:
        return None
    compares = run.grown(lcc.COMPARES)
    return 100.0 * run.grown(lcc.MERGE) / compares if compares else None
