"""LDBC Graphalytics' LCC (specification v1.0) as the `gx_lcc` op asks it
of the served node over `follows`:

    POST /analytics {"kind": "lcc", "pred": "follows", "uids": [<64 probes>]}

The vertex set is every vertex with an edge. Over the symmetrised simple
graph (both directions of every edge, self-loops and repeats dropped),
t(v) is the number of edges among v's distinct neighbours and lcc(v) =
t(v) / (d(v) (d(v) - 1) / 2), 0 where the degree d(v) < 2. The answer
holds each probe's lcc (`values`) and t (`triangles`), the triangle count
Σ t / 3 (`total`), Σ lcc over every vertex (`sum`), `nodes` and `edges`.

The reference is the benchmark's own, over the benchmark's own CSR: the
edges oriented from the lower to the higher end of the order by (degree,
uid) as an oriented matrix O, then in row blocks (O @ O) ∘ O, whose row
sums count each vertex's triangles as the lowest member and whose column
sums count them as the highest, and (Oᵀ @ O) ∘ O, whose row sums count
them as the middle one. scipy's sparse products in integers, memoised on
the Graph object: once per graph in a compare worker — about 8 s and
under 1 GB at scale 18 on a CPU host, so the eight forked workers compute
it side by side and a run stays well inside its time. It imports nothing
of the program. An answer matches when every probe's `triangles`, the
`total`, `nodes` and `edges` are equal, and every probe's lcc and the sum
are within Graphalytics' epsilon-match (relative error EPSILON).

`needed_bytes` prices an operation at the least a merge-based program
moves: 4 B × Σ (|R(u)| + |R(v)|) over the oriented edges u -> v, where
R(x) is x's out-row in the orientation (both rows read once an edge),
and 8 B a vertex (a count out)."""

from __future__ import annotations

import json

import numpy as np
from scipy.sparse import csr_matrix

from harness.graphalytics import PROBES, EPSILON, _answer, _close, \
    _vertices, draw  # noqa: F401

PROGRAM = "jit_analytics_lcc"
BLOCK = 16384          # rows of O a block of the reference multiplies


def request(p: dict):
    return "POST", "/analytics", json.dumps(
        {"kind": "lcc", "pred": "follows",
         "uids": [hex(u) for u in p["uids"]]})


def _oriented(g):
    """(O as an int32 csr over order positions, position of each uid,
    degree of each uid) of the symmetrised simple graph."""
    a = g.csr + g.csr.T
    a.setdiag(0)
    a.eliminate_zeros()
    a = a.tocsr()
    deg = np.diff(a.indptr).astype(np.int64)
    pos = np.empty(g.n, dtype=np.int64)
    pos[np.lexsort((np.arange(g.n), deg))] = np.arange(g.n)
    coo = a.tocoo()
    s, t = pos[coo.row], pos[coo.col]
    up = s < t
    o = csr_matrix((np.ones(int(up.sum()), np.int32), (s[up], t[up])),
                   shape=(g.n, g.n))
    o.sort_indices()
    return o, pos, deg


def reference(g) -> dict:
    """{"tri": int64[n] and "lcc": float64[n] by uid, "total", "sum",
    "nodes", "edges", "merge": Σ (|R(u)| + |R(v)|) over the oriented
    edges}."""
    got = g.__dict__.get("_lcc")
    if got is None:
        o, pos, deg = _oriented(g)
        ot = o.T.tocsr()
        n = g.n
        t = np.zeros(n, dtype=np.int64)
        for lo in range(0, n, BLOCK):
            hi = min(n, lo + BLOCK)
            low = (o[lo:hi] @ o).multiply(o[lo:hi]).tocsr()
            t[lo:hi] += np.asarray(low.sum(axis=1)).ravel().astype(np.int64)
            t += np.bincount(low.indices, weights=low.data,
                             minlength=n).astype(np.int64)
            mid = (ot[lo:hi] @ o).multiply(o[lo:hi]).tocsr()
            t[lo:hi] += np.asarray(mid.sum(axis=1)).ravel().astype(np.int64)
        tri = t[pos]                               # by uid
        d = deg.astype(np.float64)
        with np.errstate(divide="ignore", invalid="ignore"):
            lcc = np.where(deg > 1, tri / (d * (d - 1) / 2), 0.0)
        nodes = _vertices(g)
        od = np.diff(o.indptr)
        rows = np.repeat(np.arange(n), od)
        got = g.__dict__["_lcc"] = {
            "tri": tri, "lcc": lcc, "total": int(tri.sum() // 3),
            "sum": float(lcc[nodes].sum()), "nodes": len(nodes),
            "edges": len(g.indices),
            "merge": int((od[rows] + od[o.indices]).sum())}
    return got


def parse(data: dict) -> dict:
    a = _answer(data)
    try:
        return {"values": {int(k, 16): v for k, v in
                           (a.get("values") or {}).items()},
                "triangles": {int(k, 16): v for k, v in
                              (a.get("triangles") or {}).items()},
                **{k: a.get(k) for k in ("total", "sum", "nodes",
                                         "edges")}}
    except (TypeError, ValueError, AttributeError):
        return {"values": {}, "triangles": {}}


def answer(g, p: dict) -> dict:
    ref = reference(g)
    return {"values": {u: float(ref["lcc"][u]) for u in p["uids"]},
            "triangles": {u: int(ref["tri"][u]) for u in p["uids"]},
            **{k: ref[k] for k in ("total", "sum", "nodes", "edges")}}


def verify(g, p: dict, got: dict):
    ref = reference(g)
    stats = {"edges": ref["edges"], "nodes": ref["nodes"],
             "merge": ref["merge"]}
    tri = got.get("triangles") or {}
    vals = got.get("values") or {}
    for u in p["uids"]:
        if tri.get(u) != int(ref["tri"][u]):
            return (f"triangles of {u:#x} {tri.get(u)}, the reference "
                    f"{int(ref['tri'][u])}"), stats
        if not _close(vals.get(u), ref["lcc"][u]):
            return (f"lcc of {u:#x} {vals.get(u)}, the reference "
                    f"{ref['lcc'][u]:.9g}"), stats
    for key in ("total", "nodes", "edges"):
        if got.get(key) != ref[key]:
            return f"{key} {got.get(key)}, the reference {ref[key]}", stats
    if not _close(got.get("sum"), ref["sum"]):
        return f"sum {got.get('sum')}, the reference {ref['sum']}", stats
    return None, stats


def needed_bytes(stats: dict) -> int:
    return 4 * int(stats["merge"]) + 8 * int(stats["nodes"])


# --- what the lcc.* readers share ------------------------------------------

DEV = 'dgraph_analytics_device_runs_total{kind="lcc"}'
HOST = "dgraph_analytics_host_runs_total{"
COMPARES = "dgraph_analytics_lcc_compares_total"
MERGE = "dgraph_analytics_lcc_merge_total"


def runs(run) -> tuple[float, float] | None:
    """(device runs, host runs) of `lcc` over the window; None for a
    program without the kind's device counter."""
    if DEV not in run.after["prom"]:
        return None
    host = sum(run.grown(s) for s in run.after["prom"]
               if s.startswith(HOST) and 'kind="lcc"' in s)
    return run.grown(DEV), host


def roofline(run) -> float | None:
    """Memory-roofline share of the program: the compared requests' mean
    needed_bytes x the requests completed in the traced interval, over the
    HBM peak, over the program's own device seconds."""
    from harness import graphalytics, stats
    from harness.roofline import peaks

    secs = graphalytics.program_seconds(run, PROGRAM)
    if not secs or run.trace_span is None:
        return None
    lo, hi = run.trace_span
    mine = [r for r in run.reqs if r["op"] == "gx_lcc"]
    done = sum(1 for r in mine if stats.good(r) and lo <= r["t_done"] <= hi)
    mean = stats.mean_of_compared(mine, "needed_bytes")
    if not done or not mean:
        return None
    least_s = done * mean / peaks(run.device["kind"])["hbm_bytes_per_s"]
    return 100.0 * least_s / secs
