"""LDBC Graphalytics' PR and WCC (specification v1.0) as the `gx_pr` and
`gx_wcc` ops ask them of the served node over `follows`:

    POST /analytics {"kind": "pr", "pred": "follows", "iterations": 10,
                     "damping": 0.85, "uids": [<64 probes>], "top": 20}
    POST /analytics {"kind": "wcc", "pred": "follows", "uids": [...]}

The vertex set is every vertex with an edge. PR: `ITERATIONS` steps from
1/N, each PR(v) = (1 - d) / N + d * (the in-neighbours' PR / out-degree +
the dangling vertices' PR / N). WCC: the weakly connected components, each
vertex labelled by its component's least member (the specification asks
for the same partition; the least member makes it one answer).

The reference is the benchmark's own, in float64 over the benchmark's own
CSR (scipy's sparse product for a PR step, scipy's connected_components
for WCC), memoised on the Graph object: once per graph in a worker. It
imports nothing of the program. A PR answer matches when every probe's
rank, every top-20 score and the sum are within Graphalytics'
epsilon-match (relative error EPSILON); a WCC answer when every label, the
component count and the largest component's size are equal.

`verify` prices an operation for needed_bytes (4 B an edge, 8 B a node):
PR reads every edge and writes every node once a step, ITERATIONS times;
WCC at least once — one pass, the least any WCC moves."""

from __future__ import annotations

import json

import numpy as np
from scipy.sparse.csgraph import connected_components

PROBES = 64
ITERATIONS = 10
DAMPING = 0.85
TOP = 20
EPSILON = 1e-4
PROGRAMS = {"gx_pr": "jit_analytics_pr", "gx_wcc": "jit_analytics_wcc"}


def draw(ctx, rng) -> dict:
    """PROBES distinct probe vertices, uniform over those with an edge."""
    s = ctx.g.subjects
    return {"uids": sorted(int(u) for u in
                           rng.choice(s, size=min(PROBES, len(s)),
                                      replace=False))}


def request(kind: str, p: dict):
    body = {"kind": kind, "pred": "follows",
            "uids": [hex(u) for u in p["uids"]]}
    if kind == "pr":
        body.update(iterations=ITERATIONS, damping=DAMPING, top=TOP)
    return "POST", "/analytics", json.dumps(body)


def _vertices(g) -> np.ndarray:
    """uids with an out- or in-edge."""
    indeg = np.bincount(g.indices, minlength=g.n)
    return np.flatnonzero((g.degree > 0) | (indeg > 0))


def pr_reference(g) -> dict:
    """{"rank": float64[n] by uid (0 off the vertex set), "nodes",
    "edges", "sum"}."""
    got = g.__dict__.get("_gx_pr")
    if got is None:
        a = g.csr
        nodes = _vertices(g)
        n = len(nodes)
        inside = np.zeros(g.n, dtype=bool)
        inside[nodes] = True
        deg = g.degree.astype(np.float64)
        sink = inside & (g.degree == 0)
        inv = np.where(deg > 0, 1.0 / np.maximum(deg, 1.0), 0.0)
        r = np.where(inside, 1.0 / n, 0.0)
        for _ in range(ITERATIONS):
            pulled = a.T @ (r * inv)
            r = np.where(inside, (1.0 - DAMPING) / n
                         + DAMPING * (pulled + r[sink].sum() / n), 0.0)
        got = g.__dict__["_gx_pr"] = {"rank": r, "nodes": n,
                                      "edges": len(g.indices),
                                      "sum": float(r.sum())}
    return got


def wcc_reference(g) -> dict:
    """{"label": int64[n] by uid (the least member of its component), the
    component count, the largest's size, "nodes", "edges"}."""
    got = g.__dict__.get("_gx_wcc")
    if got is None:
        nodes = _vertices(g)
        _, comp = connected_components(g.csr, directed=True,
                                       connection="weak")
        mine = comp[nodes]
        least = np.full(int(mine.max()) + 1, g.n, dtype=np.int64)
        np.minimum.at(least, mine, nodes)
        label = np.full(g.n, -1, dtype=np.int64)
        label[nodes] = least[mine]
        sizes = np.bincount(mine)
        got = g.__dict__["_gx_wcc"] = {
            "label": label, "components": int((sizes > 0).sum()),
            "largest": int(sizes.max()), "nodes": len(nodes),
            "edges": len(g.indices)}
    return got


def _answer(data: dict) -> dict:
    out = data.get("analytics") if isinstance(data, dict) else None
    return out if isinstance(out, dict) else {}


def parse_pr(data: dict) -> dict:
    a = _answer(data)
    try:
        return {"values": {int(k, 16): v for k, v in
                           (a.get("values") or {}).items()},
                "top": [(int(t["uid"], 16), t["score"])
                        for t in a.get("top") or ()],
                "sum": a.get("sum"), "iterations": a.get("iterations"),
                "nodes": a.get("nodes"), "edges": a.get("edges")}
    except (TypeError, ValueError, KeyError, AttributeError):
        return {"values": {}}


def answer_pr(g, p: dict) -> dict:
    ref = pr_reference(g)
    r = ref["rank"]
    order = np.argsort(-r, kind="stable")[:TOP]
    return {"values": {u: float(r[u]) for u in p["uids"]},
            "top": [(int(u), float(r[u])) for u in order],
            "sum": ref["sum"], "iterations": ITERATIONS,
            "nodes": ref["nodes"], "edges": ref["edges"]}


def _close(got, want: float) -> bool:
    return isinstance(got, (int, float)) and \
        abs(got - want) <= EPSILON * abs(want)


def verify_pr(g, p: dict, got: dict):
    ref = pr_reference(g)
    stats = {"edges": ITERATIONS * ref["edges"],
             "nodes": ITERATIONS * ref["nodes"]}
    r = ref["rank"]
    vals = got.get("values") or {}
    for u in p["uids"]:
        if not _close(vals.get(u), r[u]):
            return (f"rank of {u:#x} {vals.get(u)}, the reference "
                    f"{r[u]:.9g}"), stats
    if not isinstance(got.get("sum"), (int, float)) or \
            abs(got["sum"] - ref["sum"]) > EPSILON * ref["sum"]:
        return f"sum {got.get('sum')}, the reference {ref['sum']}", stats
    top = got.get("top") or []
    want_top = np.sort(r)[::-1][:TOP]
    if len(top) != len(want_top) or len({u for u, _ in top}) != len(top):
        return f"{len(top)} top vertices", stats
    for u, score in top:
        if not (0 <= u < g.n) or not _close(score, r[u]):
            return f"top score of {u:#x} {score}", stats
    if min(s for _, s in top) < want_top[-1] * (1 - EPSILON):
        return "the top list misses a higher rank", stats
    for key in ("nodes", "edges"):
        if got.get(key) != ref[key]:
            return f"{key} {got.get(key)}, the reference {ref[key]}", stats
    if got.get("iterations") != ITERATIONS:
        return f"iterations {got.get('iterations')}", stats
    return None, stats


def parse_wcc(data: dict) -> dict:
    a = _answer(data)
    try:
        return {"labels": {int(k, 16): (int(v, 16) if v else None)
                           for k, v in (a.get("labels") or {}).items()},
                "components": a.get("components"),
                "largest": a.get("largest"), "nodes": a.get("nodes"),
                "edges": a.get("edges")}
    except (TypeError, ValueError, AttributeError):
        return {"labels": {}}


def answer_wcc(g, p: dict) -> dict:
    ref = wcc_reference(g)
    return {"labels": {u: int(ref["label"][u]) for u in p["uids"]},
            **{k: ref[k] for k in ("components", "largest", "nodes",
                                   "edges")}}


def verify_wcc(g, p: dict, got: dict):
    ref = wcc_reference(g)
    stats = {"edges": ref["edges"], "nodes": ref["nodes"]}
    labels = got.get("labels") or {}
    for u in p["uids"]:
        if labels.get(u) != int(ref["label"][u]):
            return (f"label of {u:#x} {labels.get(u)}, the reference "
                    f"{int(ref['label'][u]):#x}"), stats
    for key in ("components", "largest", "nodes", "edges"):
        if got.get(key) != ref[key]:
            return f"{key} {got.get(key)}, the reference {ref[key]}", stats
    return None, stats


# --- what the gx.* readers share ------------------------------------------

def program_seconds(run, program: str) -> float | None:
    """Device seconds of one whole program in the traced interval, from
    the trace reduction's device_ops ("program <name>"); None when the
    program is not listed."""
    tr = run.trace or {}
    for name, secs in tr.get("device_ops", []):
        if name == f"program {program}":
            return float(secs)
    return None


def roofline(run, op: str) -> float | None:
    """Memory-roofline share of the op's program: its compared mean
    needed_bytes x its requests completed in the traced interval, over
    the HBM peak, over the program's own device seconds. Bound: memory —
    a step reads 4 B an edge from HBM (the edge list does not fit VMEM)."""
    from harness import stats
    from harness.roofline import peaks

    secs = program_seconds(run, PROGRAMS[op])
    if not secs or run.trace_span is None:
        return None
    lo, hi = run.trace_span
    mine = [r for r in run.reqs if r["op"] == op]
    done = sum(1 for r in mine if stats.good(r) and lo <= r["t_done"] <= hi)
    mean = stats.mean_of_compared(mine, "needed_bytes")
    if not done or not mean:
        return None
    least_s = done * mean / peaks(run.device["kind"])["hbm_bytes_per_s"]
    return 100.0 * least_s / secs


SERIES = "dgraph_analytics_%s_total{kind=\"%s\"}"
HOST = "dgraph_analytics_host_runs_total{"


def runs(run, kind: str | None = None) -> tuple[float, float] | None:
    """(device runs, host runs) of one kind, or of both, over the window;
    None for a program without the device counter."""
    kinds = (kind,) if kind else ("pr", "wcc")
    if any(SERIES % ("device_runs", k) not in run.after["prom"]
           for k in kinds):
        return None
    dev = sum(run.grown(SERIES % ("device_runs", k)) for k in kinds)
    host = sum(run.grown(s) for s in run.after["prom"]
               if s.startswith(HOST)
               and any(f'kind="{k}"' in s for k in kinds))
    return dev, host
