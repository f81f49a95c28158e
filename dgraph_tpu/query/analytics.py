"""Whole-graph analytics over one uid predicate's tablet: LDBC
Graphalytics' PR, WCC and LCC (specification v1.0), each under two names.

One implementation of each algorithm, behind one door (`run`): a jitted
program over the PullGraph that ``pb.pull_graph_for`` keeps resident per
snapshot (ops/pallas_bfs.analytics_pr / analytics_wcc, ops/lcc.analytics_lcc
over degree-ordered rows built from it; `layout` builds what a program reads
beside it on the snapshot's first request that reads it), and a host
reference over ``graph_arrays`` for delta overlays, residency-deferred
tablets, a source that is no destination (the device's vertex set would be
partial) and, for LCC, a tablet not stored both ways — counted by reason.

KINDS maps a request's kind to its algorithm and its answer: ``pr`` runs
exactly ``iterations`` steps and answers the probes' ranks, ``pagerank``
stops after ``max_iters`` steps or a step whose L1 delta is <= ``tol`` and
answers the top ranks; ``wcc`` labels the probes with their components'
least members, ``cc`` counts the components; ``lcc`` gives the probes the
share of their neighbour pairs that are edges, ``triangles`` the graph's
triangle count. Labels and counts are exact on both paths; ranks are
float32 on the device and float64 on the host, and the answer's ``device``
flag says which ran. A probe (``uids``) that is no vertex answers None.
"""

from __future__ import annotations

from typing import Callable, NamedTuple

import numpy as np

# a request's probe ranks go to the device padded to a multiple of this:
# one program for every probe count up to it
PROBE_CLASS = 64


def graph_arrays(csr):
    """(nodes, esrc, edst): one tablet's edge list in rank space. nodes is
    the sorted union of subjects and targets (int64 uids); esrc/edst are
    int32 node ranks per edge — the coordinate system every kernel and
    every oracle below shares."""
    subjects, indptr, indices = csr.host_arrays()
    deg = np.diff(indptr)
    src_u = np.repeat(np.asarray(subjects, dtype=np.int64), deg)
    dst_u = np.asarray(indices, dtype=np.int64)
    nodes = np.unique(np.concatenate([np.asarray(subjects, np.int64),
                                      dst_u]))
    esrc = np.searchsorted(nodes, src_u).astype(np.int32)
    edst = np.searchsorted(nodes, dst_u).astype(np.int32)
    return nodes, esrc, edst


# ---------------------------------------------------------------------------
# host references (overlay, deferred or partial tablets) — the oracles
# the device programs are tested against
# ---------------------------------------------------------------------------

def pagerank_host(esrc, edst, n: int, *, damping: float = 0.85,
                  tol: float = 1e-6, max_iters: int = 100):
    """float64 power iteration, same update rule and stop criterion as
    the device program (L1 delta <= tol)."""
    if n == 0:
        return np.zeros(0), 0
    r = np.full(n, 1.0 / n)
    outdeg = np.bincount(esrc, minlength=n).astype(np.float64)[:n]
    dang = outdeg == 0
    od = np.maximum(outdeg, 1.0)
    it = 0
    while it < max_iters:
        w = r[esrc] / od[esrc]
        contrib = np.zeros(n)
        np.add.at(contrib, edst, w)
        new = (1.0 - damping) / n + damping * (contrib + r[dang].sum() / n)
        delta = np.abs(new - r).sum()
        r = new
        it += 1
        if delta <= tol:
            break
    return r, it


def cc_host(esrc, edst, n: int):
    """Union-find with union-by-minimum: every component's representative
    is its minimum node rank — the labels pb.analytics_wcc's fixpoint
    gives."""
    parent = np.arange(n, dtype=np.int64)

    def find(x: int) -> int:
        while parent[x] != x:
            parent[x] = parent[parent[x]]
            x = parent[x]
        return x

    for a, b in zip(esrc.tolist(), edst.tolist()):
        ra, rb = find(a), find(b)
        if ra == rb:
            continue
        if ra < rb:
            parent[rb] = ra
        else:
            parent[ra] = rb
    return np.fromiter((find(i) for i in range(n)), np.int64,
                       n).astype(np.int32)


def lcc_host(esrc, edst, n: int) -> tuple[np.ndarray, np.ndarray]:
    """(t, lcc) of every node over the symmetrized simple graph: t(v) the
    edges among v's distinct neighbours, lcc(v) = t(v) / (d(v) (d(v) - 1)
    / 2), 0 where d(v) < 2. Each edge oriented from its lower-degree end;
    a triangle a < b < c is found once, at a -> b, as the common out-
    neighbour c, and counts for all three."""
    a = np.concatenate([esrc, edst]).astype(np.int64)
    b = np.concatenate([edst, esrc]).astype(np.int64)
    keep = a != b
    key = np.unique(a[keep] * n + b[keep])
    u, v = key // n, key % n
    deg = np.bincount(u, minlength=n)
    pos = np.empty(n, dtype=np.int64)
    pos[np.lexsort((np.arange(n), deg))] = np.arange(n)
    up = pos[u] < pos[v]
    u, v = u[up], v[up]                  # sorted by u, then by v
    starts = np.searchsorted(u, np.arange(n + 1))
    tri = np.zeros(n, dtype=np.int64)
    for uu, vv in zip(u.tolist(), v.tolist()):
        common = np.intersect1d(v[starts[uu]:starts[uu + 1]],
                                v[starts[vv]:starts[vv + 1]],
                                assume_unique=True)
        tri[uu] += len(common)
        tri[vv] += len(common)
        tri[common] += 1
    d = deg.astype(np.float64)
    with np.errstate(divide="ignore", invalid="ignore"):
        lcc = np.where(deg > 1, tri / (d * (d - 1) / 2), 0.0)
    return tri, lcc


# ---------------------------------------------------------------------------
# the layouts the device programs read: one cache a snapshot, on the tablet
# ---------------------------------------------------------------------------

def _pull(csr):
    """(PullGraph, why its DST-RANK space is not the vertex set or None,
    whether every edge is stored in both directions). The dst ranks hold
    every vertex with an in-edge; they are the vertex set when every source
    is also a destination. Stored both ways, a WCC round needs only the
    pull, and an in-row is the vertex's whole neighbourhood."""
    from dgraph_tpu.ops import pallas_bfs as pb

    g = pb.pull_graph_for(csr)
    nd = len(g.host_in_subjects)
    reason = None
    if nd == 0:
        reason = "empty"
    elif len(g.host_map_s2d) and int(g.host_map_s2d.max()) >= nd:
        reason = "rank_spaces"           # a source that is no destination
    # an in-row holds its sources in rank order, a forward row its targets
    # in uid order: equal rows, equal graphs
    symmetric = reason is None and len(g.host_subjects) == nd \
        and np.array_equal(g.host_fwd_indptr, g.host_in_iptr) \
        and np.array_equal(g.host_subjects[g.host_in_src],
                           csr.host_arrays()[2])
    return g, reason, bool(symmetric)


def _gather(csr):
    """(GatherLayout, window steps a pass) of the gather by source rank
    that every PR / WCC step makes (pb.gather_sorted over Nd + 1 slots,
    slot Nd the pad edges'), or (None, 0) where the table is too large for
    VMEM and XLA's gather runs."""
    from dgraph_tpu.ops import pallas_bfs as pb

    g = layout(csr, "pull")[0]
    nd = len(g.host_in_subjects)
    src = np.full(g.in_src_pad_d.shape[0], nd, dtype=np.int32)
    src[:g.num_edges] = g.host_map_s2d[g.host_in_src]
    return pb.gather_layout(src, nd + 1)


def _lcc_rows(csr):
    """The degree-ordered rows LCC's program reads (ops/lcc.Layout), built
    from the in-rows of a tablet stored both ways: each is then the
    vertex's neighbours in rank space."""
    from dgraph_tpu.ops import lcc

    g = layout(csr, "pull")[0]
    return lcc.build(g.host_in_iptr, g.host_in_src)


LAYOUTS = {"pull": _pull, "gather": _gather, "lcc": _lcc_rows}


def layouts(csr) -> dict:
    """The analytics layouts built so far for the tablet's snapshot, by
    name (LAYOUTS), attached to the tablet beside its PullGraph."""
    got = getattr(csr, "_analytics", None)
    if got is None:
        got = csr._analytics = {}
    return got


def layout(csr, name: str):
    """The tablet's layout `name`, built inside the exec.prep stage on the
    snapshot's first request that reads it: only the algorithms that read
    a layout build it (LCC never the gather layout, PR and WCC never LCC's
    rows)."""
    from dgraph_tpu.obs import costs

    cache = layouts(csr)
    if name not in cache:
        with costs.stage("exec.prep"):
            cache[name] = LAYOUTS[name](csr)
    return cache[name]


def _host_reason(csr) -> str | None:
    """Why a tablet cannot take the device path before its layout is
    looked at: delta overlays (uncompacted commits) and residency-deferred
    tablets stay on the host — cold data must not force HBM pressure."""
    from dgraph_tpu.storage.delta import OverlayCSR

    if isinstance(csr, OverlayCSR):
        return "overlay"
    if getattr(csr, "_mesh_deferred", False):
        return "deferred"
    return None


# ---------------------------------------------------------------------------
# the algorithms: each one's layout, device program, host reference and
# counters
# ---------------------------------------------------------------------------

class Request(NamedTuple):
    """A request's knobs once its kind is looked up: PR stops after
    `iterations` steps or a step whose L1 delta is <= `tol` (-1: never)."""

    damping: float
    iterations: int
    tol: float
    top: int


def _stream_attrs(lay) -> dict:
    """Where a PR / WCC step runs — its per-destination reduction:
    "pallas", pb.row_reduce compiled for the chip, or "interpret", Pallas'
    interpreter off the chip; its gather by source rank: "vmem",
    pb.gather_sorted compiled for the chip, "interpret", or "xla", XLA's
    element gather (no layout: a table too large for VMEM) — and the gather
    layout's window steps a pass."""
    from dgraph_tpu.ops import pallas_bfs as pb

    gather, windows = lay
    reduce = "interpret" if pb.interpret_mode() else "pallas"
    return {"reduce": reduce, "windows": windows,
            "gather": "xla" if gather is None
            else "vmem" if reduce == "pallas" else "interpret"}


def _stream_count(metrics, lay, name: str, steps: int) -> None:
    """Of a PR / WCC run's device steps: those whose reduction ran in the
    kernel compiled for the chip, and all of them by gather path."""
    where = _stream_attrs(lay)
    if where["reduce"] == "pallas":
        metrics.keyed("dgraph_analytics_kernel_steps_total",
                      labels=("kind",)).inc(name, steps)
    metrics.keyed("dgraph_analytics_gather_steps_total",
                  labels=("kind", "path")).inc(f"{name}|{where['gather']}",
                                               steps)


def _pr_device(g, lay, symmetric, pad, req, fetch):
    from dgraph_tpu.ops import pallas_bfs as pb

    out = fetch(pb.analytics_pr(
        g.in_src_pad_d, g.in_iptr_rank, g.row_ends, g.out_degree_d, pad,
        np.int32(req.iterations), np.float32(req.damping),
        np.float32(req.tol), lay[0],
        top=max(1, min(req.top, len(g.host_in_subjects)))))
    return out[:4], int(out[4]), {**_stream_attrs(lay),
                                  "iterations": int(out[4])}


def _pr_host(esrc, edst, n, at, req):
    r, steps = pagerank_host(esrc, edst, n, damping=req.damping,
                             tol=req.tol, max_iters=req.iterations)
    order = np.argsort(-r, kind="stable")
    return (r[at] if n else at, r[order], order, r.sum()), steps


def _wcc_device(g, lay, symmetric, pad, req, fetch):
    from dgraph_tpu.ops import pallas_bfs as pb

    out = fetch(pb.analytics_wcc(g.in_src_pad_d, g.in_iptr_rank,
                                 g.row_ends, pad, lay[0],
                                 push=not symmetric))
    return out[:3], int(out[3]), {**_stream_attrs(lay),
                                  "rounds": int(out[3])}


def _wcc_host(esrc, edst, n, at, req):
    lab = cc_host(esrc, edst, n)
    sizes = np.unique(lab, return_counts=True)[1]
    # union-find: one pass of the edges
    return (lab[at] if n else at, len(sizes), sizes.max(initial=0)), 1


def _lcc_device(g, lay, symmetric, pad, req, fetch):
    from dgraph_tpu.ops import lcc

    out = fetch(lcc.analytics_lcc(
        lay.tables, lay.members, lay.tails, lay.heads, lay.head_ids,
        lay.order, lay.degree, lay.adjacency, pad, buckets=lay.buckets,
        core=lay.core, spread=lay.spread))
    return out, 1, {"oriented_edges": lay.oriented_edges,
                    "max_out": lay.max_out, "core": lay.core,
                    "core_edges": lay.core_edges, "total": int(out[2])}


def _lcc_host(esrc, edst, n, at, req):
    tri, lcc = lcc_host(esrc, edst, n)
    return (tri[at] if n else at, lcc[at] if n else at, tri.sum() // 3,
            lcc.sum()), 1


def _lcc_count(metrics, lay, name: str, steps: int) -> None:
    metrics.counter("dgraph_analytics_lcc_compares_total").inc(lay.compares)
    metrics.counter("dgraph_analytics_lcc_merge_total").inc(lay.merge)
    metrics.counter("dgraph_analytics_lcc_core_edges_total").inc(
        lay.core_edges)
    metrics.counter("dgraph_analytics_lcc_oriented_edges_total").inc(
        lay.oriented_edges)


def _at_probes(want, at, vals, cast) -> dict:
    """{probe uid: cast(its value)}, None for a probe that is no vertex."""
    return {hex(int(u)): (cast(v) if i >= 0 else None)
            for u, v, i in zip(want, vals, at)}


def _pr_answer(nodes, want, at, res, top) -> dict:
    ranks, top_v, top_i, total = res
    return {"values": _at_probes(want, at, ranks, float),
            "top": [{"uid": hex(int(nodes[i])), "score": float(v)}
                    for v, i in zip(top_v[:top], top_i[:top])],
            "sum": float(total)}


def _wcc_answer(nodes, want, at, res, top) -> dict:
    return {"labels": _at_probes(want, at, res[0],
                                 lambda lab: hex(int(nodes[lab]))),
            "components": int(res[1]), "largest": int(res[2])}


def _lcc_answer(nodes, want, at, res, top) -> dict:
    return {"values": _at_probes(want, at, res[1], float),
            "triangles": _at_probes(want, at, res[0], int),
            "total": int(res[2]), "sum": float(res[3])}


class Algorithm(NamedTuple):
    """One whole-graph algorithm. `name` labels its device program
    (pb.analytics_<name>) and its counters. Its device program and its
    host reference give the same result, which its answer reads — PR: the
    probes' ranks, the top scores and their vertex ranks, the sum; WCC:
    the probes' labels as vertex ranks, the components, the largest's
    size; LCC: the probes' t and lcc, the triangles, the sum of lcc."""

    name: str
    layout: str        # the LAYOUTS entry its program reads
    both_ways: bool    # its program needs every edge stored both ways
    device: Callable   # (g, layout, symmetric, probes, Request, fetch) ->
    #                    (result, steps, span attributes)
    host: Callable     # (esrc, edst, n, probe ranks, Request) ->
    #                    (result, steps)
    count: Callable    # (metrics, layout, name, steps): its own counters
    answer: Callable   # (nodes, probe uids, their ranks, result, top) ->
    #                    the answer's keys


PR = Algorithm("pr", "gather", False, _pr_device, _pr_host, _stream_count,
               _pr_answer)
WCC = Algorithm("wcc", "gather", False, _wcc_device, _wcc_host,
                _stream_count, _wcc_answer)
LCC = Algorithm("lcc", "lcc", True, _lcc_device, _lcc_host, _lcc_count,
                _lcc_answer)

# kind: (its algorithm; whether PR stops after `max_iters` steps or a step
# whose L1 delta is <= `tol`, else it runs exactly `iterations`; each key
# of its answer from a key of the algorithm's, "steps" the steps it ran)
KINDS = {
    "pr": (PR, False, dict(values="values", top="top", sum="sum",
                           iterations="steps")),
    "pagerank": (PR, True, dict(iterations="steps", top="top")),
    "wcc": (WCC, False, dict(labels="labels", components="components",
                             largest="largest", rounds="steps")),
    "cc": (WCC, False, dict(iterations="steps", components="components",
                            largest="largest")),
    "lcc": (LCC, False, dict(values="values", triangles="triangles",
                             total="total", sum="sum")),
    "triangles": (LCC, False, dict(triangles="total")),
}


# ---------------------------------------------------------------------------
# execution
# ---------------------------------------------------------------------------

def _device(alg: Algorithm, g, lay, symmetric: bool, gate,
            probes: np.ndarray, req: Request):
    """One launch of the algorithm's program inside a gate slot: (its
    result on the host, its steps). The probe ranks cross padded to
    PROBE_CLASS."""
    import jax

    from dgraph_tpu.obs import costs, otrace

    pad = np.zeros(-(-max(len(probes), 1) // PROBE_CLASS) * PROBE_CLASS,
                   dtype=np.int32)
    pad[:len(probes)] = probes
    family = f"pb.analytics_{alg.name}"

    def launch():
        with otrace.span("device_kernel", kernel=family,
                         nodes=len(g.host_in_subjects),
                         edges=g.num_edges) as sp, \
                costs.kernel(family, stage="dev.dispatch") as ck:
            def fetch(out):
                with costs.stage("dev.wait"):
                    out = jax.device_get(out)
                ck.set(h2d=int(pad.nbytes),
                       d2h=int(sum(np.asarray(a).nbytes for a in out)))
                return out

            res, steps, attrs = alg.device(g, lay, symmetric, pad, req,
                                           fetch)
            sp.set(**attrs)
        return res, steps

    return gate.run(launch, klass="analytics") if gate is not None \
        else launch()


def run(kind: str, csr, gate=None, metrics=None, *, damping: float = 0.85,
        tol: float = 1e-6, max_iters: int = 100, top: int = 20,
        iterations: int = 10, uids=()) -> dict:
    """One analytics job of `kind` (KINDS) over one tablet's whole graph:
    on the device over the resident PullGraph, or by the host reference
    over graph_arrays where the device cannot answer (the reason is
    counted). `gate` is the DispatchGate the device program runs inside
    (deadline and shed enforcement); `uids` are the probe vertices."""
    from dgraph_tpu.obs import costs
    from dgraph_tpu.ops.uidset import host_rank_of

    if kind not in KINDS:
        raise ValueError(f"unknown analytics kind {kind!r}; "
                         f"one of {', '.join(KINDS)}")
    alg, stops, keys = KINDS[kind]
    req = Request(float(damping), int(max_iters if stops else iterations),
                  float(tol) if stops else -1.0, max(int(top), 0))
    if req.iterations < 0:
        raise ValueError("analytics: iterations must be >= 0")
    want = np.asarray([int(u, 0) if isinstance(u, str) else int(u)
                       for u in uids], dtype=np.int64)
    reason = _host_reason(csr)
    if reason is None:
        g, reason, symmetric = layout(csr, "pull")
    if reason is None and alg.both_ways and not symmetric:
        reason = "one_way"
    if reason is None:
        lay = layout(csr, alg.layout)
        nodes, edges = g.host_in_subjects, g.num_edges
        at = host_rank_of(nodes, want, -1)
        res, steps = _device(alg, g, lay, symmetric, gate,
                             np.maximum(at, 0), req)
        post = "dev.post"
    else:
        with costs.stage("exec"):
            nodes, esrc, edst = graph_arrays(csr)
            edges = len(esrc)
            at = host_rank_of(nodes, want, -1)
            res, steps = alg.host(esrc, edst, len(nodes), at, req)
        post = "exec"
    with costs.stage(post):
        got = {**alg.answer(nodes, want, at, res, req.top),
               "steps": int(steps)}
        out = {"kind": kind, "nodes": int(len(nodes)), "edges": int(edges),
               "device": reason is None,
               **{key: got[of] for key, of in keys.items()}}
    if metrics is not None:
        name, steps = alg.name, int(steps)
        metrics.counter("dgraph_analytics_runs_total").inc()
        metrics.counter("dgraph_analytics_edges_total").inc(int(edges))
        metrics.counter("dgraph_analytics_iterations_total").inc(steps)
        if reason is None:
            metrics.keyed("dgraph_analytics_device_runs_total",
                          labels=("kind",)).inc(name)
            alg.count(metrics, lay, name, steps)
        else:
            metrics.counter("dgraph_analytics_host_fallbacks_total").inc()
            metrics.keyed("dgraph_analytics_host_runs_total",
                          labels=("kind", "reason")).inc(f"{name}|{reason}")
        metrics.keyed("dgraph_analytics_steps_total",
                      labels=("kind",)).inc(name, steps)
        metrics.keyed("dgraph_analytics_edges_read_total",
                      labels=("kind",)).inc(name, steps * int(edges))
    return out
