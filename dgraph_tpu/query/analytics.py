"""Whole-graph analytics: PageRank / connected components / triangles, and
LDBC Graphalytics' PR, WCC and LCC.

The OLAP workload class beyond the reference (ROADMAP item 3): iterative
SpMSpV programs that the per-query traversal engine cannot express run as
device-resident ``lax.while_loop`` kernels over the mesh-sharded
rank-space edge list (parallel/mesh_exec.run_pagerank / run_cc /
run_triangles — the run_bfs idiom: one collective per iteration, only
the converged vector crosses the host boundary).

Surfaced as Node.analytics(...) + HTTP /analytics; deadline/shed-aware at
the DispatchGate, cost-ledger-attributed, residency-aware: overlay or
residency-deferred tablets (and nodes without a mesh) serve via the host
fallbacks below. CC labels and triangle counts are EXACT either way (CC
converges to the minimum member rank per component on both paths);
PageRank device f32 vs host f64 agree to oracle tolerance, not bitwise —
the result carries a ``device`` flag so callers know which path ran.

Graphalytics' kinds (``pr``, ``wcc``, ``lcc``; specification v1.0) have
the specification's semantics — PR runs exactly ``iterations`` steps, WCC
labels every vertex with its component's least member, LCC gives each
vertex the share of its neighbour pairs that are edges — and answer for the
probe vertices a request names. Their device path needs no mesh: one
jitted program each (ops/pallas_bfs.analytics_pr / analytics_wcc over the
PullGraph that ``pb.pull_graph_for`` keeps resident per snapshot for the
traversal programs, so a request builds no edge list; ops/lcc.analytics_lcc
over the degree-ordered rows ``lcc_layout`` builds from that PullGraph on
the first ``lcc`` request of a snapshot). Overlay or residency-deferred
tablets, a predicate whose sources are not all destinations, and for
``lcc`` one not stored in both directions, run the host oracles under the
same kinds, counted by reason.
"""

from __future__ import annotations

import contextlib

import numpy as np

KINDS = ("pagerank", "cc", "triangles", "pr", "wcc", "lcc")
GX_KINDS = ("pr", "wcc", "lcc")

# a request's probe ranks go to the device padded to a multiple of this:
# one program for every probe count up to it
PROBE_CLASS = 64

# dense trace(A^3) replicates an ncap x ncap f32 adjacency per device —
# past this node count the exact host intersection counter wins
TRI_DENSE_MAX = 2048


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
# host fallbacks (cold tablets / no mesh) — the oracles the device
# programs are tested against
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
    is its minimum node rank — bit-identical to the device label
    propagation's fixpoint."""
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
# execution
# ---------------------------------------------------------------------------

def _device_eligible(mesh, csr) -> bool:
    """Residency gate: the device path re-shards the edge list fresh, but
    overlay tablets (uncompacted deltas) and residency-deferred shards
    stay host-side by policy — cold data must not force HBM pressure."""
    if mesh is None or csr is None:
        return False
    from dgraph_tpu.storage.delta import OverlayCSR

    if isinstance(csr, OverlayCSR):
        return False
    return not getattr(csr, "_mesh_deferred", False)


def run(kind: str, csr, mesh=None, gate=None, metrics=None, *,
        damping: float = 0.85, tol: float = 1e-6, max_iters: int = 100,
        top: int = 20, iterations: int = 10, uids=()) -> dict:
    """One analytics computation over one tablet's whole graph. mesh is a
    parallel/mesh_exec.MeshExecutor (or None → host oracles); gate the
    DispatchGate (deadline/shed enforcement around the device program).
    `iterations` and `uids` (the probe vertices) are Graphalytics'."""
    from dgraph_tpu.obs import costs

    if kind not in KINDS:
        raise ValueError(f"unknown analytics kind {kind!r}; "
                         f"one of {', '.join(KINDS)}")
    if kind in GX_KINDS:
        return _run_gx(kind, csr, gate, metrics, damping=damping,
                       iterations=iterations, top=top, uids=uids)
    nodes, esrc, edst = graph_arrays(csr)
    n = len(nodes)
    device = _device_eligible(mesh, csr)
    if kind == "triangles" and n > TRI_DENSE_MAX:
        device = False
    if metrics is not None:
        metrics.counter("dgraph_analytics_runs_total").inc()
        metrics.counter("dgraph_analytics_edges_total").inc(len(esrc))
        if not device:
            metrics.counter("dgraph_analytics_host_fallbacks_total").inc()

    def gated(fn):
        return gate.run(fn, klass="mesh") if gate is not None else fn()

    out = {"kind": kind, "nodes": int(n), "edges": int(len(esrc)),
           "device": bool(device)}
    if kind == "pagerank":
        with costs.kernel("analytics.pagerank"):
            if device:
                r, it = gated(lambda: mesh.run_pagerank(
                    esrc, edst, n, damping=damping, tol=tol,
                    max_iters=max_iters))
            else:
                r, it = pagerank_host(esrc, edst, n, damping=damping,
                                      tol=tol, max_iters=max_iters)
        order = np.argsort(-np.asarray(r, dtype=np.float64),
                           kind="stable")[: max(int(top), 0)]
        out["iterations"] = int(it)
        out["top"] = [{"uid": hex(int(nodes[i])), "score": float(r[i])}
                      for i in order.tolist()]
    elif kind == "cc":
        with costs.kernel("analytics.cc"):
            if device:
                lab, it = gated(lambda: mesh.run_cc(esrc, edst, n))
            else:
                lab, it = cc_host(esrc, edst, n), 0
        comps, sizes = np.unique(lab, return_counts=True) \
            if n else (np.zeros(0), np.zeros(0, np.int64))
        out["iterations"] = int(it)
        out["components"] = int(len(comps))
        out["largest"] = int(sizes.max()) if len(sizes) else 0
    else:
        with costs.kernel("analytics.triangles"):
            if device:
                tri = gated(lambda: mesh.run_triangles(esrc, edst, n))
            else:
                tri = lcc_host(esrc, edst, n)[0].sum() // 3
        out["triangles"] = int(tri)
    if metrics is not None and "iterations" in out:
        metrics.counter("dgraph_analytics_iterations_total").inc(
            out["iterations"])
    return out


# ---------------------------------------------------------------------------
# LDBC Graphalytics PR, WCC and LCC
# ---------------------------------------------------------------------------

def pull_layout(csr):
    """(PullGraph, why its DST-RANK space is not the vertex set or None,
    whether every edge is stored in both directions), cached on the tablet
    beside the PullGraph: once a snapshot. The dst ranks hold every vertex
    with an in-edge; they are the vertex set when every source is also a
    destination. Stored both ways, a WCC round needs only the pull."""
    got = getattr(csr, "_gx_layout", None)
    if got is None:
        from dgraph_tpu.ops import pallas_bfs as pb

        g = pb.pull_graph_for(csr)
        nd = len(g.host_in_subjects)
        reason = None
        if nd == 0:
            reason = "empty"
        elif len(g.host_map_s2d) and int(g.host_map_s2d.max()) >= nd:
            reason = "rank_spaces"       # a source that is no destination
        # an in-row holds its sources in rank order, a forward row its
        # targets in uid order: equal rows, equal graphs
        symmetric = reason is None and len(g.host_subjects) == nd \
            and np.array_equal(g.host_fwd_indptr, g.host_in_iptr) \
            and np.array_equal(g.host_subjects[g.host_in_src],
                               csr.host_arrays()[2])
        got = csr._gx_layout = (g, reason, bool(symmetric))
    return got


def lcc_layout(csr, g):
    """The degree-ordered rows LCC's program reads (ops/lcc.Layout), built
    from the PullGraph `g` of a tablet stored both ways — each in-row is
    then the vertex's neighbours in rank space — and cached on the tablet
    beside _gx_layout: once a snapshot, on its first `lcc` request, so
    that no other kind pays for it."""
    got = getattr(csr, "_lcc_layout", None)
    if got is None:
        from dgraph_tpu.ops import lcc

        got = csr._lcc_layout = lcc.build(g.host_in_iptr, g.host_in_src)
    return got


def gather_layout(csr, g):
    """(GatherLayout, window steps a pass) of the gather by source rank
    that every `pr` / `wcc` step makes (pb.gather_sorted over Nd + 1
    slots, slot Nd the pad edges'), or (None, 0) where the table is too
    large for VMEM and XLA's gather runs. Cached on the tablet beside
    _gx_layout: once a snapshot, on its first `pr` or `wcc` request, so
    that no other kind pays for it."""
    got = getattr(csr, "_gx_gather", None)
    if got is None:
        from dgraph_tpu.ops import pallas_bfs as pb

        nd = len(g.host_in_subjects)
        src = np.full(g.in_src_pad_d.shape[0], nd, dtype=np.int32)
        src[:g.num_edges] = g.host_map_s2d[g.host_in_src]
        got = csr._gx_gather = pb.gather_layout(src, nd + 1)
    return got


def _gx_reason(csr) -> str | None:
    """Why a tablet cannot take the device path before its layout is
    looked at: the residency gate of _device_eligible, without the mesh."""
    from dgraph_tpu.storage.delta import OverlayCSR

    if isinstance(csr, OverlayCSR):
        return "overlay"
    if getattr(csr, "_mesh_deferred", False):
        return "deferred"
    return None


def _reduce_path() -> str:
    """Where a device step's per-destination reduction runs: "pallas", the
    row_reduce kernel compiled for the chip, or "interpret", the same
    kernel in Pallas' interpreter off the chip (pb.interpret_mode)."""
    from dgraph_tpu.ops import pallas_bfs as pb

    return "interpret" if pb.interpret_mode() else "pallas"


def _gather_path(gather) -> str:
    """Where a device step's gather by source rank runs: "vmem", the
    gather_sorted kernel compiled for the chip, "interpret", the same
    kernel in Pallas' interpreter, or "xla", XLA's element gather (no
    layout: a table too large for VMEM)."""
    if gather is None:
        return "xla"
    return "vmem" if _reduce_path() == "pallas" else "interpret"


def _gx_device(kind: str, g, symmetric: bool, gate, probes: np.ndarray, *,
               damping: float, iterations: int, top: int, lay=None):
    """One launch of the kind's program inside a gate slot; its fetched
    host arrays. The probe ranks cross padded to PROBE_CLASS; `lay` is
    lcc_layout's for `lcc`, gather_layout's for `pr` / `wcc`."""
    import jax

    from dgraph_tpu.obs import costs, otrace
    from dgraph_tpu.ops import lcc
    from dgraph_tpu.ops import pallas_bfs as pb

    nd = len(g.host_in_subjects)
    pad = np.zeros(-(-max(len(probes), 1) // PROBE_CLASS) * PROBE_CLASS,
                   dtype=np.int32)
    pad[:len(probes)] = probes
    family = f"pb.analytics_{kind}"
    if kind == "lcc":
        attrs = {"oriented_edges": lay.oriented_edges,
                 "max_out": lay.max_out, "core": lay.core,
                 "core_edges": lay.core_edges}
    else:
        gather, windows = lay
        attrs = {"reduce": _reduce_path(), "gather": _gather_path(gather),
                 "windows": windows}
    if kind == "pr":
        attrs["iterations"] = iterations

    def launch():
        with otrace.span("device_kernel", kernel=family, nodes=nd,
                         edges=g.num_edges, **attrs) as sp, \
                costs.kernel(family, stage="dev.dispatch") as ck:
            if kind == "pr":
                out = pb.analytics_pr(
                    g.in_src_pad_d, g.in_iptr_rank, g.row_ends,
                    g.out_degree_d, pad, np.int32(iterations),
                    np.float32(damping), gather,
                    top=max(1, min(int(top), nd)))
            elif kind == "wcc":
                out = pb.analytics_wcc(g.in_src_pad_d, g.in_iptr_rank,
                                       g.row_ends, pad, gather,
                                       push=not symmetric)
            else:
                out = lcc.analytics_lcc(
                    lay.tables, lay.members, lay.tails, lay.heads,
                    lay.head_ids, lay.order, lay.degree, lay.adjacency,
                    pad, buckets=lay.buckets, core=lay.core,
                    spread=lay.spread)
            with costs.stage("dev.wait"):
                out = jax.device_get(out)
            ck.set(h2d=int(pad.nbytes),
                   d2h=int(sum(np.asarray(a).nbytes for a in out)))
            if kind == "wcc":
                sp.set(rounds=int(out[3]))
            elif kind == "lcc":
                sp.set(total=int(out[2]))
        return out

    return gate.run(launch, klass="analytics") if gate is not None \
        else launch()


def _pr_answer(nodes, want, at, ranks, top_v, top_i, total,
               top: int) -> dict:
    """`nodes` the vertex set's sorted uids, `at` each probe's rank in it
    (-1: no vertex), `ranks` the probes' own ranks in `want`'s order."""
    top = max(int(top), 0)
    return {"values": {hex(int(u)): (float(v) if i >= 0 else None)
                       for u, v, i in zip(want, ranks, at)},
            "top": [{"uid": hex(int(nodes[i])), "score": float(v)}
                    for v, i in zip(top_v[:top], top_i[:top])],
            "sum": float(total)}


def _wcc_answer(nodes, want, at, labels, components, largest) -> dict:
    """As _pr_answer; `labels` the probes' components as vertex ranks."""
    return {"labels": {hex(int(u)): (hex(int(nodes[lab])) if i >= 0
                                     else None)
                       for u, lab, i in zip(want, labels, at)},
            "components": int(components), "largest": int(largest)}


def _lcc_answer(want, at, tri, values, total, sums) -> dict:
    """As _pr_answer; `tri` and `values` the probes' t and lcc."""
    return {"values": {hex(int(u)): (float(v) if i >= 0 else None)
                       for u, v, i in zip(want, values, at)},
            "triangles": {hex(int(u)): (int(c) if i >= 0 else None)
                          for u, c, i in zip(want, tri, at)},
            "total": int(total), "sum": float(sums)}


def _run_gx(kind: str, csr, gate, metrics, *, damping: float,
            iterations: int, top: int, uids) -> dict:
    """Graphalytics' PR / WCC / LCC: on the device over the resident
    PullGraph (LCC over the rows lcc_layout builds from it), or by the
    host oracles over graph_arrays where the device path cannot answer
    over the whole vertex set, or for LCC over the whole undirected
    graph (the reason is counted)."""
    from dgraph_tpu.obs import costs
    from dgraph_tpu.ops.uidset import host_rank_of

    iterations = int(iterations)
    if kind == "pr" and iterations < 0:
        raise ValueError("analytics: iterations must be >= 0")
    want = np.asarray([int(u, 0) if isinstance(u, str) else int(u)
                       for u in uids], dtype=np.int64)
    reason, lay = _gx_reason(csr), None
    if reason is None:
        fresh = getattr(csr, "_gx_layout", None) is None or getattr(
            csr, "_lcc_layout" if kind == "lcc" else "_gx_gather",
            None) is None
        with costs.stage("exec.prep") if fresh else contextlib.nullcontext():
            g, reason, symmetric = pull_layout(csr)
            if reason is None and kind == "lcc":
                # an in-row is the whole neighbourhood only when every
                # edge is stored both ways
                if symmetric:
                    lay = lcc_layout(csr, g)
                else:
                    reason = "one_way"
            elif reason is None:
                lay = gather_layout(csr, g)
    if reason is None:
        nodes, edges = g.host_in_subjects, g.num_edges
        at = host_rank_of(nodes, want, -1)
        res = _gx_device(kind, g, symmetric, gate, np.maximum(at, 0),
                         damping=damping, iterations=iterations, top=top,
                         lay=lay)
        with costs.stage("dev.post"):
            head = res[0][:len(want)]
            if kind == "pr":
                steps = iterations
                out = _pr_answer(nodes, want, at, head, res[1], res[2],
                                 res[3], top)
            elif kind == "wcc":
                steps = int(res[3])
                out = _wcc_answer(nodes, want, at, head, res[1], res[2])
            else:
                steps = 1
                out = _lcc_answer(want, at, head, res[1][:len(want)],
                                  res[2], res[3])
    else:
        with costs.stage("exec"):
            nodes, esrc, edst = graph_arrays(csr)
            n, edges = len(nodes), len(esrc)
            at = host_rank_of(nodes, want, -1)
            if kind == "pr":
                # tol -1: no delta stops it, every step runs
                r, steps = pagerank_host(esrc, edst, n, damping=damping,
                                         tol=-1.0, max_iters=iterations)
                order = np.argsort(-r, kind="stable")
                out = _pr_answer(nodes, want, at, r[at] if n else at,
                                 r[order], order, r.sum(), top)
            elif kind == "wcc":
                lab = cc_host(esrc, edst, n)
                sizes = np.unique(lab, return_counts=True)[1]
                steps = 1                # union-find: one pass of the edges
                out = _wcc_answer(nodes, want, at, lab[at] if n else at,
                                  len(sizes), sizes.max(initial=0))
            else:
                tri, lcc = lcc_host(esrc, edst, n)
                steps = 1
                out = _lcc_answer(want, at, tri[at] if n else at,
                                  lcc[at] if n else at, tri.sum() // 3,
                                  lcc.sum())
    out = {"kind": kind, "nodes": int(len(nodes)), "edges": int(edges),
           "device": reason is None, **out}
    if kind != "lcc":
        out["iterations" if kind == "pr" else "rounds"] = int(steps)
    if metrics is not None:
        metrics.counter("dgraph_analytics_runs_total").inc()
        metrics.counter("dgraph_analytics_edges_total").inc(int(edges))
        metrics.counter("dgraph_analytics_iterations_total").inc(int(steps))
        if reason is None:
            metrics.keyed("dgraph_analytics_device_runs_total",
                          labels=("kind",)).inc(kind)
            if kind == "lcc":
                metrics.counter("dgraph_analytics_lcc_compares_total").inc(
                    lay.compares)
                metrics.counter("dgraph_analytics_lcc_merge_total").inc(
                    lay.merge)
                metrics.counter(
                    "dgraph_analytics_lcc_core_edges_total").inc(
                    lay.core_edges)
                metrics.counter(
                    "dgraph_analytics_lcc_oriented_edges_total").inc(
                    lay.oriented_edges)
            else:
                if _reduce_path() == "pallas":
                    metrics.keyed("dgraph_analytics_kernel_steps_total",
                                  labels=("kind",)).inc(kind, int(steps))
                metrics.keyed("dgraph_analytics_gather_steps_total",
                              labels=("kind", "path")).inc(
                    f"{kind}|{_gather_path(lay[0])}", int(steps))
        else:
            metrics.counter("dgraph_analytics_host_fallbacks_total").inc()
            metrics.keyed("dgraph_analytics_host_runs_total",
                          labels=("kind", "reason")).inc(f"{kind}|{reason}")
        metrics.keyed("dgraph_analytics_steps_total",
                      labels=("kind",)).inc(kind, int(steps))
        metrics.keyed("dgraph_analytics_edges_read_total",
                      labels=("kind",)).inc(kind, int(steps) * int(edges))
    return out
