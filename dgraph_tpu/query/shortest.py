"""Shortest path / k-shortest paths.

Reference semantics: query/shortest.go — ShortestPath (:437): single-source
Dijkstra over an adjacency map accreted by level-synchronous frontier
expansion (expandOut :134-261); edge cost from a facet else 1.0 (getCost
:102); KShortestPath (:274): k-paths variant carrying the full path per heap
item; capped by QueryEdgeLimit returning ErrTooBig (:214); result
materialized as a `_path_` block (:598).

TPU shape: a single-predicate unweighted `shortest` runs FULLY ON DEVICE —
on TPU the Pallas BFS kernel covers the whole device range
(ops/pallas_bfs.bfs_dist: the whole hop loop in one dispatch, one fetch of
the uint8 distance labels, host predecessor walk); ops/traversal.sssp edge
relaxation remains the device path for extreme depths (>= 254 hops) and
for non-TPU backends/tests.
MESH MODE (ISSUE 12): blocks over mesh-sharded tablets —
multi-predicate included — run the whole expandOut loop as ONE
`lax.while_loop` dispatch (mesh_exec.run_bfs) with frontier, visited set,
and distance vector device-resident between hops; single paths
reconstruct straight from the distance vector, k-shortest rebuilds the
level adjacency from it. Facet-weighted costs and child filters keep the
exact host path: the expansion there is still batched CSR expands per
level.
"""

from __future__ import annotations

import heapq

import numpy as np

from dgraph_tpu.obs import costs, otrace
from dgraph_tpu.query import dql
from dgraph_tpu.query.engine import QueryError, SubGraph
from dgraph_tpu.query.task import TaskQuery
from dgraph_tpu.utils.types import TypeID


def _resolve_end(ex, end) -> int:
    if isinstance(end, dql.VarRef):
        vv = ex.vars.get(end.name)
        if vv is None or vv.uids is None or len(vv.uids) == 0:
            raise QueryError(f"shortest endpoint var {end.name} is empty")
        return int(vv.uids[0])
    return int(end)


def _build_adjacency(ex, sg: SubGraph, src: int, dst: int):
    """Level-synchronous expansion accreting adjacency[from] = [(to, cost, attr)]."""
    spec = sg.gq.shortest
    adj: dict[int, list[tuple[int, float, str]]] = {}
    frontier = np.asarray([src], dtype=np.int64)
    seen: set[int] = {src}
    edges = 0
    max_depth = spec.depth if spec.depth > 0 else 64
    for _level in range(max_depth):
        if len(frontier) == 0:
            break
        next_f: set[int] = set()
        for cgq in sg.gq.children:
            facet_key = None
            if cgq.facets is not None and cgq.facets.keys:
                facet_key = cgq.facets.keys[0][1]
            tq = TaskQuery(cgq.attr, frontier=np.sort(frontier),
                           facet_keys=[facet_key] if facet_key else [])
            res = ex._dispatch(tq)
            edges += res.traversed_edges
            if edges > ex.edge_budget():
                raise QueryError("shortest path exceeded edge budget (ErrTooBig)")
            dests = res.dest_uids
            if cgq.filter is not None:
                allowed = set(int(x) for x in ex._apply_filter(cgq.filter, dests))
            else:
                allowed = None
            for u, targets, facets in zip(
                    np.sort(frontier), res.uid_matrix,
                    res.facet_matrix or [[]] * len(res.uid_matrix)):
                for j, t in enumerate(targets):
                    t = int(t)
                    if allowed is not None and t not in allowed:
                        continue
                    cost = 1.0
                    if facet_key and facets and j < len(facets):
                        fv = dict(facets[j]).get(facet_key)
                        if fv is not None and isinstance(fv.value, (int, float)):
                            cost = float(fv.value)
                    adj.setdefault(int(u), []).append((t, cost, cgq.attr))
                    if t not in seen:
                        seen.add(t)
                        next_f.add(t)
        frontier = np.asarray(sorted(next_f), dtype=np.int64)
    return adj


# below this edge count the host adjacency walk + Dijkstra beats the
# device relaxation's fixed dispatch/sync cost (size-adaptive, same
# rationale as task.HOST_EXPAND_MAX)
DEVICE_SSSP_MIN_EDGES = 1 << 17

# above this edge count the Pallas BFS kernel (ops/pallas_bfs.bfs_dist:
# whole hop loop in one dispatch, one uint8 label fetch) replaces the
# Bellman-Ford E-gather of traversal.sssp. Tests set the module global to
# 0 to force it (interpret mode off-TPU).
SSSP_KERNEL_MIN: int | None = None


_SSSP_KERNEL_MIN_TPU = 1 << 17   # == the device tier's default floor —
# the kernel's distance fetch (one byte a destination: Nd bytes) moves 8x
# fewer bytes to the host than Bellman-Ford's dist+parent fetch (8 B/node)
# at every size the device path serves. A SEPARATE constant: tests
# monkeypatch DEVICE_SSSP_MIN_EDGES to force the sssp tier on tiny graphs,
# and the kernel floor must not follow it down.


def _sssp_kernel_min() -> int:
    if SSSP_KERNEL_MIN is not None:
        return SSSP_KERNEL_MIN
    import jax

    return _SSSP_KERNEL_MIN_TPU if jax.default_backend() == "tpu" \
        else (1 << 62)


def _device_csr(ex, sg: SubGraph):
    """The single predicate CSR eligible for the device sssp path, or None.

    Eligible: one uid child, no facet cost key, no child filter, no lang,
    numpaths <= 1, predicate CSR resident on THIS device (tablet-routed
    DistPredCSR falls back to the per-level wire expansion) and large
    enough that device relaxation amortizes its dispatch cost."""
    spec = sg.gq.shortest
    if spec.numpaths > 1 or len(sg.gq.children) != 1:
        return None
    cgq = sg.gq.children[0]
    if cgq.filter is not None or cgq.lang:
        return None
    if cgq.facets is not None and cgq.facets.keys:
        return None
    rev = cgq.attr.startswith("~")
    pd = ex.snap.pred(cgq.attr[1:] if rev else cgq.attr)
    if pd is None:
        return None
    csr = pd.rev_csr if rev else pd.csr
    if csr is None or getattr(csr, "is_dist", False):
        return None
    if csr.num_edges < DEVICE_SSSP_MIN_EDGES:
        return None
    return cgq.attr, csr


def _device_shortest(attr: str, csr, src: int, dst: int, max_depth: int,
                     metrics=None):
    """Unweighted single-source shortest path on device, parent chain
    walked on host, under a device_kernel span and a cost timer (both
    calls below fetch their result, so the timer sees the device step).
    On TPU the Pallas BFS kernel serves the whole device range (bfs_dist —
    one dispatch for the whole hop loop, one uint8 label fetch); the
    Bellman-Ford relaxation (ops/traversal.sssp) serves extreme depths
    (>= 254) and non-TPU backends. Work is bounded by iterations x E (the
    resident CSR), so the reference's discovered-edge budget does not
    apply here. This path does not take a dispatch-gate slot."""
    from dgraph_tpu.ops import traversal

    from dgraph_tpu.ops.pallas_bfs import DIST_UNREACHED

    # depth > the kernel's distance-label range keeps the sssp tier (its
    # max_iters honors any depth); 254+ hop shortest paths are vanishingly
    # rare but must not silently go "unreachable"
    if csr.num_edges >= _sssp_kernel_min() and max_depth < DIST_UNREACHED:
        from dgraph_tpu.ops import pallas_bfs as pb

        with costs.stage("exec.prep"):
            g = pb.pull_graph_for(csr)  # host prep: outside the timer
        # level 1 of the search, by the root's out-degree: its own row
        # ("push") or the whole in-edge stream ("stream")
        first_hop = pb.first_hop_mode(g, src)
        if metrics is not None:
            metrics.keyed("dgraph_bfs_first_hop_total",
                          labels=("mode",)).inc(first_hop)
        # shortest_bfs splits the window: dev.dispatch up to the jitted
        # call's return, dev.wait in the fetch, dev.post after it
        with otrace.span("device_kernel", kernel="pb.bfs_dist",
                         edges=g.num_edges, first_hop=first_hop), \
                costs.kernel("pb.bfs_dist", attr=attr,
                             stage="dev.dispatch"):
            path = pb.shortest_bfs(g, src, dst, max_depth)
        if path is None:
            return None
        return (float(len(path) - 1), path, [attr] * (len(path) - 1))

    subjects, indptr, indices = csr.host_arrays()
    hi = max(int(subjects[-1]) if len(subjects) else 0,
             int(indices.max()) if len(indices) else 0)
    if src > hi or dst > hi:
        return None              # endpoint outside this predicate's uid space
    # pow2 capacity class: snapshot-to-snapshot uid growth must not retrace
    num_nodes = 1 << max(int(np.ceil(np.log2(hi + 2))), 4)
    with otrace.span("device_kernel", kernel="traversal.sssp",
                     edges=csr.num_edges), \
            costs.kernel("traversal.sssp", attr=attr,
                         stage="dev.dispatch"):
        res = traversal.sssp(csr.subjects, csr.indptr, csr.indices, None,
                             src, num_nodes=num_nodes, max_iters=max_depth)
        dist_d = res.dist[dst]
        with costs.stage("dev.wait"):
            dist = float(np.asarray(dist_d))
    if not np.isfinite(dist):
        return None
    # the parent fetch and the chain walk run after the window closed (the
    # ledger's device_ms never held them); the clock books them as dev.post
    with costs.stage("dev.post"):
        parent = np.asarray(res.parent)
        path = [dst]
        while path[-1] != src:
            p = int(parent[path[-1]])
            if p < 0 or len(path) > max_depth + 1:
                return None  # broken chain (cannot happen for finite dist)
            path.append(p)
    return (dist, path[::-1], [attr] * (len(path) - 1))


def _mesh_csrs(ex, sg: SubGraph):
    """[(attr, mesh-sharded CSR)] when the block's whole expansion can run
    as ONE fused BFS dispatch: every uid child (multi-predicate blocks
    included — the level union is synchronous) free of filters, lang, and
    facet cost keys, over tablets this mesh placed. Serves both single
    and k-shortest (the rebuilt adjacency feeds either). Declines record
    the labeled fallback reason when a mesh-owned tablet was involved."""
    mesh = getattr(ex, "mesh", None)
    if mesh is None or not sg.gq.children:
        return None
    from dgraph_tpu.query import fusedplan as fp

    csrs = []
    owned_any = False
    reason = None
    for cgq in sg.gq.children:
        rev = cgq.attr.startswith("~")
        pd = ex.snap.pred(cgq.attr[1:] if rev else cgq.attr)
        csr = (pd.rev_csr if rev else pd.csr) if pd is not None else None
        if csr is not None and mesh.owns(csr):
            owned_any = True
        elif csr is not None:
            reason = reason or ex._mesh_break_reason(cgq) or fp.REASON_SHAPE
        if cgq.filter is not None:
            reason = reason or fp.REASON_FILTER
        elif cgq.lang:
            reason = reason or fp.REASON_LANG
        elif cgq.facets is not None and cgq.facets.keys:
            reason = reason or fp.REASON_FACET
        csrs.append((cgq.attr, csr))
    if reason is None and owned_any and \
            all(c is not None and mesh.owns(c) for _a, c in csrs):
        return csrs
    if owned_any and reason is not None:
        ex._mesh_miss(reason)
    return None


def _mesh_shortest_single(ex, sg: SubGraph, csrs, src: int, dst: int):
    """Single shortest path from ONE fused BFS dispatch, reconstructed
    straight from the distance vector — no adjacency dict, no host
    Dijkstra. With unit edge costs (the mesh path rejects facet costs)
    Dijkstra's prev[x] is exactly the MINIMUM-uid predecessor at
    dist[x]-1 (all dist-(d-1) nodes pop before any dist-d node, in uid
    order), and its recorded attr is the FIRST child predicate holding
    that edge — both derivable from dist + the host CSR mirrors. The
    program early-exits once the destination's level completes
    (reference stopExpansion, query/shortest.go): levels beyond
    dist[dst] cannot shorten the path."""
    spec = sg.gq.shortest
    max_depth = spec.depth if spec.depth > 0 else 64
    mesh = ex.mesh
    only = [c for _a, c in csrs]
    with costs.kernel("mesh.bfs"):
        dist, hops, edges = ex.gated(
            lambda: mesh.run_bfs(only, src, max_depth, ex.edge_budget(),
                                 stop_at=dst),
            klass="shortest")
    if edges > ex.edge_budget():
        raise QueryError("shortest path exceeded edge budget (ErrTooBig)")
    ex._mesh_fused += 1
    tgt = mesh.bfs_targets(only)
    pos = int(np.searchsorted(tgt, dst)) if len(tgt) else 0
    if not len(tgt) or pos >= len(tgt) or tgt[pos] != dst or \
            dist[pos] >= int(mesh.BFS_UNREACHED):
        return None
    d = int(dist[pos])
    host = [(attr, csr.host_arrays()) for attr, csr in csrs]

    def _edge_exists(arrs, u: int, t: int) -> bool:
        subjects, indptr, indices = arrs
        r = int(np.searchsorted(subjects, u))
        if r >= len(subjects) or subjects[r] != u:
            return False
        row = indices[indptr[r]: indptr[r + 1]]
        j = int(np.searchsorted(row, t))
        return j < len(row) and row[j] == t

    path = [dst]
    attrs: list[str] = []
    cur = dst
    for level in range(d - 1, -1, -1):
        cands = tgt[dist == level].astype(np.int64)
        if level == 0:
            cands = np.unique(np.concatenate(
                [cands, np.asarray([src], dtype=np.int64)]))
        best = None
        for _attr, arrs in host:
            subjects, indptr, indices = arrs
            rows = np.searchsorted(subjects, cands)
            rc = np.clip(rows, 0, max(len(subjects) - 1, 0))
            ok = (len(subjects) > 0) & (subjects[rc] == cands)
            starts = np.where(ok, indptr[rc], 0).astype(np.int64)
            deg = np.where(ok, indptr[rc + 1] - starts, 0).astype(np.int64)
            total = int(deg.sum())
            if not total:
                continue
            offs = np.zeros(len(cands) + 1, dtype=np.int64)
            np.cumsum(deg, out=offs[1:])
            flat = np.repeat(starts - offs[:-1], deg) + np.arange(total)
            hit = indices[flat] == cur
            if hit.any():
                seg = np.searchsorted(offs[1:], np.flatnonzero(hit),
                                      side="right")
                u = int(cands[seg].min())
                best = u if best is None else min(best, u)
        if best is None:
            return None       # cannot happen for a finite dist
        # attr = the FIRST child predicate holding the chosen edge (the
        # first (t, cost, attr) tuple Dijkstra relaxed from adj[u])
        attr_used = next(a for a, arrs in host
                         if _edge_exists(arrs, best, cur))
        path.append(best)
        attrs.append(attr_used)
        cur = best
    return (float(d), path[::-1], attrs[::-1])


def _mesh_bfs_adjacency(ex, sg: SubGraph, csrs, src: int):
    """expandOut's whole level loop (query/shortest.go:134) as ONE
    `lax.while_loop` dispatch (mesh_exec.run_bfs): frontier, visited set,
    and distance vector stay device-resident between hops — the 12
    stepped dispatches (12 gRPC rounds per group on the wire path) become
    one launch. The host rebuilds the level adjacency from the distance
    vector and its CSR mirrors: a node expanded at level L holds its full
    per-predicate rows in child order, exactly what _build_adjacency
    accretes (cost 1.0, all targets recorded), so Dijkstra / k-shortest
    see byte-identical inputs."""
    spec = sg.gq.shortest
    max_depth = spec.depth if spec.depth > 0 else 64
    mesh = ex.mesh
    only = [c for _a, c in csrs]
    with costs.kernel("mesh.bfs"):
        dist, hops, edges = ex.gated(
            lambda: mesh.run_bfs(only, src, max_depth, ex.edge_budget()),
            klass="shortest")
    if edges > ex.edge_budget():
        raise QueryError("shortest path exceeded edge budget (ErrTooBig)")
    ex._mesh_fused += 1
    tgt = mesh.bfs_targets(only)
    # nodes EXPANDED by the loop: in the frontier of an executed level —
    # dist L < hops (the last level's fresh targets joined no frontier)
    reached = tgt[dist < hops].astype(np.int64) if hops else \
        np.zeros(0, np.int64)
    uids = np.unique(np.concatenate(
        [np.asarray([src], dtype=np.int64), reached]))
    adj: dict[int, list[tuple[int, float, str]]] = {}
    for attr, csr in csrs:
        subjects, indptr, indices = csr.host_arrays()
        rows = np.searchsorted(subjects, uids)
        rc = np.clip(rows, 0, max(len(subjects) - 1, 0))
        ok = (len(subjects) > 0) & (subjects[rc] == uids)
        for i in np.flatnonzero(ok):
            u = int(uids[i])
            r = int(rc[i])
            row = indices[indptr[r]: indptr[r + 1]]
            if len(row):
                adj.setdefault(u, []).extend(
                    (int(t), 1.0, attr) for t in row)
    return adj


def shortest_path(ex, sg: SubGraph) -> None:
    spec = sg.gq.shortest
    src = _resolve_end(ex, spec.from_)
    dst = _resolve_end(ex, spec.to)
    max_depth = spec.depth if spec.depth > 0 else 64
    sg.paths = []
    if src == dst:
        sg.paths = [(0.0, [src], [])]
    else:
        dev = _device_csr(ex, sg)
        mesh = _mesh_csrs(ex, sg) if dev is None else None
        if dev is not None:
            p = _device_shortest(dev[0], dev[1], src, dst, max_depth,
                                 getattr(ex.snap, "metrics", None))
            sg.paths = [p] if p is not None else []
        elif mesh is not None and spec.numpaths <= 1:
            p = _mesh_shortest_single(ex, sg, mesh, src, dst)
            sg.paths = [p] if p is not None else []
        else:
            if mesh is not None:
                adj = _mesh_bfs_adjacency(ex, sg, mesh, src)
            else:
                adj = _build_adjacency(ex, sg, src, dst)
            if spec.numpaths <= 1:
                p = _dijkstra(adj, src, dst)
                sg.paths = [p] if p is not None else []
            else:
                sg.paths = _k_shortest(adj, src, dst, spec.numpaths,
                                        ex.edge_budget())
        sg.paths = [p for p in sg.paths
                    if spec.minweight <= p[0] <= spec.maxweight]
    uids = sorted({u for _c, path, _a in sg.paths for u in path})
    sg.dest_uids = np.asarray(uids, dtype=np.int64)
    if sg.gq.var_name:
        from dgraph_tpu.query.engine import VarValue

        ex.vars[sg.gq.var_name] = VarValue(uids=sg.dest_uids)


def _dijkstra(adj, src: int, dst: int):
    dist = {src: 0.0}
    prev: dict[int, tuple[int, str]] = {}
    pq = [(0.0, src)]
    while pq:
        d, u = heapq.heappop(pq)
        if u == dst:
            break
        if d > dist.get(u, float("inf")):
            continue
        for (t, c, attr) in adj.get(u, ()):
            nd = d + c
            if nd < dist.get(t, float("inf")):
                dist[t] = nd
                prev[t] = (u, attr)
                heapq.heappush(pq, (nd, t))
    if dst not in dist:
        return None
    path = [dst]
    attrs: list[str] = []
    while path[-1] != src:
        p, attr = prev[path[-1]]
        attrs.append(attr)
        path.append(p)
    return (dist[dst], path[::-1], attrs[::-1])


def _k_shortest(adj, src: int, dst: int, k: int, budget: int):
    """Loopless k-shortest via best-first path enumeration (the reference
    carries whole paths per heap item too, query/shortest.go:274). The pop
    budget is the query edge limit (x/init.go:53 QueryEdgeLimit) — each pop
    relaxes at most one path-edge extension."""
    out = []
    pq = [(0.0, [src], [])]
    pops = 0
    while pq and len(out) < k and pops < budget:
        d, path, attrs = heapq.heappop(pq)
        pops += 1
        u = path[-1]
        if u == dst:
            out.append((d, path, attrs))
            continue
        for (t, c, attr) in adj.get(u, ()):
            if t in path:
                continue
            heapq.heappush(pq, (d + c, path + [t], attrs + [attr]))
    return out


def encode_paths(ex, sg: SubGraph, out: dict) -> None:
    """Materialize `_path_` (reference query/shortest.go:598)."""
    paths = getattr(sg, "paths", [])
    objs = []
    for cost, path, attrs in paths:
        node: dict = {"uid": hex(path[-1])}
        for i in range(len(path) - 2, -1, -1):
            node = {"uid": hex(path[i]), attrs[i]: [node]}
        node["_weight_"] = cost
        objs.append(node)
    if objs:
        out["_path_"] = objs
