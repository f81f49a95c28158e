"""@recurse: iterative frontier expansion to fixed depth or exhaustion.

Reference semantics: query/recurse.go — expandRecurse (:31-177): loop per
level, spawning copies of the original children as the new frontier's
SubGraphs (:157-164); loop prevention via a reach-set of (attr, from, to)
edges (:129-141) unless `loop: true`; bounded by the edge budget (:167).

TPU shape — one hot path, benched and served alike (worker/task.go:605):

  * Large resident CSRs run the SAME Pallas row-end kernels the
    benchmark measures (ops/pallas_bfs): per level, the kernel streams the
    dst-sorted edge array against the VMEM frontier bitmap and hands back
    one prefix value a destination; the next frontier is a node-sized
    diff. Edge dedup is kept on vertices: an edge was traversed iff its
    source was in an earlier frontier, so the reach-set of recurse.go:129
    is a device-resident bool vector over the vertices (`expanded`) and a
    level reaches from `frontier & ~expanded`.
    The common single-child no-filter shape runs ALL levels in one
    dispatch (recurse_fused) — no host sync between levels — from seeds
    handed over as ranks, its first level reading the seeds' own rows.
    Per-source target lists (uidMatrix) stay CSR-shaped and deferred
    (LazyRecurseMatrix): output encoders materialize on demand, a
    frontier vertex's whole row if it was in no earlier frontier, else
    nothing — told from the level frontiers the host already holds.
  * Small CSRs keep the vectorized host-mirror gather (the size-adaptive
    dispatch rule of task.HOST_EXPAND_MAX: below the device's fixed
    dispatch+sync cost, host numpy wins).
  * Tablet-routed (is_dist) predicates expand over the wire with
    (attr, from, to) edge-key dedup, exactly recurse.go:129-141.

Variables. The block's own uid variable holds its roots, as any block's.
A uid variable on a CHILD (`{ var(func: uid(r)) @recurse(depth: k)
{ v as follows } }`) holds the sorted union of that child's destinations
over every level (query/query.go populateUidValVar merges a recurse
child's levels). With `loop: false` the traversal dedups edges, so a
level's destinations are those of its fresh edges: on an undirected graph
stored in both directions `v` is every vertex within k hops of the root,
plus the root itself from k = 2 on (it comes back over the reverse edge).
All three tiers record through `_record_vars`; the plain reference is
dgraph_tpu/models/khop.py. A `var` block renders nothing, so the fused
tier builds no SubGraph chain for it: no LazyRecurseMatrix, one OR over
the fetched level masks.
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from dgraph_tpu.obs import costs, otrace
from dgraph_tpu.query import dql
from dgraph_tpu.query.engine import QueryError, SubGraph, VarValue
from dgraph_tpu.query.task import TaskQuery, process_task
from dgraph_tpu.utils.types import TypeID

# kernel-path admission: below this edge count the host mirror's vectorized
# gather beats the kernel's fixed dispatch + per-chunk VPU cost. Tests set
# the module global to 0 to force the kernel (interpret mode off-TPU).
KERNEL_MIN_EDGES: int | None = None       # None = backend-dependent default
_KERNEL_MIN_TPU = 1 << 20
FUSED_MAX_DEPTH = 8   # one compiled program per static depth


def _kernel_min() -> int:
    if KERNEL_MIN_EDGES is not None:
        return KERNEL_MIN_EDGES
    if jax.default_backend() == "tpu":
        return _KERNEL_MIN_TPU
    return 1 << 62    # interpret-mode Pallas: host path always wins


class LazyRecurseMatrix:
    """A recurse level's uidMatrix in deferred CSR form.

    The kernel path's native result is the next frontier mask; ragged
    per-source target lists are materialized host-side only when an output
    encoder, cascade, or count actually reads them (SURVEY §7: result
    materialization is inherently ragged → host-side by design). Edge
    dedup on the host is the device's, on vertices: a frontier vertex's
    out-edges are all fresh if it was in no earlier frontier of the
    traversal (`first`, a bool per frontier vertex; None = every one, the
    `loop: true` rule), else all seen."""

    def __init__(self, csr, frontier: np.ndarray,
                 first: np.ndarray | None, metrics=None):
        self._csr = csr
        self._frontier = np.asarray(frontier, dtype=np.int64)
        self._first = first
        self._metrics = metrics
        self._rows: list[np.ndarray] | None = None

    def _materialize(self) -> list[np.ndarray]:
        if self._rows is not None:
            return self._rows
        if self._metrics is not None:
            self._metrics.counter("dgraph_recurse_materialized_total").inc()
        if self._first is None:
            at = np.arange(len(self._frontier))
        else:
            at = np.flatnonzero(self._first)
        _pos, offs, targets = _gather_frontier_edges(
            self._csr, self._frontier[at])
        rows = [targets[:0]] * len(self._frontier)
        for j, i in enumerate(at):
            rows[i] = targets[offs[j]: offs[j + 1]]
        self._rows = rows
        return rows

    def __len__(self) -> int:
        return len(self._frontier)

    def __bool__(self) -> bool:
        return len(self._frontier) > 0

    def __getitem__(self, i):
        return self._materialize()[i]

    def __iter__(self):
        return iter(self._materialize())


class LazyCounts:
    """list-like per-source counts over a LazyRecurseMatrix."""

    def __init__(self, m: LazyRecurseMatrix):
        self._m = m

    def __len__(self) -> int:
        return len(self._m)

    def __bool__(self) -> bool:
        return len(self._m) > 0

    def __getitem__(self, i) -> int:
        return len(self._m._materialize()[i])

    def __iter__(self):
        return (len(r) for r in self._m._materialize())


def _gather_frontier_edges(csr, frontier: np.ndarray):
    """The frontier's CSR edge positions, gathered in one vectorized shot:
    (pos int64[total], offs int64[F+1], targets int64[total])."""
    from dgraph_tpu.ops import uidset as us

    subjects, indptr, indices = csr.host_arrays()
    rows = us.host_rank_of(subjects, frontier, -1)
    ok = rows >= 0
    rc = np.where(ok, rows, 0)
    starts = np.where(ok, indptr[rc], 0).astype(np.int64)
    ends = np.where(ok, indptr[rc + 1], 0).astype(np.int64)
    counts = ends - starts
    total = int(counts.sum())
    offs = np.zeros(len(frontier) + 1, dtype=np.int64)
    np.cumsum(counts, out=offs[1:])
    pos = np.repeat(starts - offs[:-1], counts) + np.arange(total)
    return pos, offs, indices[pos].astype(np.int64)


def _first_visits(expanded: np.ndarray, frontier: np.ndarray) -> np.ndarray:
    """A bool per frontier vertex: it was in no earlier frontier of the
    traversal. `expanded` (bool[num_nodes]: the vertices that were) is
    updated in place; a uid past it has no row to dedup."""
    inside = frontier < len(expanded)
    first = np.ones(len(frontier), dtype=bool)
    first[inside] = ~expanded[frontier[inside]]
    expanded[frontier[inside]] = True
    return first


def _expand_dedup(csr, frontier: np.ndarray, seen: np.ndarray,
                  allow_loop: bool) -> tuple[list[np.ndarray], int]:
    """One level of expansion with first-traversal edge dedup, vectorized:
    previously seen positions masked out, seen mask updated in place."""
    pos, offs, targets = _gather_frontier_edges(csr, frontier)
    total = len(pos)
    if allow_loop:
        fresh = np.ones(total, dtype=bool)
    else:
        fresh = ~seen[pos]
        seen[pos] = True
    matrix = [targets[offs[i]: offs[i + 1]][fresh[offs[i]: offs[i + 1]]]
              for i in range(len(frontier))]
    return matrix, total


def _set_list_result(child: SubGraph, matrix: list[np.ndarray]) -> None:
    """Shared tail of the list-producing branches: uidMatrix + per-source
    counts + merged dest set."""
    child.uid_matrix = matrix
    child.counts = [len(m) for m in matrix]
    child.dest_uids = (np.unique(np.concatenate(matrix))
                       if any(len(m) for m in matrix)
                       else np.zeros(0, np.int64))


def _seeds_mask(uids: np.ndarray, num_nodes: int) -> jnp.ndarray:
    sel = uids[uids < num_nodes].astype(np.int64)
    m = jnp.zeros((num_nodes,), dtype=bool)
    if len(sel):
        m = m.at[jnp.asarray(sel)].set(True)
    return m


def _record_vars(ex, sg: SubGraph, level_dests: dict) -> None:
    """The one place a recurse block's variables are recorded, whichever
    tier ran it: the block's own (its roots), and for each uid child that
    names one the sorted union of its destinations over all levels
    (`level_dests`: id(child gq) -> the levels' destination arrays)."""
    gq = sg.gq
    ex._record_uid_var(gq, sg)
    for cgq in gq.children:
        parts = level_dests.get(id(cgq))
        if parts is None:
            continue
        # a level's destinations are sorted and distinct already: only
        # several levels need the merge
        ex.vars[cgq.var_name] = VarValue(
            uids=parts[0] if len(parts) == 1
            else np.unique(np.concatenate(parts)) if parts
            else np.zeros(0, np.int64))


def _book_edges(attr: str, n: int) -> None:
    """Edges a level traversed here (host mirror, stepped or fused kernel,
    the mesh tier's replay), on the request's cost ledger, as the
    dispatched tasks book theirs (engine run_ledgered: the wire tier's
    levels go that way). Without it a host-mirror recurse closes a
    ledger that says nothing ran, and /debug/top drops the request."""
    lg = costs.current()
    if lg is not None:
        lg.add_task(attr[1:] if attr.startswith("~") else attr, n)


def _count_levels(ex, live: int, empty: int = 0) -> None:
    """dgraph_recurse_levels_total: levels a device recurse program ran,
    by whether their frontier held a vertex. The fused scan runs all
    `depth` of them; a stepped level is live by construction."""
    metrics = getattr(ex.snap, "metrics", None)
    if metrics is not None:
        metrics.keyed("dgraph_recurse_levels_total",
                      labels=("state",)).inc_many(
            {"live": live, "empty": empty})


def recurse(ex, sg: SubGraph) -> None:
    gq = sg.gq
    spec = gq.recurse
    depth = spec.depth if spec.depth > 0 else 64  # "until exhaustion" cap
    uid_children = [c for c in gq.children
                    if ex.schema.type_of(c.attr) == TypeID.UID
                    or (ex.snap.pred(c.attr) is not None
                        and ex.snap.pred(c.attr).csr is not None)
                    or c.attr.startswith("~")]
    val_children = [c for c in gq.children if c not in uid_children]
    seen_masks: dict[str, np.ndarray] = {}     # host path: attr -> bool[E]
    kstates: dict[str, dict] = {}     # kernel path: attr -> g, expanded
    seen_edges: set[tuple[str, int, int]] = set()   # dist-CSR fallback only
    edges = 0
    # id(child gq) -> each level's destinations, for the children that
    # name a uid variable (_record_vars)
    level_dests: dict[int, list[np.ndarray]] = {
        id(c): [] for c in uid_children if c.var_name and not c.is_count}

    def _csr_for(cgq):
        attr = cgq.attr
        rev = attr.startswith("~")
        pd = ex.snap.pred(attr[1:] if rev else attr)
        if pd is None:
            return None
        return pd.rev_csr if rev else pd.csr

    def _use_kernel(csr) -> bool:
        return (csr is not None and not getattr(csr, "is_dist", False)
                and csr.num_edges >= _kernel_min())

    def _kstate(attr: str, csr):
        from dgraph_tpu.ops import pallas_bfs as pb

        st = kstates.get(attr)
        if st is None:
            g = pb.pull_graph_for(csr)
            # the vertices that were in an earlier frontier of this
            # predicate's traversal: on the device by src rank (the
            # program's), on the host by uid (the lazy matrices')
            st = kstates[attr] = {
                "g": g,
                "expanded": jnp.zeros((len(g.host_subjects),), dtype=bool),
                "expanded_h": np.zeros(g.num_nodes, dtype=bool)}
        return st

    # ---- fused fast path: single uid child, no filters/val children -------
    if (len(uid_children) == 1 and not val_children
            and uid_children[0].filter is None
            and depth <= FUSED_MAX_DEPTH and len(sg.dest_uids)):
        cgq = uid_children[0]
        csr = _csr_for(cgq)
        if _use_kernel(csr):
            _recurse_fused_path(ex, sg, cgq, csr, depth, spec.allow_loop,
                                level_dests)
            _record_vars(ex, sg, level_dests)
            return
    # ---- mesh fused path: single uid child, filters compile to allow-set
    # formulas, value children layer host-side per level (ISSUE 12) ---------
    mesh = getattr(ex, "mesh", None)
    if mesh is not None and len(sg.dest_uids) and \
            any(mesh.owns(_csr_for(c)) for c in uid_children):
        from dgraph_tpu.query import fusedplan as fp

        cgq = uid_children[0] if len(uid_children) == 1 else None
        if cgq is None:
            # multi-predicate recurse dedups edges in DEPTH-FIRST sibling
            # order (build_level recursion) — inherently sequential, the
            # one traversal shape the level-synchronous program can't hold
            ex._mesh_miss(fp.REASON_MULTI_PRED)
        elif depth > FUSED_MAX_DEPTH:
            ex._mesh_miss(fp.REASON_DEPTH)
        elif mesh.owns(_csr_for(cgq)):
            csr = _csr_for(cgq)
            formula = None
            sets: list | None = None
            ok = True
            if cgq.filter is not None:
                try:
                    formula, leaves = fp.compile_filter(
                        cgq.filter, ex.schema,
                        fp._block_child_defines(gq))
                    sets = [fp.resolve_leaf(ex, s) for s in leaves]
                except fp.Unfusable as e:
                    ex._mesh_miss(e.reason)
                    ok = False
                except Exception:
                    ex._mesh_miss(fp.REASON_FILTER)
                    ok = False
            if ok:
                _mesh_recurse_path(ex, sg, cgq, csr, depth,
                                   spec.allow_loop, mesh, formula, sets,
                                   val_children, level_dests)
                _record_vars(ex, sg, level_dests)
                return

    def build_level(frontier: np.ndarray, remaining: int) -> list[SubGraph]:
        nonlocal edges
        out: list[SubGraph] = []
        frontier = np.sort(frontier)
        # value/scalar children appear at every level
        for cgq in val_children:
            child = SubGraph(gq=cgq, attr=cgq.attr, src_uids=frontier)
            res = ex._dispatch(TaskQuery(cgq.attr, frontier=frontier,
                                                  lang=cgq.lang))
            child.value_matrix = res.value_matrix
            child.uid_matrix = res.uid_matrix
            child.counts = res.counts
            child.dest_uids = res.dest_uids
            out.append(child)
        if remaining <= 0:
            return out
        for cgq in uid_children:
            child = SubGraph(gq=cgq, attr=cgq.attr, src_uids=frontier)
            csr = _csr_for(cgq)
            if _use_kernel(csr) and len(frontier):
                # PRODUCTION KERNEL PATH: one stepped Pallas level
                from dgraph_tpu.ops import pallas_bfs as pb

                st = _kstate(cgq.attr, csr)
                g = st["g"]
                fmask = _seeds_mask(frontier, g.num_nodes)
                # the device step runs through the dispatch gate: N
                # concurrent recurse queries pipeline instead of thrashing
                def _step():
                    dest_words, trav, expanded = pb.recurse_step(
                        g.in_src_pad, g.in_iptr_rank, g.row_ends,
                        g.subjects, g.in_subjects, g.fwd_indptr, fmask,
                        st["expanded"], chunks=g.chunks,
                        num_nodes=g.num_nodes, allow_loop=spec.allow_loop)
                    # the fetch is the fence, as in _solo_fused: dispatch
                    # is asynchronous, so the timer and the gate slot
                    # cover the device step and not only its launch
                    return jax.device_get((dest_words, trav)), expanded

                with costs.kernel("pb.recurse_step", attr=cgq.attr):
                    (dest_words_h, trav_h), st["expanded"] = ex.gated(
                        _step, klass="recurse")
                _count_levels(ex, live=1)
                _book_edges(cgq.attr, int(trav_h))
                edges += int(trav_h)
                if edges > ex.edge_budget():
                    raise QueryError(
                        "recurse exceeded edge budget (ErrTooBig)")
                m = LazyRecurseMatrix(
                    csr, frontier,
                    None if spec.allow_loop
                    else _first_visits(st["expanded_h"], frontier),
                    getattr(ex.snap, "metrics", None))
                child.uid_matrix = m
                child.counts = LazyCounts(m)
                child.dest_uids = np.flatnonzero(pb.unpack_words(
                    dest_words_h, g.num_nodes)).astype(np.int64)
            elif csr is not None and not getattr(csr, "is_dist", False):
                # small CSR: vectorized host-mirror gather (size-adaptive)
                if cgq.attr not in seen_masks and len(frontier):
                    seen_masks[cgq.attr] = np.zeros(csr.num_edges, dtype=bool)
                matrix, total = (_expand_dedup(
                    csr, frontier, seen_masks.get(cgq.attr),
                    spec.allow_loop) if len(frontier)
                    else ([], 0))
                _book_edges(cgq.attr, total)
                edges += total
                if edges > ex.edge_budget():
                    raise QueryError(
                        "recurse exceeded edge budget (ErrTooBig)")
                _set_list_result(child, matrix)
            else:
                # tablet-routed / missing CSR: expand over the wire, dedup
                # on (attr, from, to) keys (reference recurse.go:129-141)
                res = ex._dispatch(TaskQuery(cgq.attr, frontier=frontier))
                edges += res.traversed_edges
                if edges > ex.edge_budget():
                    raise QueryError(
                        "recurse exceeded edge budget (ErrTooBig)")
                matrix = []
                for u, targets in zip(frontier, res.uid_matrix):
                    kept = []
                    for t in targets:
                        ek = (cgq.attr, int(u), int(t))
                        if not spec.allow_loop and ek in seen_edges:
                            continue
                        seen_edges.add(ek)
                        kept.append(int(t))
                    matrix.append(np.asarray(kept, dtype=np.int64))
                _set_list_result(child, matrix)
            child.dest_uids = ex._apply_filter(cgq.filter, child.dest_uids)
            if id(cgq) in level_dests:
                level_dests[id(cgq)].append(child.dest_uids)
            if len(child.dest_uids):
                child.children = build_level(child.dest_uids, remaining - 1)
            out.append(child)
        return out

    sg.children = build_level(sg.dest_uids, depth)
    _record_vars(ex, sg, level_dests)


def _mesh_recurse_path(ex, sg: SubGraph, cgq, csr, depth: int,
                       allow_loop: bool, mesh, formula=None, sets=None,
                       val_children=(), level_dests=()) -> None:
    """All levels of a mesh-sharded recurse in ONE device dispatch: the
    seen-edge vector lives per shard on device across levels, the fresh
    dest blocks all-gather into the next frontier over ICI, and the
    child filter's allow-set formula narrows it device-side
    (mesh_exec.run_recurse — only replicated frontiers and edge totals
    come back). The SubGraph chain replays from the HOST mirrors
    (_expand_dedup, the same vectorized gather the classic small-CSR
    path runs), so matrices, filter narrowing, and value children are
    byte-identical to build_level's depth recursion by construction."""
    seeds = np.asarray(sg.dest_uids, dtype=np.int64)
    with costs.kernel("mesh.recurse", attr=cgq.attr):
        levels = ex.gated(lambda: mesh.run_recurse(csr, seeds, depth,
                                                   allow_loop, formula,
                                                   sets),
                          klass="mesh")
    ex._mesh_fused += 1
    seen = np.zeros(csr.num_edges, dtype=bool)
    attach = sg.children = []
    cum = 0
    frontier = seeds
    for lvl in range(depth + 1):
        fr_sorted = np.sort(frontier)
        cur: list[SubGraph] = []
        # value/scalar children appear at every level (build_level's
        # per-invocation head), including the depth-exhausted tail
        for vq in val_children:
            vchild = SubGraph(gq=vq, attr=vq.attr, src_uids=fr_sorted)
            res = ex._dispatch(TaskQuery(vq.attr, frontier=fr_sorted,
                                         lang=vq.lang))
            vchild.value_matrix = res.value_matrix
            vchild.uid_matrix = res.uid_matrix
            vchild.counts = res.counts
            vchild.dest_uids = res.dest_uids
            cur.append(vchild)
        child = None
        if depth - lvl > 0:
            matrix, total = _expand_dedup(csr, fr_sorted, seen,
                                          allow_loop)
            _book_edges(cgq.attr, total)
            cum += total
            if cum > ex.edge_budget():
                raise QueryError("recurse exceeded edge budget (ErrTooBig)")
            child = SubGraph(gq=cgq, attr=cgq.attr, src_uids=fr_sorted)
            _set_list_result(child, matrix)
            child.dest_uids = ex._apply_filter(cgq.filter,
                                               child.dest_uids)
            if id(cgq) in level_dests:
                level_dests[id(cgq)].append(child.dest_uids)
            cur.append(child)
            # cross-check the device program's level frontiers against
            # the host replay (the host — which evaluates the REAL
            # filter tree — stays authoritative, so a divergence means
            # an allow-set resolver gap or a program bug: surfaced as a
            # counter, never a wrong result)
            if lvl + 1 < len(levels) and not np.array_equal(
                    levels[lvl + 1][0], child.dest_uids):
                mesh.metrics.counter(
                    "dgraph_mesh_replay_divergence_total").inc()
        attach.extend(cur)
        if child is None or not len(child.dest_uids):
            break
        attach = child.children
        frontier = child.dest_uids


def _fused_levels(masks_h: np.ndarray) -> tuple[int, np.ndarray]:
    """(levels of a fused scan whose frontier held a vertex, OR of their
    packed destination masks). The scan runs every level: after the first
    one that reached nothing, each streams the graph for an empty
    frontier and hands back an all-zero mask."""
    depth = masks_h.shape[0]
    reached = masks_h.reshape(depth, -1).any(axis=1)
    live = depth if reached.all() else int(np.argmin(reached)) + 1
    return live, np.bitwise_or.reduce(masks_h[:live], axis=0)


def _recurse_fused_path(ex, sg: SubGraph, cgq, csr, depth: int,
                        allow_loop: bool, level_dests: dict) -> None:
    """All levels in one device dispatch; SubGraph chain built from the
    stacked per-level masks. Matches build_level's output for the
    single-uid-child no-filter shape exactly (tests equality-gate it).

    The seeds go in as ranks, found here on the host (pb.seed_ranks): one
    numpy array is all that crosses to the device, and nothing runs there
    for a request but the jitted program. Level 1 of it reads the seeds'
    forward rows ("push") or streams every in-edge ("stream") by their
    out-degree sum: dgraph_recurse_first_hop_total counts the traversal
    under the mode the same predicate gives the host's degrees.

    Stages of the request's clock (obs/costs.py): pull_graph_for is
    exec.prep; the seed array and the jitted call are dev.dispatch;
    blocked in the fetch is dev.wait; everything the host does with the
    fetched masks is dev.post. In a stacked launch (query/batch.py) only
    the leader has dev.dispatch and dev.wait: a follower is in batch.wait
    until its slices are there."""
    from dgraph_tpu.ops import pallas_bfs as pb

    with costs.stage("exec.prep"):
        g = pb.pull_graph_for(csr)
    nd = len(g.host_in_subjects)
    seeds = np.sort(np.asarray(sg.dest_uids, dtype=np.int64))
    ranks = pb.seed_ranks(g, seeds)
    first_hop = pb.recurse_first_hop_mode(g, ranks)
    metrics = getattr(ex.snap, "metrics", None)
    if metrics is not None:
        metrics.keyed("dgraph_recurse_first_hop_total",
                      labels=("mode",)).inc(first_hop)

    # batched-dispatch seam (query/batch.py): compatible concurrent
    # traversals hand their seed ranks to one multi-source dispatch; a
    # traversal that runs alone runs this, inside its gate slot. Without
    # a batcher this is exactly the old gated solo call
    def _solo_fused():
        with otrace.span("device_kernel", kernel="pb.recurse_fused",
                         depth=depth, edges=g.num_edges,
                         first_hop=first_hop) as sp, \
                costs.kernel("pb.recurse_fused", attr=cgq.attr,
                             stage="dev.dispatch") as ck:
            seeds_h = pb.stack_seeds(g, [ranks], 1)[0]
            masks_p, trav = pb.recurse_fused(
                *pb.fused_graph_args(g), seeds_h, depth=depth,
                chunks=g.chunks, chunks_d=g.chunks_d, allow_loop=allow_loop,
                first_hop_cap=pb.FIRST_HOP_CAP)
            # the fetch is the fence: dispatch is asynchronous, so the
            # timer (and the gate slot) must cover it to book device time
            with costs.stage("dev.wait"):
                masks_h, trav_h = jax.device_get((masks_p, trav))
            with costs.stage("dev.post"):
                d2h = int(masks_h.nbytes + trav_h.nbytes)
                ck.set(h2d=int(seeds_h.nbytes), d2h=d2h)
                if sp:
                    live, union = _fused_levels(masks_h)
                    sp.set(transfer_h2d_bytes=int(seeds_h.nbytes),
                           transfer_d2h_bytes=d2h, levels_live=live,
                           reached=int(pb.unpack_words(union, nd).sum()))
            return masks_h, trav_h

    # ONE host round-trip for the whole traversal, bit-packed in DST-RANK
    # space, fetched under the timer of whichever launch ran it (the solo
    # closure, or the batch leader's runner: slices of its host arrays);
    # the host maps ranks -> uids
    masks_h, trav_h = ex.batched_recurse(g, ranks, first_hop, depth,
                                         allow_loop, _solo_fused)
    with costs.stage("dev.post"):
        live, union = _fused_levels(masks_h)
        _count_levels(ex, live, depth - live)
        traversed = int(trav_h[:live].sum())
        _book_edges(cgq.attr, traversed)
        if traversed > ex.edge_budget():
            raise QueryError("recurse exceeded edge budget (ErrTooBig)")

        def uids_of(words) -> np.ndarray:
            ranks = np.flatnonzero(pb.unpack_words(words, nd))
            return g.host_in_subjects[ranks].astype(np.int64)

        if sg.gq.attr == "var":
            # nothing renders: the variable is all a later block can read
            if id(cgq) in level_dests:
                level_dests[id(cgq)].append(uids_of(union))
            return
        expanded = None if allow_loop else np.zeros(g.num_nodes, dtype=bool)
        frontier = seeds
        attach = sg.children = []
        for lvl in range(live):
            child = SubGraph(gq=cgq, attr=cgq.attr, src_uids=frontier)
            m = LazyRecurseMatrix(
                csr, frontier,
                None if allow_loop else _first_visits(expanded, frontier),
                metrics)
            child.uid_matrix = m
            child.counts = LazyCounts(m)
            child.dest_uids = uids_of(masks_h[lvl])
            if id(cgq) in level_dests:
                level_dests[id(cgq)].append(child.dest_uids)
            attach.append(child)
            attach = child.children
            frontier = child.dest_uids
