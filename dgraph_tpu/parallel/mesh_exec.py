"""Mesh-native cross-shard execution: the ICI fan-out the paper promises.

Reference semantics: a multi-hop traversal crossing predicate shards pays
one ProcessTaskOverNetwork gRPC round trip PER HOP PER GROUP
(worker/task.go:137), each paying a fixed dispatch + sync on top of the
network hop. Here the `intern.Query` fan-out is remapped onto a `jax.sharding.Mesh` (the BASELINE north star):
per-predicate CSR arrays are placed across the mesh as NamedSharding device
arrays (row-range partition; small tablets stay replicated on the classic
single-device/host path), and the planner's WHOLE physical plan — the
expansion chain with its pointwise filters and per-row pagination windows
(query/fusedplan.py), the fused single-child `@recurse`, and the
shortest-path BFS — runs as ONE jitted `shard_map` program whose only
inter-device traffic is one all_gather per hop of (frontier-UID block ‖
local edge total) over ICI. N hops across N shards = one device dispatch
instead of N×hops RPCs.

Program shape (ISSUE 12, the perf remap): fused programs ship ONLY
replicated frontier blocks and per-shard edge totals back to the host —
never per-shard uidMatrix columns. Result materialization is inherently
ragged and host-side by design (SURVEY §7): the host replays each hop's
pruned rows from its CSR mirrors with the same allow-sets the device
applied (fusedplan.replay_hop), byte-identical by construction. Shortest
path runs its whole expandOut loop as a `lax.while_loop` with frontier,
visited set, and distance vector device-resident between hops (12
dispatches → 1), and every program donates its frontier/visited/distance
input buffers (`donate_argnums`, SNIPPETS [1]) so hops stop re-allocating
HBM.

The gRPC path (parallel/remote.py) remains the cross-pod / CPU-host
fallback: shapes the fused programs do not cover fall back to the classic
per-task seam, labeled by reason on dgraph_mesh_fallbacks_total{reason=}.

Observability: every fused dispatch runs under a `device_kernel` span with
one `mesh_hop` event per collective step (obs/otrace.py), and the
`dgraph_mesh_*` counters below land on /metrics next to the query tiers.
"""

from __future__ import annotations

from collections import OrderedDict
from dataclasses import replace

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from dgraph_tpu.obs import otrace
from dgraph_tpu.parallel.dist import (SNT, DistPredCSR, _local_rows,
                                      pad_frontier)
from dgraph_tpu.parallel.mesh import make_mesh
from dgraph_tpu.storage.csr_build import GraphSnapshot, PredCSR


def _target_table(csr: DistPredCSR) -> np.ndarray:
    """Sorted distinct destination uids of one sharded tablet (cached: one
    O(E log E) host pass per placement). Doubles as the rank space for
    traversal visited/distance vectors — anything a hop can reach is in
    here, so a vector over ranks is O(tablet), never O(uid-space)."""
    t = getattr(csr, "_target_table", None)
    if t is None:
        t = (np.unique(csr.indices).astype(np.int32) if len(csr.indices)
             else np.zeros(0, np.int32))
        csr._target_table = t
    return t


def _fcap_for(n: int) -> int:
    return 1 << max(int(np.ceil(np.log2(max(n, 1) + 1))), 4)


def _edge_rows(csr: DistPredCSR) -> jax.Array:
    """[S, edge_cap] local-edge → local-row map, sharded like the CSR;
    padding slots point at row `rows_per` (a reserved always-inactive
    slot). This is the recurse program's per-edge activity gather — the
    mesh analog of pallas_bfs's dst-sorted in_src stream."""
    er = getattr(csr, "_edge_rows", None)
    if er is not None:
        return er
    from jax.sharding import NamedSharding

    n_shards = csr.mesh.shape["shard"]
    ecap = int(csr.sharded.indices.shape[-1])
    rows_per = csr.rows_per
    n_rows = len(csr.subjects)
    out = np.full((n_shards, ecap), rows_per, dtype=np.int32)
    for s in range(n_shards):
        lo = min(s * rows_per, n_rows)
        hi = min((s + 1) * rows_per, n_rows)
        deg = np.diff(csr.indptr[lo: hi + 1]).astype(np.int64)
        local = np.repeat(np.arange(hi - lo, dtype=np.int32), deg)
        out[s, : len(local)] = local
    er = jax.device_put(out, NamedSharding(csr.mesh, P("shard")))
    csr._edge_rows = er
    return er


def _eval_formula(formula: tuple, membs: list[jax.Array]) -> jax.Array:
    """Formula evaluation inside traced programs: jax arrays support the
    same & | ~ operators numpy does, so the ONE implementation
    (fusedplan.eval_formula_np) serves both the device masks and the
    host replay — a future formula-node addition cannot diverge the two
    sides of the byte-identity invariant."""
    from dgraph_tpu.query.fusedplan import eval_formula_np

    return eval_formula_np(formula, membs)


def _pag_window_dense(keep: jax.Array, lptr: jax.Array, erow: jax.Array,
                      rows_per: int, first: jax.Array,
                      offset: jax.Array) -> jax.Array:
    """Per-row [offset, offset+first) window over the filter-SURVIVING
    positions — the device twin of engine._apply_child_row_mods' slicing
    (negative first keeps the last |first| of the post-offset run).
    lptr [rows_per+1] holds the shard-local row→edge offsets, erow the
    per-edge local row. first/offset are traced scalars: one compiled
    program serves every pagination value of the same plan shape."""
    ecap = keep.shape[0]
    ki = keep.astype(jnp.int32)
    ci = jnp.cumsum(ki)
    cexcl = ci - ki
    cext = jnp.concatenate([cexcl, ci[-1:]])             # [ecap + 1]
    base_r = jnp.take(cext, jnp.clip(lptr[:-1], 0, ecap))   # [rows_per]
    cnt_r = jnp.take(cext, jnp.clip(lptr[1:], 0, ecap)) - base_r
    er = jnp.clip(erow, 0, rows_per - 1)
    p = cexcl - jnp.take(base_r, er)
    win = p >= offset
    win &= jnp.where(first > 0, p < offset + first, True)
    win &= jnp.where(first < 0, p >= jnp.take(cnt_r, er) + first, True)
    return keep & win


class MeshExecutor:
    """Owns the device mesh, the tablet placement cache, the allow-set
    cache, and the compiled fused-plan programs. One per Node (or one per
    group submesh on a multi-group pod)."""

    # tablets below this edge count stay replicated (the classic
    # single-device/host path): sharding them buys no bandwidth and pays
    # the all-gather per hop. Aligned with task.HOST_EXPAND_MAX so a
    # sharded tablet is by definition a device-class tablet; per-task
    # expands over one still take the host mirror below the planner's
    # frontier cutover (query/task._expand_csr).
    SHARD_MIN_EDGES = 1 << 16
    _PLACE_CACHE = 512      # placed-PredData entries (identity-keyed)
    _SNAP_CACHE = 8         # placed-snapshot entries (identity-keyed)
    _ALLOW_CACHE = 512      # resolved allow-sets (pred-identity-keyed)
    _DEVSET_CACHE = 256     # uploaded allow-set rank masks
    _DENSE_CACHE = 256      # (tablet, rank-space) edge/row rank maps

    def __init__(self, mesh: Mesh | None = None, n_devices: int | None = None,
                 metrics=None, shard_min_edges: int | None = None,
                 residency=None) -> None:
        from dgraph_tpu.utils.metrics import Registry

        self.mesh = mesh if mesh is not None else make_mesh(n_devices)
        self.metrics = metrics if metrics is not None else Registry()
        if shard_min_edges is not None:
            self.SHARD_MIN_EDGES = int(shard_min_edges)
        # device working-set manager (storage/residency.py): placement
        # defers to it — a tablet whose per-device row-shard would not
        # fit the node's device budget stays on the host/replicated path
        # instead of pinning every device's HBM
        self.residency = residency
        # id(PredData) -> (PredData ref, placed PredData): the assembler
        # reuses PredData identity for clean predicates, so identity-keyed
        # placement keeps per-predicate cache tokens stable across commits
        # to OTHER predicates
        self._placed_pd: OrderedDict[int, tuple] = OrderedDict()
        self._placed_snaps: OrderedDict[int, tuple] = OrderedDict()
        self._progs: dict = {}
        self._allow: OrderedDict[tuple, tuple] = OrderedDict()
        self._dev_sets: OrderedDict[tuple, tuple] = OrderedDict()
        self._dense: OrderedDict[tuple, tuple] = OrderedDict()
        self._bfs_tgt: OrderedDict[tuple, tuple] = OrderedDict()
        m = self.metrics
        self._c_dispatch = m.counter("dgraph_mesh_dispatches_total")
        self._c_hops = m.counter("dgraph_mesh_fused_hops_total")
        self._c_edges = m.counter("dgraph_mesh_traversed_edges_total")
        # per-reason fallback breakdown (ISSUE 12 satellite): the labeled
        # series dgraph_mesh_fallbacks_total{reason=} enumerates every
        # fused-coverage gap from /metrics (one KeyedGauge, no shadow
        # counter — two families under one name would break exposition)
        self._k_fallback = m.keyed("dgraph_mesh_fallbacks_total",
                                   labels=("reason",))
        self._c_fused_q = m.counter("dgraph_mesh_fused_queries_total")
        self._c_unfused_q = m.counter("dgraph_mesh_unfused_queries_total")
        self._c_compiles = m.counter("dgraph_mesh_program_builds_total")
        # device-runtime observatory (obs/devprof.py, ISSUE 19): the
        # node attaches its DevProfiler here so every program-cache miss
        # notes its family + triggering shape key (retrace-storm input);
        # None (--no_devprof) costs one attribute load per build.
        self._prof = None
        m.counter("dgraph_mesh_devices").set(self.n_devices)
        m.counter("dgraph_mesh_sharded_tablets").set(0)
        m.counter("dgraph_mesh_replicated_tablets").set(0)

    @property
    def n_devices(self) -> int:
        return int(self.mesh.shape["shard"])

    def owns(self, csr) -> bool:
        """Is this a tablet THIS executor placed (fused programs only run
        over their own mesh's shards)?"""
        return isinstance(csr, DistPredCSR) and csr.mesh is self.mesh

    def fallback(self, reason: str) -> None:
        """One labeled fused-coverage miss (the engine also folds these
        into the per-query fused/unfused ratio)."""
        self._k_fallback.inc(reason)

    def note_query(self, fused: bool) -> None:
        """Per-query coverage accounting: a query that touched mesh-owned
        tablets either ran its traversals fully fused or recorded at least
        one labeled fallback. fused/(fused+unfused) is the coverage ratio
        surfaced on /debug/metrics."""
        (self._c_fused_q if fused else self._c_unfused_q).inc()

    # -- allow-set caches ----------------------------------------------------

    def allow_cached(self, key: tuple, pd) -> np.ndarray | None:
        hit = self._allow.get(key)
        if hit is not None and hit[0] is pd:
            self._allow.move_to_end(key)
            return hit[1]
        return None

    def allow_store(self, key: tuple, pd, s: np.ndarray) -> None:
        self._allow[key] = (pd, s)
        while len(self._allow) > self._ALLOW_CACHE:
            self._allow.popitem(last=False)

    # -- placement (snapshot assembly → mesh) --------------------------------

    def place_snapshot(self, snap: GraphSnapshot) -> GraphSnapshot:
        """Mesh view of a snapshot: large uid adjacencies become
        row-range-sharded DistPredCSRs over the mesh; small tablets, value
        tables, and token indexes stay replicated (the host keeps them —
        the control-plane side, exactly like the reference's per-node
        tokenizer tables). Identity-cached at both the snapshot and the
        PredData level so cache tokens (qcache.task_token) stay stable."""
        hit = self._placed_snaps.get(id(snap))
        if hit is not None and hit[0] is snap:
            return hit[1]
        out = GraphSnapshot(snap.read_ts)
        out.metrics = getattr(snap, "metrics", None)
        pend_fn = getattr(snap.preds, "pending_attrs", None)
        # capture the pending list BEFORE folded_items: a tablet resolving
        # between the two reads (prefetch folds run on the pool) must land
        # in at least one set — register() no-ops on already-placed attrs,
        # so the overlap direction is safe while the gap direction would
        # silently drop the tablet from the cached placed snapshot
        pending = pend_fn() if pend_fn is not None else []
        if pending:
            # lazy base (ISSUE 15): placement must not fold the world at
            # snapshot time. Folded tablets place eagerly; pending ones
            # register pass-through thunks that fold-then-place on first
            # read — placement stays identity-cached at the PredData
            # level, so cache tokens behave exactly as today
            from dgraph_tpu.storage.csr_build import DelegateThunk, LazyPreds

            preds = LazyPreds()
            preds.hint_fn = getattr(snap.preds, "hint_fn", None)
            out.preds = preds
            for attr, pd in snap.preds.folded_items():
                preds[attr] = self._place_pred(pd)
            for attr in pending:
                preds.register(attr, DelegateThunk(snap.preds, attr,
                                                   wrap=self._place_pred))
            preds.on_resolve = lambda _a, _pd: self._count_placed(preds)
        else:
            for attr, pd in snap.preds.items():
                out.preds[attr] = self._place_pred(pd)
        self._count_placed(out.preds)
        self._placed_snaps[id(snap)] = (snap, out)
        while len(self._placed_snaps) > self._SNAP_CACHE:
            self._placed_snaps.popitem(last=False)
        return out

    def _count_placed(self, preds) -> None:
        """Refresh the sharded/replicated tablet gauges over the placed
        (folded) entries — lazy placements update them as they resolve.
        Concurrent resolutions mutate the dict mid-walk; retry the
        briefly-inconsistent iteration (the overlay_stats contract) —
        a gauge refresh must never fail the triggering read."""
        for _ in range(4):
            sharded = replicated = 0
            try:
                items = getattr(preds, "folded_items", preds.items)()
                for _attr, pd in items:
                    for c in (pd.csr, pd.rev_csr):
                        if c is None:
                            continue
                        if self.owns(c):
                            sharded += 1
                        else:
                            replicated += 1
            except RuntimeError:
                continue
            break
        else:
            return
        self.metrics.counter("dgraph_mesh_sharded_tablets").set(sharded)
        self.metrics.counter("dgraph_mesh_replicated_tablets").set(replicated)

    def _place_pred(self, pd):
        hit = self._placed_pd.get(id(pd))
        if hit is not None and hit[0] is pd:
            self._placed_pd.move_to_end(id(pd))
            return hit[1]
        csr = self._place_csr(pd.csr)
        rev = self._place_csr(pd.rev_csr)
        vec = self._place_vec(pd.vecindex)
        placed = pd if (csr is pd.csr and rev is pd.rev_csr
                        and vec is pd.vecindex) \
            else replace(pd, csr=csr, rev_csr=rev, vecindex=vec)
        self._placed_pd[id(pd)] = (pd, placed)
        while len(self._placed_pd) > self._PLACE_CACHE:
            self._placed_pd.popitem(last=False)
        return placed

    def _place_vec(self, vi):
        """Mesh placement of a vector index: large embedding matrices scan
        row-sharded across the mesh with a replicated top-k merge
        (vector_topk); small ones and delta overlays stay on the classic
        single-device/host path until compaction folds a fresh base."""
        if vi is None or vi.is_overlay or \
                vi.n * vi.dim < self.SHARD_MIN_EDGES:
            return vi
        if self.residency is not None and self.residency.enabled and \
                vi.device_nbytes() // max(self.n_devices, 1) > \
                self.residency.budget:
            self.metrics.counter(
                "dgraph_mesh_residency_deferred_total").inc()
            return vi
        import copy

        placed = copy.copy(vi)
        placed._mesh = self
        placed._mesh_dev = None
        return placed

    def _place_csr(self, csr):
        """Shard one adjacency, or leave it on the fallback path: None,
        already-dist, delta overlays (O(Δ) freshness keeps serving host-side
        until compaction folds a fresh base — then it shards), and small
        tablets (replicated)."""
        if csr is None or getattr(csr, "is_dist", False):
            return csr
        if not isinstance(csr, PredCSR):
            return csr               # OverlayCSR etc.: host fallback
        if csr.num_edges < self.SHARD_MIN_EDGES:
            return csr               # small tablet: replicated
        if self.residency is not None and self.residency.enabled and \
                csr.host_nbytes() // max(self.n_devices, 1) > \
                self.residency.budget:
            # placement defers to the working-set manager: even one
            # row-shard of this tablet would blow the per-device budget —
            # keep it on the warm/cold host path (task._expand_csr) and
            # mark it so the fused-plan classifier can label the miss
            # reason=budget instead of treating it as a small tablet
            self.metrics.counter(
                "dgraph_mesh_residency_deferred_total").inc()
            csr._mesh_deferred = True
            return csr
        sub, ptr, idx = csr.host_arrays()
        placed = DistPredCSR(sub, ptr, idx, self.mesh)
        placed.metrics = self.metrics
        return placed

    # -- dense rank-space precomputes (host, identity-cached) ----------------
    #
    # Fused traversals run DENSE: frontiers are bool masks over a tablet's
    # sorted distinct-target table (the rank space), edges carry
    # precomputed (local row, target rank) indices, and the per-hop
    # exchange is ONE psum of an int32 [nd+1] vector (per-rank
    # contribution counts ‖ local raw edge total). No sorts, no
    # searchsorted over frontiers, no capacity classes that could
    # truncate — the same dense-mask design ops/pallas_bfs proved for the
    # single-device kernel, lifted onto the mesh.

    def _dense_maps(self, csr: DistPredCSR, tgt: np.ndarray):
        """(erank, rrank) device arrays for one (tablet, rank-space)
        pair: erank [S, ecap] maps each local edge to its target's rank
        in `tgt` (nd = dump slot for padding), rrank [S, rows_per] maps
        each local row's SUBJECT to its rank (nd where absent) — the
        hop-to-hop mask hand-off."""
        key = (id(csr), id(tgt))
        hit = self._dense.get(key)
        if hit is not None and hit[0] is csr and hit[1] is tgt:
            self._dense.move_to_end(key)
            return hit[2], hit[3]
        from jax.sharding import NamedSharding

        nd = len(tgt)
        S = csr.mesh.shape["shard"]
        ecap = int(csr.sharded.indices.shape[-1])
        rows_per = csr.rows_per
        n_rows = len(csr.subjects)
        erank = np.full((S, ecap), nd, dtype=np.int32)
        rrank = np.full((S, rows_per), nd, dtype=np.int32)
        for s in range(S):
            lo = min(s * rows_per, n_rows)
            hi = min((s + 1) * rows_per, n_rows)
            seg = csr.indices[csr.indptr[lo]: csr.indptr[hi]]
            if len(seg):
                pos = np.searchsorted(tgt, seg)
                pc = np.clip(pos, 0, max(nd - 1, 0))
                erank[s, : len(seg)] = np.where(
                    (nd > 0) & (tgt[pc] == seg), pc, nd)
            subs = csr.subjects[lo:hi]
            if len(subs):
                pos = np.searchsorted(tgt, subs)
                pc = np.clip(pos, 0, max(nd - 1, 0))
                rrank[s, : len(subs)] = np.where(
                    (nd > 0) & (tgt[pc] == subs), pc, nd)
        sh = NamedSharding(csr.mesh, P("shard"))
        erank_d = jax.device_put(erank, sh)
        rrank_d = jax.device_put(rrank, sh)
        self._dense[key] = (csr, tgt, erank_d, rrank_d)
        while len(self._dense) > self._DENSE_CACHE:
            self._dense.popitem(last=False)
        return erank_d, rrank_d

    def _dense_set_mask(self, s: np.ndarray, tgt: np.ndarray) -> jax.Array:
        """One allow-set as a replicated bool[nd + 1] rank mask (tail
        slot False for padding takes); identity-cached per (set,
        rank-space) so repeated queries skip the upload."""
        key = (id(s), id(tgt))
        hit = self._dev_sets.get(key)
        if hit is not None and hit[0] is s and hit[1] is tgt:
            self._dev_sets.move_to_end(key)
            return hit[2]
        nd = len(tgt)
        m = np.zeros(nd + 1, dtype=bool)
        if nd and len(s):
            pos = np.searchsorted(s, tgt)
            pc = np.clip(pos, 0, len(s) - 1)
            m[:nd] = s[pc] == tgt
        dev = jnp.asarray(m)
        self._dev_sets[key] = (s, tgt, dev)
        while len(self._dev_sets) > self._DEVSET_CACHE:
            self._dev_sets.popitem(last=False)
        return dev

    def _local_ptr(self, csr: DistPredCSR) -> jax.Array:
        """[S, rows_per + 1] local row→edge offsets (the pagination
        window's row boundaries), sharded like the CSR."""
        lp = getattr(csr, "_local_ptr", None)
        if lp is not None:
            return lp
        from jax.sharding import NamedSharding

        S = csr.mesh.shape["shard"]
        rows_per = csr.rows_per
        n_rows = len(csr.subjects)
        out = np.zeros((S, rows_per + 1), dtype=np.int32)
        for s in range(S):
            lo = min(s * rows_per, n_rows)
            hi = min((s + 1) * rows_per, n_rows)
            base = int(csr.indptr[lo])
            out[s, : hi - lo + 1] = csr.indptr[lo: hi + 1] - base
            out[s, hi - lo + 1:] = out[s, hi - lo]
        lp = jax.device_put(out, NamedSharding(csr.mesh, P("shard")))
        csr._local_ptr = lp
        return lp

    # -- whole-plan fused program: N hops + filters + pagination, ONE dispatch

    def _plan_program(self, fcap0: int, meta: tuple, term: tuple = None):
        """meta: per hop (ecap, rows_per, nd, formula, nsets, has_pag).
        The compiled program ships back ONLY the per-hop dest rank masks
        (replicated bool [nd]) and raw edge totals — the host replays
        uidMatrix rows from its own mirrors, so no sharded result
        columns ever cross the device boundary.

        term: optional (ecap, rows_per, ndt, ops) TERMINAL segmented-
        reduce stage (fusedplan.TerminalIR): the groupby key tablet
        expands from the final hop's mask and reduces per key-target
        rank — int32 member counts (posting lists hold no duplicate
        edges, so edge counts ARE distinct-member counts) plus one
        (f32 candidate, f32 valid-count) pair per __agg_* op. The
        per-agg reductions cost extra collectives (psum / pmin / pmax)
        but stay inside the same single dispatch."""
        key = ("plan", fcap0, meta, term)
        prog = self._progs.get(key)
        if prog is not None:
            return prog
        self._c_compiles.inc()
        if self._prof is not None:
            self._prof.on_build("mesh.plan", key)
        mesh = self.mesh
        nargs = 1 + sum(2 + m[4] + (3 if m[5] else 0) + (1 if h else 0)
                        for h, m in enumerate(meta)) + 1
        if term is not None:
            nargs += 3 + len(term[3])

        def run2(*args):
            sub0 = args[0]
            fr0 = args[-1]
            i = 1
            outs = []
            carry_mext = None
            for h, (ecap, rows_per, nd, formula, nsets, has_pag) \
                    in enumerate(meta):
                erow, erank = args[i: i + 2]
                i += 2
                if h:
                    prow = args[i]
                    i += 1
                    act = jnp.concatenate([
                        jnp.take(carry_mext, jnp.clip(prow[0], 0,
                                                      carry_mext.shape[0]
                                                      - 1)),
                        jnp.zeros(1, bool)])
                else:
                    rows = _local_rows(sub0[0], fr0)
                    act = jnp.zeros((rows_per + 1,), bool).at[
                        jnp.where(rows == SNT, rows_per + 1, rows)].set(
                        True, mode="drop")
                sets = args[i: i + nsets]
                i += nsets
                if has_pag:
                    lptr, first, offset = args[i: i + 3]
                    i += 3
                ae = jnp.take(act, erow[0])               # [ecap]
                keep = ae
                if formula is not None:
                    er = erank[0]
                    membs = [jnp.take(s_, er, mode="clip") for s_ in sets]
                    keep &= _eval_formula(formula, membs)
                if has_pag:
                    keep = _pag_window_dense(keep, lptr[0], erow[0],
                                             rows_per, first, offset)
                contrib = jnp.zeros((nd + 1,), jnp.int32).at[
                    jnp.where(keep, erank[0], nd)].add(1, mode="drop")
                trav = jnp.sum(ae, dtype=jnp.int32)
                packed = jnp.concatenate([contrib[:nd], trav[None]])
                tot = lax.psum(packed, "shard")       # the ONE ICI hop
                mask = tot[:nd] > 0
                outs += [mask, tot[nd]]
                carry_mext = jnp.concatenate([mask, jnp.zeros(1, bool)])
            if term is not None:
                _ecap_t, rows_per_t, ndt, ops = term
                erow_t, erank_t, prow_t = args[i: i + 3]
                i += 3
                act = jnp.concatenate([
                    jnp.take(carry_mext, jnp.clip(prow_t[0], 0,
                                                  carry_mext.shape[0] - 1)),
                    jnp.zeros(1, bool)])
                ae = jnp.take(act, erow_t[0])              # [ecap_t]
                iv_all = jnp.where(ae, erank_t[0], ndt)
                contrib = jnp.zeros((ndt + 1,), jnp.int32).at[iv_all].add(
                    1, mode="drop")
                trav = jnp.sum(ae, dtype=jnp.int32)
                cnt = lax.psum(jnp.concatenate([contrib[:ndt], trav[None]]),
                               "shard")
                outs += [cnt[:ndt], cnt[ndt]]
                for a, op in enumerate(ops):
                    av = args[i + a]
                    avx = jnp.concatenate([av[0],
                                           jnp.full(1, jnp.nan, jnp.float32)])
                    v = jnp.take(avx, erow_t[0])
                    ok = ae & ~jnp.isnan(v)
                    iv = jnp.where(ok, erank_t[0], ndt)
                    if op == "min":
                        cand = lax.pmin(jnp.full((ndt + 1,), jnp.inf,
                                                 jnp.float32).at[iv].min(
                            jnp.where(ok, v, jnp.inf), mode="drop"),
                            "shard")[:ndt]
                    elif op == "max":
                        cand = lax.pmax(jnp.full((ndt + 1,), -jnp.inf,
                                                 jnp.float32).at[iv].max(
                            jnp.where(ok, v, -jnp.inf), mode="drop"),
                            "shard")[:ndt]
                    else:        # sum / avg share the f32 sum candidate
                        cand = lax.psum(jnp.zeros((ndt + 1,),
                                                  jnp.float32).at[iv].add(
                            jnp.where(ok, v, 0.0), mode="drop"),
                            "shard")[:ndt]
                    cntv = lax.psum(jnp.zeros((ndt + 1,),
                                              jnp.float32).at[iv].add(
                        jnp.where(ok, 1.0, 0.0), mode="drop"), "shard")[:ndt]
                    outs += [cand, cntv]
            return tuple(outs)

        in_specs: list = [P("shard")]
        for h, (_e, _r, _nd, _f, nsets, has_pag) in enumerate(meta):
            in_specs += [P("shard")] * 2
            if h:
                in_specs.append(P("shard"))
            in_specs += [P()] * nsets
            if has_pag:
                in_specs += [P("shard"), P(), P()]
        out_specs = (P(), P()) * len(meta)
        if term is not None:
            in_specs += [P("shard")] * (3 + len(term[3]))
            out_specs += (P(), P()) + (P(), P()) * len(term[3])
        in_specs.append(P())
        # the seed frontier buffer is donated (SNIPPETS [1]
        # donate_argnums): the program reuses its HBM for the first hop's
        # row scatter instead of allocating fresh
        prog = jax.jit(shard_map(run2, mesh=mesh,
                                 in_specs=tuple(in_specs),
                                 out_specs=out_specs, check_vma=False),
                       donate_argnums=(nargs - 1,))
        self._progs[key] = prog
        return prog

    def run_plan(self, hops: list, seeds: np.ndarray, terminal=None):
        """Execute a whole fused chain — root frontier through every hop's
        filter/pagination/expansion — as ONE device dispatch.

        hops: list of (csr, formula, sets, first, offset) where formula /
        sets come from fusedplan (sets are sorted int64 host arrays).
        Returns one (frontier_in, traversed, next_frontier) per hop; the
        caller replays the pruned uidMatrix rows from the host mirrors
        (fusedplan.replay_hop), byte-identical to the classic loop. Dense
        rank masks cannot truncate, so there is no capacity class to
        outgrow.

        terminal: optional (csr, ops, avals) groupby/aggregation stage
        (fusedplan.TerminalIR) — csr is the key predicate's tablet, ops a
        tuple of agg op names, avals one host f32 [S, rows_per] value
        plane per op (NaN = subject has no value). When given, returns
        (levels, {"table", "counts", "traversed", "aggs"}) with per-rank
        member counts and f32 (candidate, valid-count) pairs, still ONE
        dispatch."""
        seeds = np.asarray(seeds, dtype=np.int64)
        fcap0 = _fcap_for(len(seeds))
        meta = []
        args: list = [hops[0][0].sharded.subjects]
        tgts = []
        prev_tgt = None
        for h, (csr, formula, sets, first, offset) in enumerate(hops):
            tgt = _target_table(csr)
            tgts.append(tgt)
            erank, _rrank = self._dense_maps(csr, tgt)
            ecap = int(csr.sharded.indices.shape[-1])
            has_pag = bool(first or offset)
            meta.append((ecap, csr.rows_per, len(tgt), formula,
                         len(sets), has_pag))
            args += [_edge_rows(csr), erank]
            if h:
                _er, rrank_prev = self._dense_maps(csr, prev_tgt)
                args.append(rrank_prev)
            args += [self._dense_set_mask(s, tgt) for s in sets]
            if has_pag:
                args += [self._local_ptr(csr), jnp.int32(first),
                         jnp.int32(offset)]
            prev_tgt = tgt
        term = None
        tgt_t = None
        if terminal is not None:
            tcsr, ops, avals = terminal
            tgt_t = _target_table(tcsr)
            erank_t, _ = self._dense_maps(tcsr, tgt_t)
            _er2, prow_t = self._dense_maps(tcsr, prev_tgt)
            ecap_t = int(tcsr.sharded.indices.shape[-1])
            term = (ecap_t, tcsr.rows_per, len(tgt_t), tuple(ops))
            args += [_edge_rows(tcsr), erank_t, prow_t]
            from jax.sharding import NamedSharding
            shd = NamedSharding(self.mesh, P("shard"))
            args += [jax.device_put(av, shd) for av in avals]
        args.append(jnp.asarray(pad_frontier(seeds, fcap0)))
        prog = self._plan_program(fcap0, tuple(meta), term)
        with otrace.span("device_kernel", kernel="mesh.plan",
                         hops=len(hops), terminal=bool(term),
                         devices=self.n_devices) as sp:
            with self.mesh:
                flat = prog(*args)
            flat = jax.device_get(flat)  # ONE host round trip, at the end
            self._c_dispatch.inc()
            self._c_hops.inc(len(hops))
            levels = []
            frontier = seeds
            total = 0
            for h in range(len(hops)):
                mask, trav = flat[2 * h], int(flat[2 * h + 1])
                nxt = tgts[h][mask].astype(np.int64)
                total += trav
                otrace.event("mesh_hop", hop=h, edges=trav,
                             frontier=len(frontier), dest=len(nxt))
                levels.append((frontier, trav, nxt))
                frontier = nxt
            term_out = None
            if term is not None:
                base = 2 * len(hops)
                counts = np.asarray(flat[base], dtype=np.int64)
                ttrav = int(flat[base + 1])
                total += ttrav
                aggs = [(np.asarray(flat[base + 2 + 2 * a]),
                         np.asarray(flat[base + 3 + 2 * a]))
                        for a in range(len(term[3]))]
                otrace.event("mesh_hop", hop=len(hops), edges=ttrav,
                             frontier=len(frontier),
                             dest=int(np.count_nonzero(counts)),
                             terminal=True)
                term_out = {"table": tgt_t.astype(np.int64),
                            "counts": counts, "traversed": ttrav,
                            "aggs": aggs}
            self._c_edges.inc(total)
            if sp:
                sp.set(edges=total)
        if terminal is not None:
            return levels, term_out
        return levels

    # -- fused @recurse: edge-dedup levels, ONE dispatch ---------------------

    def _recurse_prog(self, key_meta: tuple):
        (ecap, rows_per, nd, fcap0, depth, allow_loop, formula, nsets) = \
            key_meta
        key = ("recurse", key_meta)
        prog = self._progs.get(key)
        if prog is not None:
            return prog
        self._c_compiles.inc()
        if self._prof is not None:
            self._prof.on_build("mesh.recurse", key)
        mesh = self.mesh

        def run(sub, erow, erank, rrank, *rest):
            sets = rest[: nsets]
            fr0 = rest[-1]
            rows = _local_rows(sub[0], fr0)
            act0 = jnp.zeros((rows_per + 1,), bool).at[
                jnp.where(rows == SNT, rows_per + 1, rows)].set(
                True, mode="drop")

            def body(carry, _):
                act, seen = carry
                ae = jnp.take(act, erow[0])                # [ecap]
                if allow_loop:
                    fresh_e, seen2 = ae, seen
                else:
                    fresh_e = ae & ~seen                   # edge-dedup
                    seen2 = seen | ae                      # (recurse.go:129)
                contrib = jnp.zeros((nd + 1,), jnp.int32).at[
                    jnp.where(fresh_e, erank[0], nd)].add(1, mode="drop")
                trav = jnp.sum(ae, dtype=jnp.int32)
                packed = jnp.concatenate([contrib[:nd], trav[None]])
                tot = lax.psum(packed, "shard")            # ICI hop
                mask = tot[:nd] > 0
                if formula is not None:
                    # classic recurse filters the NEXT frontier
                    # (child.dest_uids), never the matrix rows
                    mask &= _eval_formula(formula,
                                          [s_[:nd] for s_ in sets])
                mext = jnp.concatenate([mask, jnp.zeros(1, bool)])
                act2 = jnp.concatenate([
                    jnp.take(mext, jnp.clip(rrank[0], 0, nd)),
                    jnp.zeros(1, bool)])
                return (act2, seen2), (mask, tot[nd])

            seen0 = jnp.zeros((ecap,), dtype=bool)
            (_a, _s), (masks, tots) = lax.scan(
                body, (act0, seen0), jnp.arange(depth), length=depth)
            return masks, tots

        in_specs = (P("shard"),) * 4 + (P(),) * nsets + (P(),)
        prog = jax.jit(shard_map(
            run, mesh=mesh, in_specs=in_specs,
            out_specs=(P(), P()), check_vma=False),
            donate_argnums=(4 + nsets,))
        self._progs[key] = prog
        return prog

    def run_recurse(self, csr: DistPredCSR, seeds: np.ndarray, depth: int,
                    allow_loop: bool, formula: tuple | None = None,
                    sets: list | None = None):
        """All `depth` edge-dedup recurse levels in ONE dispatch (the mesh
        analog of ops/pallas_bfs.recurse_fused): per level, each shard
        masks its first-traversal edges against a carried seen vector,
        the fresh target-rank contributions merge in ONE psum over ICI,
        and the child filter's allow-set formula narrows the frontier
        mask device-side. Returns one (frontier, traversed) per level;
        matrices replay from the host mirrors (query/recurse.py),
        byte-identical to the stepped (attr, from, to)-dedup wire path."""
        seeds = np.asarray(seeds, dtype=np.int64)
        tgt = _target_table(csr)
        nd = len(tgt)
        fcap0 = _fcap_for(len(seeds))
        ecap = int(csr.sharded.indices.shape[-1])
        erank, rrank = self._dense_maps(csr, tgt)
        devsets = [self._dense_set_mask(s, tgt) for s in (sets or [])]
        prog = self._recurse_prog((ecap, csr.rows_per, nd, fcap0, depth,
                                   allow_loop, formula, len(devsets)))
        with otrace.span("device_kernel", kernel="mesh.recurse",
                         depth=depth, devices=self.n_devices) as sp:
            with self.mesh:
                masks, tots = prog(
                    csr.sharded.subjects, _edge_rows(csr), erank, rrank,
                    *devsets, jnp.asarray(pad_frontier(seeds, fcap0)))
            masks, tots = jax.device_get((masks, tots))
            self._c_dispatch.inc()
            self._c_hops.inc(depth)
            levels = []
            frontier = seeds
            total = 0
            for lvl in range(depth):
                trav = int(tots[lvl])
                total += trav
                otrace.event("mesh_hop", hop=lvl, edges=trav,
                             frontier=len(frontier))
                levels.append((frontier, trav))
                frontier = tgt[masks[lvl]].astype(np.int64)
            self._c_edges.inc(total)
            if sp:
                sp.set(edges=total)
        return levels

    # -- fused shortest-path BFS: the whole expandOut loop, ONE dispatch -----

    def bfs_targets(self, csrs: list[DistPredCSR]) -> np.ndarray:
        """Combined sorted distinct-target table of a multi-predicate
        traversal — the rank space of the BFS distance vector (cached per
        CSR identity tuple)."""
        key = tuple(id(c) for c in csrs)
        hit = self._bfs_tgt.get(key)
        if hit is not None and all(a is b for a, b in zip(hit[0], csrs)):
            self._bfs_tgt.move_to_end(key)
            return hit[1]
        tgt = (np.unique(np.concatenate(
            [_target_table(c) for c in csrs]))
            if csrs else np.zeros(0, np.int32))
        self._bfs_tgt[key] = (tuple(csrs), tgt)
        while len(self._bfs_tgt) > 64:
            self._bfs_tgt.popitem(last=False)
        return tgt

    BFS_UNREACHED = np.int32(np.iinfo(np.int32).max)

    def _bfs_program(self, shapes: tuple, nd: int):
        """shapes: per pred (ecap, rows_per)."""
        key = ("bfs", shapes, nd)
        prog = self._progs.get(key)
        if prog is not None:
            return prog
        self._c_compiles.inc()
        if self._prof is not None:
            self._prof.on_build("mesh.bfs", key)
        mesh = self.mesh
        P_n = len(shapes)

        def run(*args):
            csr_args = args[: 4 * P_n]    # per pred: sub, erow, erank, rrank
            vis0, dist0, src, maxd, budget, stop = args[4 * P_n:]

            acts0 = []
            for p in range(P_n):
                sub = csr_args[4 * p]
                rows_per = shapes[p][1]
                pos = jnp.searchsorted(sub[0], src).astype(jnp.int32)
                posc = jnp.clip(pos, 0, rows_per - 1)
                ok = jnp.take(sub[0], posc) == src
                acts0.append(jnp.zeros((rows_per + 1,), bool).at[
                    jnp.where(ok, posc, rows_per + 1)].set(
                    True, mode="drop"))

            def cond(c):
                _acts, vis, _d, hop, edges, live = c
                # stop >= 0: single-path callers exit once the target's
                # level completes (its whole predecessor level is
                # discovered by then — reference stopExpansion,
                # query/shortest.go); stop < 0 explores exhaustively
                # (k-shortest needs the full level adjacency)
                found = (stop >= 0) & jnp.take(
                    vis, jnp.clip(stop, 0, max(nd - 1, 0)), mode="clip")
                return live & (hop < maxd) & (edges <= budget) & ~found

            def body(c):
                acts, vis, dist, hop, edges = c[:5]
                contrib = jnp.zeros((nd + 1,), jnp.int32)
                for p in range(P_n):
                    erow, erank = csr_args[4 * p + 1], csr_args[4 * p + 2]
                    ae = jnp.take(acts[p], erow[0])
                    contrib = contrib.at[
                        jnp.where(ae, erank[0], nd)].add(1, mode="drop")
                    contrib = contrib.at[nd].add(
                        jnp.sum(ae, dtype=jnp.int32))
                tot = lax.psum(contrib, "shard")           # ICI hop
                gmask = tot[:nd] > 0
                fresh = gmask & ~vis
                vis2 = vis | gmask
                dist2 = jnp.where(fresh, hop + 1, dist)
                fext = jnp.concatenate([fresh, jnp.zeros(1, bool)])
                acts2 = tuple(
                    jnp.concatenate([
                        jnp.take(fext, jnp.clip(
                            csr_args[4 * p + 3][0], 0, nd)),
                        jnp.zeros(1, bool)])
                    for p in range(P_n))
                return (acts2, vis2, dist2, hop + 1,
                        edges + tot[nd], jnp.any(fresh))

            init = (tuple(acts0), vis0, dist0, jnp.int32(0),
                    jnp.int32(0), jnp.bool_(True))
            _a, vis, dist, hop, edges, _l = lax.while_loop(
                cond, body, init)
            return dist, hop, edges

        in_specs = (P("shard"),) * (4 * P_n) + (P(),) * 6
        # visited / distance carries are donated: the whole while_loop
        # reuses their HBM between hops instead of re-allocating per
        # level (the 12-dispatch loop's per-hop cost)
        prog = jax.jit(shard_map(
            run, mesh=mesh, in_specs=in_specs,
            out_specs=(P(), P(), P()), check_vma=False),
            donate_argnums=(4 * P_n, 4 * P_n + 1))
        self._progs[key] = prog
        return prog

    def run_bfs(self, csrs: list[DistPredCSR], src: int, max_depth: int,
                budget: int, stop_at: int | None = None):
        """The whole shortest-path expandOut loop (query/shortest.go:134)
        as ONE `lax.while_loop` dispatch: frontier masks, visited set,
        and distance vector stay device-resident between hops — 12
        stepped dispatches (or 12 gRPC rounds per group) become one
        launch.

        Returns (dist, hops, edges): dist[i] is the BFS level at which
        the combined target table's i-th uid was first reached (UNREACHED
        otherwise), hops the number of levels executed, edges the raw
        traversed-edge total — everything the host needs to rebuild the
        level adjacency byte-identically (query/shortest.py)."""
        tgt = self.bfs_targets(csrs)
        nd = len(tgt)
        if nd == 0:
            return (np.zeros(0, np.int32), 0, 0)
        shapes = tuple((int(c.sharded.indices.shape[-1]), c.rows_per)
                       for c in csrs)
        prog = self._bfs_program(shapes, nd)
        vis = np.zeros(nd, dtype=bool)
        dist = np.full(nd, int(self.BFS_UNREACHED), dtype=np.int32)
        pos = int(np.searchsorted(tgt, src))
        if pos < nd and tgt[pos] == src:
            vis[pos] = True
            dist[pos] = 0
        args = []
        for c in csrs:
            erank, rrank = self._dense_maps(c, tgt)
            args += [c.sharded.subjects, _edge_rows(c), erank, rrank]
        stop_rank = -1
        if stop_at is not None:
            sp_ = int(np.searchsorted(tgt, stop_at))
            if sp_ < nd and tgt[sp_] == stop_at:
                stop_rank = sp_
        args += [jnp.asarray(vis), jnp.asarray(dist),
                 jnp.int32(min(src, int(SNT))), jnp.int32(max_depth),
                 jnp.int32(min(budget, (1 << 30))),
                 jnp.int32(stop_rank)]
        with otrace.span("device_kernel", kernel="mesh.bfs",
                         devices=self.n_devices, preds=len(csrs),
                         nd=nd) as sp:
            with self.mesh:
                dist_d, hops_d, edges_d = prog(*args)
            dist_h, hops_h, edges_h = jax.device_get(
                (dist_d, hops_d, edges_d))
            self._c_dispatch.inc()
            self._c_hops.inc(int(hops_h))
            self._c_edges.inc(int(edges_h))
            otrace.event("mesh_hop", hop=int(hops_h),
                         edges=int(edges_h))
            if sp:
                sp.set(edges=int(edges_h), hops=int(hops_h))
        return dist_h, int(hops_h), int(edges_h)
    # -- sharded vector top-k: row-scan fan-out, replicated merge ------------

    def _vec_program(self, rows_per: int, dim: int, kk: int, metric: str):
        key = ("vec", rows_per, dim, kk, metric)
        prog = self._progs.get(key)
        if prog is not None:
            return prog
        self._c_compiles.inc()
        if self._prof is not None:
            self._prof.on_build("mesh.vector_topk", key)
        mesh = self.mesh

        def run(mat, nrm, valid, qv):
            from dgraph_tpu.ops.vector import _block_neg_dist

            m, n, v = mat[0], nrm[0], valid[0]
            qn2 = jnp.sum(qv * qv)
            qn = jnp.sqrt(qn2)
            nd = _block_neg_dist(m, n, qv, qn, qn2, metric)
            nd = jnp.where(v, nd, -jnp.inf)
            cs, ci = lax.top_k(nd, kk)
            rows = (lax.axis_index("shard") * rows_per + ci).astype(
                jnp.int32)
            # the replicated top-k merge: each shard's local winners
            # all-gather over ICI; the host takes the union as the
            # candidate superset (global top-kk ⊆ union by construction)
            gs = lax.all_gather(cs, "shard")
            gr = lax.all_gather(rows, "shard")
            return gs.reshape(-1), gr.reshape(-1)

        prog = jax.jit(shard_map(
            run, mesh=mesh,
            in_specs=(P("shard"), P("shard"), P("shard"), P()),
            out_specs=(P(), P()), check_vma=False))
        self._progs[key] = prog
        return prog

    def _vec_sharded(self, vi):
        dev = getattr(vi, "_mesh_dev", None)
        if dev is not None:
            return dev
        from jax.sharding import NamedSharding

        nd = self.n_devices
        from dgraph_tpu.ops.vector import row_capacity

        # ceil-division shard rows (dist.shard_rows_per convention): a
        # non-pow2 device count must still tile the pow2 row capacity
        rows_per = -(-max(row_capacity(vi.n), nd) // nd)
        R = rows_per * nd
        mat = np.zeros((nd, rows_per, vi.dim), dtype=np.float32)
        mat.reshape(R, vi.dim)[: vi.n] = vi.vecs
        nrm = np.ones((nd, rows_per), dtype=np.float32)
        nrm.reshape(R)[: vi.n] = np.linalg.norm(vi.vecs, axis=1)
        sh = NamedSharding(self.mesh, P("shard"))
        dev = (jax.device_put(mat, sh), jax.device_put(nrm, sh),
               R, rows_per)
        vi._mesh_dev = dev
        return dev

    def vector_topk(self, vi, q: np.ndarray, kprime: int,
                    dead_rows: np.ndarray) -> np.ndarray:
        """Float32 candidate rows of one similarity probe, row-sharded
        across the mesh (storage/vecindex.search's device stage; the
        float64 re-rank stays on the host, so mesh results are
        byte-identical to the single-device path)."""
        from jax.sharding import NamedSharding

        mat, nrm, R, rows_per = self._vec_sharded(vi)
        valid = np.zeros(R, dtype=bool)
        valid[: vi.n] = True
        if len(dead_rows):
            valid[dead_rows] = False
        vdev = jax.device_put(
            valid.reshape(self.n_devices, rows_per),
            NamedSharding(self.mesh, P("shard")))
        kk = min(kprime, rows_per)
        prog = self._vec_program(rows_per, vi.dim, kk, vi.metric)
        with otrace.span("device_kernel", kernel="mesh.vector_topk",
                         rows=int(vi.n), k=kk,
                         devices=self.n_devices) as sp:
            with self.mesh:
                scores, rows = prog(mat, nrm, vdev,
                                    jnp.asarray(q.astype(np.float32)))
            scores_h, rows_h = jax.device_get((scores, rows))
            self._c_dispatch.inc()
            self.metrics.counter(
                "dgraph_vector_mesh_dispatches_total").inc()
            if sp:
                sp.set(cands=int((scores_h > -np.inf).sum()))
        return rows_h[scores_h > -np.inf]
