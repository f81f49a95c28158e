"""process_task: execute one (predicate, frontier, function) task on a snapshot.

Reference semantics: worker/task.go — processTask (:605) → helpProcessTask
(:635) dispatches on posting-list kind: handleValuePostings (:319, value
predicates: fetch/convert/compare) or handleUidPostings (:476, uid/index/
reverse/count lists: per-uid iteration intersected with the frontier).
Function taxonomy at :211-271: eq/le/lt/ge/gt (indexed, via
worker/tokens.go:124 getInequalityTokens), has, uid_in, regexp (trigram index
+ automaton :768), term (anyofterms/allofterms), full-text, geo (:921),
compare-scalar over the count index (:1498), password. Lossy tokenizers
require post-filtering candidates against stored values (:837-919).

TPU redesign: the per-uid pointer walk becomes one batched CSR gather
(ops.csr.expand) over the predicate's HBM-resident adjacency; index functions
select token rows host-side (the token table is tiny) and the device unions /
intersects the token rows' uid lists. The uidMatrix result stays in CSR form
(flat targets + per-source counts) end to end.

This module is the dispatch seam the north star required: its result uid sets
are diffable 1:1 against the reference's processTask.
"""

from __future__ import annotations

import bisect
import re as remod
from dataclasses import dataclass, field

import numpy as np
import jax.numpy as jnp

from dgraph_tpu.obs import costs, otrace
from dgraph_tpu.ops import csr as csrops
from dgraph_tpu.ops import uidset as us
from dgraph_tpu.storage.csr_build import GraphSnapshot, PredCSR, PredData, TokenIndex
from dgraph_tpu.utils import geo as geomod
from dgraph_tpu.utils import tok as tokmod
from dgraph_tpu.utils.schema import SchemaState
from dgraph_tpu.utils.types import (TypeID, Val, compare_vals, convert,
                                    to_device_scalar, verify_password)


class TaskError(ValueError):
    pass


# below this edge volume a host-mirror gather beats the device's fixed
# per-dispatch + sync cost (the size-adaptive strategy switch; reference
# algo/uidlist.go:147-155 ratio heuristic)
HOST_EXPAND_MAX = 1 << 16


@dataclass
class TaskQuery:
    """One execution task (reference: intern.Query, protos/internal.proto:38)."""

    attr: str
    frontier: np.ndarray | None = None      # subject uids; None = root function
    func: tuple[str, list] | None = None    # (name, args) root/filter function
    reverse: bool = False                   # traverse ReverseKey space (~attr)
    lang: str = ""
    facet_keys: list[str] = field(default_factory=list)
    first: int = 0                          # per-uid result truncation
    # planner override of the host/device expand cutover (query/planner.py
    # estimated-frontier-size decision); 0 = the static HOST_EXPAND_MAX.
    # Purely an execution-strategy knob — results are identical either
    # way, so qcache.task_key deliberately excludes it (cache heat is
    # shared across planner on/off).
    cutover: int = 0


@dataclass
class TaskResult:
    """Reference: intern.Result (protos/internal.proto:69)."""

    uid_matrix: list[np.ndarray] = field(default_factory=list)
    value_matrix: list[list[Val]] = field(default_factory=list)
    facet_matrix: list[list[tuple]] = field(default_factory=list)
    counts: list[int] = field(default_factory=list)
    dest_uids: np.ndarray = field(default_factory=lambda: np.zeros(0, np.int64))
    traversed_edges: int = 0


# ---------------------------------------------------------------------------
# frontier <-> CSR row mapping
# ---------------------------------------------------------------------------

def rows_for_uids(csr: PredCSR, uids: np.ndarray) -> np.ndarray:
    """Map subject uids to CSR rows; missing subjects → sentinel."""
    subjects = csr.host_arrays()[0]
    return us.host_rank_of(subjects, uids, us.SENTINEL32).astype(np.int32)


def _frontier_degrees(csr, uids: np.ndarray):
    """(rows, indptr_h, deg, need) for a frontier over one adjacency's host
    mirrors — the shared first pass of every size-adaptive expand branch."""
    rows = rows_for_uids(csr, uids)
    indptr_h = csr.host_arrays()[1]
    rc = np.clip(rows, 0, max(len(indptr_h) - 2, 0))
    ok = rows != us.SENTINEL32
    deg = np.where(ok, indptr_h[rc + 1] - indptr_h[rc], 0)
    return rows, indptr_h, deg, int(deg.sum())


def _host_expand_matrix(indptr_h: np.ndarray, indices_h: np.ndarray,
                        rows: np.ndarray, deg: np.ndarray, uids: np.ndarray,
                        need: int, cutover: int) -> list[np.ndarray]:
    """Below-cutover uidMatrix straight from the host mirrors (shared by
    the resident and mesh-sharded branches of _expand_csr)."""
    otrace.event("host_expand", need=need,
                 cutover=int(cutover or HOST_EXPAND_MAX))
    offs = np.zeros(len(uids) + 1, dtype=np.int64)
    np.cumsum(deg, out=offs[1:])
    targets = _gather_rows_host(indptr_h, indices_h, rows, deg, offs)
    return [targets[offs[i]: offs[i + 1]] for i in range(len(uids))]


def _gather_rows_host(indptr_h: np.ndarray, indices_h: np.ndarray,
                      rows: np.ndarray, deg: np.ndarray,
                      offs: np.ndarray) -> np.ndarray:
    """Flat host gather of per-row spans: rows (SENTINEL32 = skip) with
    per-slot degree `deg` and output offsets `offs` (cumsum of deg) —
    the shared inner step of the host expand paths."""
    total = int(offs[-1])
    ok = rows != us.SENTINEL32
    rc = np.clip(rows, 0, max(len(indptr_h) - 2, 0))
    starts = np.where(ok, indptr_h[rc], 0).astype(np.int64)
    pos = np.repeat(starts - offs[:-1], deg) + np.arange(total)
    return indices_h[pos].astype(np.int64)


def _tier_prefer_host(csr) -> bool:
    """Residency tier consult (storage/residency.py): True when the
    tablet is COLD — its device footprint exceeds the node's whole device
    budget — so the expand must take the host-mirror gather regardless of
    frontier size. Unmanaged tablets (no ResidencyManager attached) never
    prefer host: exactly the pre-residency behavior.

    This helper sits at the SERVE sites (expand / overlay / index
    union), so a True here counts one cold serve — consult-only callers
    (fused-shape checks) use owner.prefer_host() directly."""
    f = getattr(csr, "prefer_host", None)
    if f is None:
        return False
    try:
        if not f():
            return False
    except Exception:
        return False
    mgr = getattr(csr, "_res", None)
    if mgr is not None:
        mgr.note_cold_serve()
    return True


def _upload_fault_fallback(csr) -> None:
    """An injected residency.h2d_upload fault surfaced mid-expand: count
    it and let the caller serve the byte-identical host gather."""
    mgr = getattr(csr, "_res", None)
    if mgr is not None:
        mgr.metrics.counter(
            "dgraph_residency_host_fallbacks_total").inc()


def _expand_overlay(ov, uids: np.ndarray,
                    cutover: int = 0) -> tuple[list[np.ndarray], int]:
    """Merge-on-read expand over an OverlayCSR (storage/delta.py): gather
    untouched rows from the UNCHANGED base (host mirror below the dispatch
    cutover, ops/csr.expand_masked above it) and splice the overlay's
    replacement rows per frontier slot — O(frontier + Δ), never a merge of
    the tablet. The base device arrays keep identity: a commit costs its
    delta, not a re-fold or re-upload."""
    rb, ro, deg_b, deg_o = ov.frontier_plan(uids)
    need_base = int(deg_b.sum())
    total = need_base + int(deg_o.sum())
    offs = np.zeros(len(uids) + 1, dtype=np.int64)
    np.cumsum(deg_b, out=offs[1:])
    base = ov.base
    if base is None or need_base == 0:
        base_targets = np.zeros(0, np.int64)
    elif need_base <= (cutover or HOST_EXPAND_MAX) \
            or _tier_prefer_host(base):
        _, indptr_h, indices_h = base.host_arrays()
        base_targets = _gather_rows_host(indptr_h, indices_h, rb, deg_b,
                                         offs)
    else:
        from dgraph_tpu.utils.faults import FaultError

        cap = 1 << max(int(np.ceil(np.log2(need_base + 1))), 4)
        try:
            with otrace.span("device_kernel", kernel="csr.expand_masked",
                             need=need_base,
                             cutover=int(cutover or HOST_EXPAND_MAX)) as sp, \
                    costs.kernel("csr.expand_masked") as ck:
                res = csrops.expand_masked(base.indptr, base.indices,
                                           jnp.asarray(rb), ro >= 0,
                                           out_cap=cap)
                if sp:
                    # fence so the kernel's wall time lands in THIS span,
                    # not wherever the lazy value is first read
                    res.targets.block_until_ready()
                targets_dev = np.asarray(res.targets)  # one D2H, shared
                ck.set(h2d=int(rb.nbytes), d2h=int(targets_dev.nbytes))
                if sp:
                    sp.set(edges=need_base,
                           transfer_h2d_bytes=int(rb.nbytes),
                           transfer_d2h_bytes=int(targets_dev.nbytes))
                base_targets = targets_dev[:need_base].astype(np.int64)
        except FaultError:
            # injected residency.h2d_upload fault: the host gather is
            # byte-identical by the size-adaptive-strategy contract
            _upload_fault_fallback(base)
            _, indptr_h, indices_h = base.host_arrays()
            base_targets = _gather_rows_host(indptr_h, indices_h, rb,
                                             deg_b, offs)
    matrix = [base_targets[offs[i]: offs[i + 1]] for i in range(len(uids))]
    for i in np.flatnonzero(ro >= 0).tolist():
        matrix[i] = ov.delta.rows[ro[i]]
    return matrix, total


def _expand_csr(csr: PredCSR, uids: np.ndarray, first: int = 0,
                cutover: int = 0) -> tuple[list[np.ndarray], int]:
    """uidMatrix for a frontier over one adjacency; device gather + host split.

    Two-pass count-then-gather (SURVEY §7): the output capacity is the
    frontier's exact degree sum (counted on the cached host indptr mirror),
    rounded to a pow2 capacity class to bound jit recompiles — NOT the
    predicate's total edge count. A 1-uid frontier on a 16M-edge predicate
    allocates its own degree, not the whole edge array.

    cutover: planner override of the host/device switch point (0 = the
    static HOST_EXPAND_MAX); the two paths produce identical matrices."""
    from dgraph_tpu.storage.delta import OverlayCSR

    if len(uids) == 0 or csr is None:
        return [np.zeros(0, np.int64) for _ in range(len(uids))], 0
    if getattr(csr, "is_dist", False):
        # mesh-sharded tablet: the SAME size-adaptive host/device cutover
        # as the resident path (the planner's estimated-frontier decision
        # applies unchanged) — a small frontier gathers from the host
        # mirrors in microseconds; past the cutover the expand runs SPMD
        # over the owning group's submesh (ProcessTaskOverNetwork remapped
        # to ICI, parallel/dist.DistPredCSR)
        rows, indptr_h, deg, need = _frontier_degrees(csr, uids)
        if need <= (cutover or HOST_EXPAND_MAX):
            matrix = _host_expand_matrix(indptr_h, csr.host_arrays()[2],
                                         rows, deg, uids, need, cutover)
            total = need
        else:
            with otrace.span("device_kernel", kernel="dist.expand",
                             need=need,
                             cutover=int(cutover or HOST_EXPAND_MAX)) as sp, \
                    costs.kernel("dist.expand"):
                matrix, total = csr.expand_matrix(uids)
                if sp:
                    sp.set(edges=total)
    elif isinstance(csr, OverlayCSR):
        matrix, total = _expand_overlay(csr, uids, cutover)
    else:
        rows, indptr_h, deg, need = _frontier_degrees(csr, uids)
        if need <= (cutover or HOST_EXPAND_MAX) or _tier_prefer_host(csr):
            # size-adaptive strategy (the TPU-era analog of the reference's
            # linear/gallop/binary ratio switch, algo/uidlist.go:147-155):
            # a small gather is microseconds on the cached host mirror but
            # pays fixed per-dispatch + sync latency on device — the device
            # path wins only once the edge volume amortizes it. COLD
            # tablets (residency tier: footprint > device budget) take
            # this path at ANY frontier size.
            matrix = _host_expand_matrix(indptr_h, csr.host_arrays()[2],
                                         rows, deg, uids, need, cutover)
            total = need
        else:
            from dgraph_tpu.utils.faults import FaultError

            try:
                cap = 1 << max(int(np.ceil(np.log2(need + 1))), 4)
                with otrace.span("device_kernel", kernel="csr.expand",
                                 need=need,
                                 cutover=int(cutover
                                             or HOST_EXPAND_MAX)) as sp, \
                        costs.kernel("csr.expand") as ck:
                    res = csrops.expand(csr.indptr, csr.indices,
                                        jnp.asarray(rows), out_cap=cap)
                    total = int(res.total)   # device sync point
                    if total > cap:  # capacity retry (cannot happen)
                        res = csrops.expand(csr.indptr, csr.indices,
                                            jnp.asarray(rows),
                                            out_cap=total)
                    targets_dev = np.asarray(res.targets)
                    ck.set(h2d=int(rows.nbytes),
                           d2h=int(targets_dev.nbytes))
                    if sp:
                        sp.set(edges=total,
                               transfer_h2d_bytes=int(rows.nbytes),
                               transfer_d2h_bytes=int(targets_dev.nbytes))
                targets = targets_dev[:total].astype(np.int64)
                counts = np.asarray(res.counts)[: len(uids)]
                offs = np.zeros(len(uids) + 1, dtype=np.int64)
                np.cumsum(counts, out=offs[1:])
                matrix = [targets[offs[i]: offs[i + 1]]
                          for i in range(len(uids))]
            except FaultError:
                # injected residency.h2d_upload fault: the host gather
                # is byte-identical, the read never fails
                _upload_fault_fallback(csr)
                matrix = _host_expand_matrix(
                    indptr_h, csr.host_arrays()[2], rows, deg, uids,
                    need, cutover)
                total = need
    return apply_first(matrix, first), total


def apply_first(matrix: list[np.ndarray], first: int) -> list[np.ndarray]:
    """Per-uid result truncation (intern.Query.first) — shared by the solo
    expand path and the batched demux (query/batch.py), so both truncate
    identically."""
    if first > 0:
        return [m[:first] for m in matrix]
    if first < 0:
        return [m[first:] for m in matrix]
    return matrix


def _merge_matrix(matrix: list[np.ndarray]) -> np.ndarray:
    if not matrix:
        return np.zeros(0, np.int64)
    return np.unique(np.concatenate(matrix)) if any(len(m) for m in matrix) else np.zeros(0, np.int64)


# ---------------------------------------------------------------------------
# index helpers
# ---------------------------------------------------------------------------

def _index_uids_for_rows(ti: TokenIndex, rows: list[int]) -> np.ndarray:
    """Union of uid lists of the chosen token rows (size-adaptive: host
    merge below the dispatch-amortization point, device merge above;
    COLD-tier indexes — residency consult — stay on the host merge)."""
    if not rows:
        return np.zeros(0, np.int64)
    indptr_h, uids_h = ti.host_arrays()
    total = int(sum(indptr_h[r + 1] - indptr_h[r] for r in rows))

    def host_union():
        parts = [uids_h[indptr_h[r]: indptr_h[r + 1]] for r in rows]
        return np.unique(np.concatenate(parts)) if parts \
            else np.zeros(0, np.int64)

    costs.add_rows(total)
    if total <= HOST_EXPAND_MAX or _tier_prefer_host(ti):
        return host_union()
    from dgraph_tpu.utils.faults import FaultError

    rows_arr = us.make_set(np.asarray(rows, dtype=np.int32), capacity=len(rows))
    cap = int(indptr_h[-1]) or 1
    try:
        with otrace.span("device_kernel", kernel="csr.expand_dest",
                         need=total, rows=len(rows)) as sp, \
                costs.kernel("csr.expand_dest") as ck:
            dest, _total = csrops.expand_dest(ti.indptr, ti.uids, rows_arr,
                                              out_cap=cap)
            out = us.to_numpy(dest).astype(np.int64)
            ck.set(d2h=int(out.nbytes))
            if sp:
                sp.set(edges=int(len(out)),
                       transfer_d2h_bytes=int(out.nbytes))
        return out
    except FaultError:
        # injected residency.h2d_upload fault: host merge, byte-identical
        _upload_fault_fallback(ti)
        return host_union()


def _index_uids_intersect_rows(ti: TokenIndex, rows: list[int]) -> np.ndarray:
    """Intersection of uid lists of the chosen token rows (allofterms) —
    on the cached host mirrors (overlay-merged indexes never pay a device
    round-trip here)."""
    if not rows:
        return np.zeros(0, np.int64)
    indptr, uids_h = ti.host_arrays()
    out = None
    for r in rows:
        u = uids_h[indptr[r]: indptr[r + 1]]
        out = u if out is None else us.intersect_host(out, u)
        if len(out) == 0:
            break
    return out


def _tokens_for(pd: PredData, schema: SchemaState, v: Val,
                prefer: tuple[str, ...]) -> tuple[str, list[bytes]]:
    """Pick a tokenizer (preference order) and produce query tokens.

    A predicate indexed per schema but with no index rows yet (no data at
    this read_ts) matches zero uids instead of erroring."""
    names = schema.tokenizer_names(pd.attr)
    for p in prefer:
        if p in names:
            if p not in pd.indexes:
                return p, []  # indexed, but empty at this snapshot
            tz = tokmod.get(p)
            sv = convert(v, tz.type_id) if v.tid != tz.type_id else v
            return p, [t[1:] for t in tz.tokens(sv)]  # strip ident byte: index rows store it stripped
    raise TaskError(f"predicate {pd.attr} needs @index({'|'.join(prefer)})")


def _ineq_rows(ti: TokenIndex, op: str, token: bytes) -> list[int]:
    """Token rows satisfying an inequality against a *sortable* tokenizer
    (reference: worker/tokens.go:124 getInequalityTokens — walks the sorted
    index bucket space). Terms are byte-ordered == value-ordered."""
    i = bisect.bisect_left(ti.terms, token)
    if op == "eq":
        return [i] if i < len(ti.terms) and ti.terms[i] == token else []
    if op in ("lt", "le"):
        hi = bisect.bisect_right(ti.terms, token)
        if op == "lt" and i < len(ti.terms) and ti.terms[i] == token:
            return list(range(0, i))
        return list(range(0, hi))
    if op in ("gt", "ge"):
        if op == "ge":
            return list(range(i, len(ti.terms)))
        hi = bisect.bisect_right(ti.terms, token)
        return list(range(hi, len(ti.terms)))
    raise TaskError(f"bad inequality {op}")


def _stored_values(pd: PredData, u: int) -> list[Val]:
    """Every stored value of subject u: the full [type] list when present
    (host_values holds only the first-by-sort representative — a match on
    ANY element counts), else the scalar, else lang-tagged values. Shared by
    all lossy-tokenizer post-filters (eq/ineq, regexp, geo)."""
    vals = list(pd.list_values.get(u, ()))
    if not vals:
        sv = pd.host_values.get(u)
        vals = [sv] if sv is not None else []
    if not vals and u in pd.lang_values:
        vals = list(pd.lang_values[u].values())
    return [v for v in vals if v is not None]


def _post_filter_compare(pd: PredData, uids: np.ndarray, op: str, v: Val) -> np.ndarray:
    """Exact re-check for lossy tokenizers (reference worker/task.go:837-919)."""
    keep = []
    for u in uids.tolist():
        if any(compare_vals(op, x, v) for x in _stored_values(pd, int(u))):
            keep.append(u)
    return np.asarray(keep, dtype=np.int64)


def _eq_candidates(pd: PredData, schema, v: Val) -> np.ndarray:
    name, toks = _tokens_for(
        pd, schema, v, ("int", "float", "bool", "exact", "hash", "term",
                        "year", "month", "day", "hour"))
    ti = pd.indexes.get(name)
    if ti is None:
        return np.zeros(0, np.int64)
    rows = [r for t in toks if (r := ti.term_row(t)) >= 0]
    uids = _index_uids_for_rows(ti, rows)
    if tokmod.get(name).lossy:
        uids = _post_filter_compare(pd, uids, "eq", v)
    return uids


# ---------------------------------------------------------------------------
# main dispatch
# ---------------------------------------------------------------------------

def process_task(snap: GraphSnapshot, q: TaskQuery,
                 schema: SchemaState) -> TaskResult:
    """Execute one task against a snapshot (reference worker/task.go:605)."""
    attr = q.attr
    if attr.startswith("~"):
        attr = attr[1:]
        q = TaskQuery(attr, q.frontier, q.func, True, q.lang, q.facet_keys,
                      q.first, q.cutover)
    pd = snap.pred(attr) or PredData(attr, schema.type_of(attr))
    res = TaskResult()

    fname = q.func[0].lower() if q.func else None
    args = q.func[1] if q.func else []

    # ---- root functions (no frontier): produce dest_uids ------------------
    if q.frontier is None:
        if fname == "similar_to":
            # vector similarity probe (storage/vecindex.py): dest_uids is
            # the top-k set; value_matrix carries the aligned distances so
            # the engine can expose them as the `vector_distance` val var
            _similar_root(snap, pd, schema, args, res)
            return res
        res.dest_uids = _root_func(snap, pd, schema, fname, args, q)
        return res

    frontier = np.asarray(q.frontier, dtype=np.int64)

    # ---- frontier + uid-edge predicate: expand ----------------------------
    entry_tid = pd.type_id
    if entry_tid == TypeID.UID or pd.csr is not None or q.reverse:
        csr = pd.rev_csr if q.reverse else pd.csr
        matrix, traversed = _expand_csr(csr, frontier, q.first, q.cutover) \
            if csr is not None else (
            [np.zeros(0, np.int64) for _ in frontier], 0)
        return finish_uid_expand(pd, q, frontier, matrix, traversed)

    # ---- frontier + value predicate: fetch values / compare filter --------
    # vectorized presence over the device-aligned value table: one
    # searchsorted instead of a dict probe per frontier uid
    # (handleValuePostings' per-uid posting fetch, worker/task.go:319)
    costs.add_rows(len(frontier))      # value rows scanned host-side
    if pd.value_subjects_host is not None:
        vsub = pd.value_subjects_host
        pos = np.searchsorted(vsub, frontier)
        posc = np.clip(pos, 0, max(len(vsub) - 1, 0))
        present = (len(vsub) > 0) & (vsub[posc] == frontier)
    else:
        present = np.zeros(len(frontier), dtype=bool)

    if fname == "has" and not q.lang:
        # value_subjects includes lang-only nodes (csr_build appends them),
        # so presence alone decides has() — no per-uid Python loop
        res.dest_uids = frontier[present]
        res.value_matrix = [[] for _ in frontier]
        return res

    if (fname in ("eq", "le", "lt", "ge", "gt") and not q.lang
            and pd.num_values_host is not None
            and not schema.is_list(attr)
            and pd.type_id in (TypeID.INT, TypeID.FLOAT, TypeID.BOOL,
                               TypeID.DATETIME)):
        # num_values_host holds ONE representative value per subject, so the
        # vector fast path is wrong for [type] list predicates (a match on
        # any element counts) — those fall through to the all-values loop,
        # which reads pd.list_values.
        # numeric compare on the exact float64 mirror: gather + compare per
        # frontier slot (the indexed-ineq fast path of tokens.go, but as one
        # vector op over the frontier). Exact for INT < 2^53, DATETIME
        # (epoch seconds), FLOAT, BOOL — the same lattice the host compares.
        vs = [_parse_arg_val(pd, schema, a)
              for a in (args if fname == "eq" else args[:1])]
        rhs = [to_device_scalar(v) for v in vs]
        nv = pd.num_values_host
        x = np.where(present, nv[posc], np.nan)
        keep = np.zeros(len(frontier), dtype=bool)
        for r in (r for r in rhs if r is not None):
            if fname == "eq":
                keep |= x == r
            elif fname == "le":
                keep |= x <= r
            elif fname == "lt":
                keep |= x < r
            elif fname == "ge":
                keep |= x >= r
            elif fname == "gt":
                keep |= x > r
        res.dest_uids = frontier[keep]
        res.value_matrix = [
            [pd.host_values[int(u)]] if k and int(u) in pd.host_values else []
            for u, k in zip(frontier, keep)]
        return res

    res.value_matrix = []
    lang_chain = q.lang.split(":") if q.lang else ()
    for u, pres in zip(frontier.tolist(), present):
        vals: list[Val] = []
        if q.lang:
            # language preference chain "fr:es:." — first hit wins; "."
            # means untagged-first-then-any (reference: @lang fallback,
            # query/outputnode.go valToBytes language handling)
            lv = pd.lang_values.get(int(u), {})
            for lg in lang_chain:
                if lg == ".":
                    sv = pd.host_values.get(int(u))
                    if sv is not None:
                        vals = [sv]
                    elif lv:
                        vals = [next(iter(lv.values()))]
                    break
                if lg in lv:
                    vals = [lv[lg]]
                    break
        elif pres:
            lv = pd.list_values.get(int(u))
            if lv is not None:
                vals = list(lv)        # [type] predicate: every value
            else:
                sv = pd.host_values.get(int(u))
                if sv is not None:
                    vals = [sv]
        res.value_matrix.append(vals)
    if q.facet_keys:
        # facets on VALUE edges live at the untagged slot (subj, 0); lang
        # slots carry their own (reference: facets on scalar postings)
        from dgraph_tpu.storage.postings import lang_uid
        slot = lang_uid(q.lang.split(":")[0]) if q.lang else 0
        res.facet_matrix = [[pd.facets.get((int(u), slot), ())]
                            for u in frontier]
    if fname in ("eq", "le", "lt", "ge", "gt"):
        # eq(pred, v1, v2, ...) matches ANY listed value (reference parses the
        # multi-value form on root and frontier paths alike)
        vs = [_parse_arg_val(pd, schema, a) for a in (args if fname == "eq" else args[:1])]
        keep = np.asarray(
            [any(compare_vals(fname, x, v) for x in vals for v in vs)
             for vals in res.value_matrix],
            dtype=bool)
        res.dest_uids = frontier[keep]
    elif fname == "has":
        # has(attr) matches lang-only nodes too (the data key exists)
        keep = np.asarray(
            [len(vals) > 0 or int(u) in pd.lang_values
             for u, vals in zip(frontier.tolist(), res.value_matrix)], dtype=bool)
        res.dest_uids = frontier[keep]
    elif fname == "checkpwd":
        keep = []
        for u, vals in zip(frontier.tolist(), res.value_matrix):
            ok = bool(vals) and verify_password(str(args[0]), str(vals[0].value))
            keep.append(ok)
        res.dest_uids = frontier[np.asarray(keep, dtype=bool)]
        res.value_matrix = [[Val(TypeID.BOOL, k)] for k in keep]
    else:
        res.dest_uids = frontier[
            np.asarray([len(v) > 0 for v in res.value_matrix], dtype=bool)]
    return res


def finish_uid_expand(pd: PredData, q: TaskQuery, frontier: np.ndarray,
                      matrix: list[np.ndarray], traversed: int) -> TaskResult:
    """Host tail of a uid-predicate frontier task — everything after the
    adjacency gather (facets, uid_in/has filter functions, dest merge).
    Shared by process_task's solo path and the batched-dispatch demux
    (query/batch.py), so a batched task's result is byte-identical to solo
    execution by construction. q must already be reverse-resolved (attr
    stripped of "~", q.reverse set) exactly as process_task rewrites it."""
    res = TaskResult()
    fname = q.func[0].lower() if q.func else None
    args = q.func[1] if q.func else []
    res.uid_matrix = matrix
    res.counts = [len(m) for m in matrix]
    res.traversed_edges = traversed
    if q.facet_keys:
        res.facet_matrix = [
            [pd.facets.get((int(s), int(o)), ()) for o in m]
            for s, m in zip(frontier, matrix)]
    # filter-function applied over the frontier itself (uid_in / has)
    if fname == "uid_in":
        # uid_in(pred, u1, u2, ...) keeps subjects with ANY listed
        # object (decimal and 0x-hex uid forms accepted)
        want = {int(str(a), 0) for a in args}
        keep = np.asarray([bool(want.intersection(m)) for m in matrix],
                          dtype=bool)
        res.dest_uids = frontier[keep]
    elif fname == "has":
        # has(attr) over a frontier: subjects with >= 1 edge (or a value,
        # for mixed untyped predicates)
        keep = np.asarray([len(m) > 0 for m in matrix], dtype=bool)
        if pd.value_subjects_host is not None:
            vsub = pd.value_subjects_host
            posv = np.clip(np.searchsorted(vsub, frontier), 0,
                           max(len(vsub) - 1, 0))
            keep |= (len(vsub) > 0) & (vsub[posv] == frontier)
        res.dest_uids = frontier[keep]
    else:
        res.dest_uids = _merge_matrix(matrix)
    return res


def _parse_arg_val(pd: PredData, schema, arg) -> Val:
    if isinstance(arg, Val):
        return arg
    tid = pd.type_id if pd.type_id != TypeID.DEFAULT else TypeID.STRING
    if tid == TypeID.UID:
        tid = TypeID.STRING
    return convert(Val(TypeID.STRING, str(arg)), tid)


def _root_func(snap: GraphSnapshot, pd: PredData, schema, fname: str | None,
               args: list, q: TaskQuery) -> np.ndarray:
    if fname is None:
        raise TaskError("root query needs a function or explicit uids")
    if fname == "uid":
        return np.unique(np.asarray([int(a) for a in args], dtype=np.int64))
    if fname == "has":
        if q.reverse:
            # has(~pred): nodes with at least one INCOMING edge
            if pd.rev_csr is None:
                return np.zeros(0, np.int64)
            from dgraph_tpu.storage.delta import csr_subjects_host

            return csr_subjects_host(pd.rev_csr)
        return pd.has_subjects().astype(np.int64)

    if fname in ("le", "lt", "ge", "gt", "eq"):
        # compare-scalar over count index: eq(count(pred), N); the reverse
        # form eq(count(~pred), N) compares in-degrees over the reverse CSR
        if args and isinstance(args[0], str) and args[0] == "__count__":
            return _count_func(pd, fname, int(args[1]), reverse=q.reverse)
        if not args:
            if fname == "eq":
                # eq(pred, []) — degenerate but parseable; matches nothing
                return np.zeros(0, np.int64)
            raise TaskError(f"{fname}({pd.attr}) needs a value to compare")
        v = _parse_arg_val(pd, schema, args[0])
        if fname == "eq":
            out = [_eq_candidates(pd, schema, vv) for vv in
                   [v] + [_parse_arg_val(pd, schema, a) for a in args[1:]]]
            return np.unique(np.concatenate(out)) if out else np.zeros(0, np.int64)
        name, toks = _tokens_for(pd, schema, v, ("int", "float", "exact",
                                                 "year", "month", "day", "hour"))
        ti = pd.indexes.get(name)
        if ti is None or not toks:
            return np.zeros(0, np.int64)
        rows = _ineq_rows(ti, fname, toks[0])
        uids = _index_uids_for_rows(ti, rows)
        if tokmod.get(name).lossy:
            uids = _post_filter_compare(pd, uids, fname, v)
        return uids

    if fname in ("anyofterms", "allofterms"):
        return _terms_func(pd, schema, fname, str(args[0]), "term")
    if fname in ("anyoftext", "alloftext"):
        # the attr's lang tag picks the full-text analyzer (tok/fts.go):
        # alloftext(desc@ru, ...) stems the query the way @ru values were
        # indexed
        return _terms_func(pd, schema,
                           "anyofterms" if fname == "anyoftext" else "allofterms",
                           str(args[0]), "fulltext", lang=q.lang)
    if fname == "regexp":
        return _regexp_func(pd, schema, str(args[0]),
                            str(args[1]) if len(args) > 1 else "")
    if fname in ("near", "within", "contains", "intersects"):
        return _geo_func(pd, schema, fname, args)
    if fname == "uid_in":
        raise TaskError("uid_in is not a root function")
    raise TaskError(f"unknown function {fname!r}")


def parse_similar_args(pd: PredData, args: list) -> tuple[np.ndarray, int]:
    """similar_to(pred, $vec, k) argument canonicalization: one vector
    literal (string "[...]" / JSON array / GraphQL var) + one integer k,
    accepted in either order (the reference's v24 surface puts k first)."""
    from dgraph_tpu.utils.types import parse_vector

    vec_arg = k_arg = None
    for a in args:
        if isinstance(a, bool):
            raise TaskError(f"similar_to({pd.attr}): bad argument {a!r}")
        if isinstance(a, int) and k_arg is None:
            k_arg = a
        elif isinstance(a, (str, list, tuple)) and vec_arg is None:
            vec_arg = a
        else:
            raise TaskError(
                f"similar_to({pd.attr}) takes one vector and one integer k")
    if vec_arg is None or k_arg is None:
        raise TaskError(
            f"similar_to({pd.attr}) needs a query vector and k")
    if k_arg <= 0:
        raise TaskError(f"similar_to({pd.attr}): k must be >= 1")
    try:
        vec = np.asarray(parse_vector(vec_arg), dtype=np.float32)
    except ValueError as e:
        raise TaskError(f"similar_to({pd.attr}): {e}") from None
    return vec, int(k_arg)


def _similar_root(snap: GraphSnapshot, pd: PredData, schema,
                  args: list, res: TaskResult) -> None:
    from dgraph_tpu.storage import vecindex as vecmod

    spec = schema.vector_spec(pd.attr)
    if spec is None:
        raise TaskError(f"predicate {pd.attr} needs @index(vector(...))")
    vec, k = parse_similar_args(pd, args)
    if len(vec) != spec.dim:
        raise TaskError(
            f"similar_to({pd.attr}): query vector dim {len(vec)} != "
            f"schema dim {spec.dim}")
    vi = pd.vecindex
    if vi is None:
        # indexed per schema but empty at this snapshot: zero matches
        res.dest_uids = np.zeros(0, np.int64)
        return
    uids, dists = vecmod.search(vi, vec, k,
                                metrics=getattr(snap, "metrics", None))
    set_similar_result(res, uids, dists)


def set_similar_result(res: TaskResult, uids: np.ndarray,
                       dists: np.ndarray) -> None:
    """Shape ranked (uid, distance) pairs into a TaskResult — shared by
    the solo similar_to root and the batched vector demux (query/batch.py).
    dest_uids is a SORTED uid set (engine set algebra); distances ride
    value_matrix in the same order."""
    order = np.argsort(uids, kind="stable")
    res.dest_uids = uids[order]
    res.value_matrix = [[Val(TypeID.FLOAT, float(d))]
                        for d in dists[order]]


def _count_func(pd: PredData, op: str, n: int,
                reverse: bool = False) -> np.ndarray:
    """Compare-scalar on degree (reference countParams.evaluate :1498; the
    count index becomes a device degree reduction over the CSR)."""
    csr = pd.rev_csr if reverse else pd.csr
    if csr is None:
        return np.zeros(0, np.int64)
    from dgraph_tpu.storage.delta import csr_subjects_degrees

    subjects, deg = csr_subjects_degrees(csr)
    mask = {"eq": deg == n, "le": deg <= n, "lt": deg < n,
            "ge": deg >= n, "gt": deg > n}[op]
    return subjects[mask]


def _empty_or_missing_index(pd: PredData, schema, tokname: str) -> np.ndarray | None:
    """Indexed per schema but no rows at this snapshot → zero matches;
    not indexed at all → None (caller raises TaskError)."""
    if tokname in schema.tokenizer_names(pd.attr):
        return np.zeros(0, np.int64)
    return None


def _terms_func(pd: PredData, schema, fname: str, text: str, tokname: str,
                lang: str = "") -> np.ndarray:
    ti = pd.indexes.get(tokname)
    if ti is None:
        empty = _empty_or_missing_index(pd, schema, tokname)
        if empty is not None:
            return empty
        raise TaskError(f"predicate {pd.attr} needs @index({tokname})")
    tz = tokmod.get(tokname)
    if tokname == "fulltext" and lang:
        toks = tokmod.fulltext_tokens(text, lang.split(":")[0])
    else:
        toks = [t[1:] for t in tz.tokens(Val(TypeID.STRING, text))]
    rows = [r for t in toks if (r := ti.term_row(t)) >= 0]
    if fname == "allofterms":
        if len(rows) != len(toks):
            return np.zeros(0, np.int64)
        return _index_uids_intersect_rows(ti, rows)
    return _index_uids_for_rows(ti, rows)


def _regexp_func(pd: PredData, schema, pattern: str, flags: str) -> np.ndarray:
    """Trigram-index candidates + exact automaton post-filter
    (reference worker/task.go:768-835, worker/trigram.go:36)."""
    ti = pd.indexes.get("trigram")
    if ti is None:
        empty = _empty_or_missing_index(pd, schema, "trigram")
        if empty is not None:
            return empty
        raise TaskError(f"predicate {pd.attr} needs @index(trigram)")
    rx = remod.compile(pattern, remod.IGNORECASE if "i" in flags else 0)
    # candidate trigrams: any literal 3-gram required by the pattern; fall
    # back to scanning every indexed uid when the pattern has no required
    # per-branch OR-of-AND trigram query (worker/trigram.go:36 + codesearch
    # index/regexp): candidates = union over alternation branches of the
    # intersection of each required trigram's uid list. Case-insensitive
    # patterns probe each trigram's 2^3 case variants (the index stores
    # raw-case trigrams) — case-folded query expansion, not a full scan.
    plan = _trigram_plan(pattern)
    # inline ignorecase ((?i) / (?i:...)) is invisible to the literal
    # analysis — the trigrams come out exact-case, so the probe must
    # case-expand exactly as for /re/i. Substring detection over-matches
    # (e.g. an escaped paren) only toward a WIDER probe — always sound.
    ci = "i" in flags or "(?i" in pattern
    if plan is not None:
        cands = None
        for tris in plan:
            branch = None
            for t in tris:
                if ci:
                    rows = [r for v in _case_variants(t)
                            if (r := ti.term_row(v.encode())) >= 0]
                else:
                    r0 = ti.term_row(t.encode())
                    rows = [r0] if r0 >= 0 else []
                uids = _index_uids_for_rows(ti, rows)
                branch = uids if branch is None \
                    else us.intersect_host(branch, uids)
                if not len(branch):
                    break
            if branch is not None and len(branch):
                cands = branch if cands is None \
                    else np.union1d(cands, branch)
        if cands is None:
            cands = np.zeros(0, np.int64)
    else:
        nrows = max(len(ti.terms), 0)
        cands = _index_uids_for_rows(ti, list(range(nrows)))
    keep = []
    for u in cands.tolist():
        if any(rx.search(str(v.value)) for v in _stored_values(pd, int(u))):
            keep.append(u)
    return np.asarray(keep, dtype=np.int64)


def _case_variants(tri: str) -> list[str]:
    """All case spellings of one trigram (8 for pure-alpha)."""
    out = [""]
    for c in tri:
        if c.lower() != c.upper():
            out = [p + v for p in out for v in (c.lower(), c.upper())]
        else:
            out = [p + c for p in out]
    return out


_MAX_PLAN_ALTS = 16     # alternation product cap (planner bail-out)


def _lit_alternatives(seq) -> list[list[str]] | None:
    """Required-literal analysis of a parsed regex sequence (simplified
    codesearch index/regexp, the planner behind worker/trigram.go:36).

    Returns a list of alternatives — ANY match satisfies at least one — and
    for each alternative the list of literal runs EVERY match of it must
    contain. Soundness rules: constructs we don't model (classes, anchors,
    backrefs, min==0 repeats) contribute nothing and break the current run;
    group/repeat boundaries also break runs (never concatenate across them,
    "ab+c" must not claim "abc"). None = give up (caller scans)."""
    alts: list[list[str]] = [[""]]      # per alternative: runs; last is open

    def brk(a):
        if a[-1] != "":
            a.append("")

    def product(sub_alts):
        nonlocal alts
        if sub_alts is None:
            return False
        if len(alts) * len(sub_alts) > _MAX_PLAN_ALTS:
            return False
        out = []
        for a in alts:
            base = a if a[-1] == "" else a + [""]
            for s in sub_alts:
                out.append(base + [r for r in s if r] + [""])
            # the empty-run padding keeps sub-runs from concatenating
        alts = out
        return True

    for op, av in seq:
        name = str(op)
        if name == "LITERAL":
            ch = chr(av)
            for a in alts:
                a[-1] += ch
        elif name == "SUBPATTERN":
            sub = av[3]
            if not product(_lit_alternatives(sub)):
                return None
        elif name == "BRANCH":
            branches = av[1]
            sub_alts: list[list[str]] = []
            for b in branches:
                r = _lit_alternatives(b)
                if r is None:
                    return None
                sub_alts.extend(r)
            if not product(sub_alts):
                return None
        elif name in ("MAX_REPEAT", "MIN_REPEAT"):
            mn, _mx, sub = av
            if mn >= 1:
                # at least one occurrence is required
                if not product(_lit_alternatives(sub)):
                    return None
            else:
                for a in alts:
                    brk(a)
        else:
            # IN / ANY / AT / CATEGORY / GROUPREF / ...: matches something
            # we don't track — requireds on either side still hold
            for a in alts:
                brk(a)
    return [[r for r in a if r] for a in alts]


def _trigram_plan(pattern: str) -> list[list[str]] | None:
    """OR-of-AND trigram query for a pattern: one AND-list per alternation
    branch (candidates = union over branches of the intersection of each
    trigram's uid list). None = no branch has a literal >= 3 chars, or the
    pattern is beyond the planner — caller falls back to the full scan."""
    try:
        parsed = list(remod._parser.parse(pattern))
    except Exception:
        return None
    alts = _lit_alternatives(parsed)
    if alts is None:
        return None
    plan = []
    for runs in alts:
        tris = sorted({run[i: i + 3] for run in runs if len(run) >= 3
                       for i in range(len(run) - 2)})
        if not tris:
            return None     # one unbounded branch poisons the whole query
        plan.append(tris)
    return plan


def _geo_func(pd: PredData, schema, fname: str, args: list) -> np.ndarray:
    ti = pd.indexes.get("geo")
    if ti is None:
        empty = _empty_or_missing_index(pd, schema, "geo")
        if empty is not None:
            return empty
        raise TaskError(f"predicate {pd.attr} needs @index(geo)")
    a0 = args[0]
    if isinstance(a0, (list, tuple)) and len(a0) == 2 and \
            all(isinstance(x, (int, float)) for x in a0):
        # DQL coordinate form: near(loc, [lon, lat], dist)
        a0 = {"type": "Point", "coordinates": [float(a0[0]), float(a0[1])]}
    g = a0 if isinstance(a0, geomod.Geom) else geomod.parse_geojson(a0)
    radius = float(args[1]) if fname == "near" and len(args) > 1 else None
    qtoks = geomod.query_tokens(g, radius)
    # probe covers and all their indexed ancestors/descendants
    rows = set()
    for t in qtoks:
        for p in range(geomod.MIN_PRECISION, len(t) + 1):
            r = ti.term_row(t[:p].encode())
            if r >= 0:
                rows.add(r)
        # descendants: terms with prefix t
        i = bisect.bisect_left(ti.terms, t.encode())
        while i < len(ti.terms) and ti.terms[i].startswith(t.encode()):
            rows.add(i)
            i += 1
    cands = _index_uids_for_rows(ti, sorted(rows))
    keep = []
    for u in cands.tolist():
        for sv in _stored_values(pd, int(u)):
            stored = sv.value
            ok = {"near": lambda: geomod.near(stored, g.coords if g.kind == "Point" else next(iter(g.points())), radius or 0.0),
                  "within": lambda: geomod.within(stored, g),
                  "contains": lambda: geomod.contains(stored, g),
                  "intersects": lambda: geomod.intersects(stored, g)}[fname]()
            if ok:
                keep.append(u)
                break
    return np.asarray(keep, dtype=np.int64)
