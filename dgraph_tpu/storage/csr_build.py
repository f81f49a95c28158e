"""Build immutable device snapshots: posting store → HBM-resident CSR graphs.

This is the load-bearing TPU redesign (SURVEY.md §7): the reference reads
posting lists one (predicate, uid) at a time through an LRU over badger
(posting/lists.go Get → mvcc.ReadPostingList), merging the mutable layer on
every read. Here a *snapshot at read_ts* is folded once into flat arrays and
uploaded; the device then serves every read of that epoch with zero host
round-trips:

  - uid predicates      → forward CSR (subjects / indptr / indices) and, for
                          @reverse predicates, a reverse CSR
                          (ReverseKey tablets, posting/index.go:190).
  - indexed predicates  → per-tokenizer token→uid CSR. The host keeps the
                          sorted term list; inequality functions binary-search
                          it and the device unions the chosen token rows
                          (worker/tokens.go:124 getInequalityTokens redesigned
                          as an expand over token rows).
  - value predicates    → host-side exact {uid: Val} map (post-filters,
                          output encoding) plus a best-effort numeric mirror
                          aligned to value_subjects for device aggregation.
  - count index         → implicit: degree = indptr[i+1]-indptr[i] on device
                          (CountKey tablets exist host-side for exactness).

Snapshot isolation falls out naturally: a snapshot is just read_ts plus
immutable arrays; concurrent txns keep writing to the store and later epochs
build new snapshots (posting/mvcc.go's readTs gating, without device MVCC).
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from typing import Iterable

import numpy as np
import jax.numpy as jnp

from dgraph_tpu.storage import keys as K
from dgraph_tpu.storage.postings import VALUE_UID, PostingList
from dgraph_tpu.storage.store import Store
from dgraph_tpu.utils.types import TypeID, Val, to_device_scalar

MAX_DEVICE_UID = 2**31 - 2  # int32 space, sentinel-exclusive


class PredCSR:
    """Adjacency of one predicate: row r = subjects[r] → indices[indptr[r]:indptr[r+1]].

    Residency refactor (storage/residency.py): the HOST numpy columns are
    the authoritative fold; the device columns are a droppable cache that
    uploads lazily on first kernel access and — when a ResidencyManager
    is attached at fold time — admits against the node's device-byte
    budget (evicting colder tablets) and can be demoted back to the warm
    host tier without touching this object's identity. Identity stability
    is the contract qcache per-predicate tokens, the DeviceBatcher's
    same-CSR-object rule, and mesh placement caches all rely on."""

    # residency owner protocol (set by ResidencyManager.adopt_pred)
    _res = None
    _res_attr = ""
    _res_kind = "csr"

    def __init__(self, subjects, indptr, indices) -> None:
        self._subjects_h = np.asarray(subjects)   # int32[N] sorted
        self._indptr_h = np.asarray(indptr)       # int32[N+1]
        self._indices_h = np.asarray(indices)     # int32[E] sorted per row
        self._dev: tuple | None = None            # droppable device cache
        self._max_degree: int | None = None       # lazy per-snapshot const

    @property
    def num_subjects(self) -> int:
        return int(self._subjects_h.shape[0])

    @property
    def num_edges(self) -> int:
        return int(self._indices_h.shape[0])

    # -- device tier ----------------------------------------------------------

    def device_arrays(self, prefetch: bool = False) -> tuple:
        """(subjects, indptr, indices) on device — the HBM tier. Uploads
        on first access through the residency seam (budget admission +
        the residency.h2d_upload fault point) when managed."""
        from dgraph_tpu.storage import residency as resmod

        return resmod.ensure_device(
            self, "_dev",
            lambda: (jnp.asarray(self._subjects_h),
                     jnp.asarray(self._indptr_h),
                     jnp.asarray(self._indices_h)),
            prefetch=prefetch)

    @property
    def subjects(self):
        return self.device_arrays()[0]

    @property
    def indptr(self):
        return self.device_arrays()[1]

    @property
    def indices(self):
        return self.device_arrays()[2]

    def device_resident(self) -> bool:
        return self._dev is not None

    def drop_device(self) -> None:
        """Demote to the warm tier: free the device buffers, keep the
        host fold. Kernels mid-flight keep their array references alive;
        the next device access re-uploads byte-identical columns."""
        self._dev = None

    def device_nbytes(self) -> int:
        return int(self._subjects_h.nbytes + self._indptr_h.nbytes
                   + self._indices_h.nbytes)

    def host_nbytes(self) -> int:
        return self.device_nbytes()

    def prefer_host(self) -> bool:
        """Tier consult for the query layer: True = COLD (footprint
        exceeds the whole device budget) — serve via the host-cutover
        machinery instead of uploading."""
        from dgraph_tpu.storage import residency as resmod

        return resmod.prefer_host(self)

    # -- host tier ------------------------------------------------------------

    def host_arrays(self) -> tuple:
        """(subjects, indptr, indices) as numpy — the warm-tier truth:
        frontier→row mapping, degree counting, and recurse edge-dedup run
        per expand and never touch the device."""
        return (self._subjects_h, self._indptr_h, self._indices_h)

    def max_degree(self) -> int:
        """Largest row length — cached: capacity sizing (the fused ANN
        pipeline's ecap) runs per query and must not rescan indptr."""
        if self._max_degree is None:
            ptr = self._indptr_h
            self._max_degree = int(np.max(ptr[1:] - ptr[:-1])) \
                if len(ptr) > 1 else 0
        return self._max_degree


class TokenIndex:
    """token→uid CSR for one (predicate, tokenizer). Same host-truth +
    droppable-device-cache shape as PredCSR (the residency tiers)."""

    _res = None
    _res_attr = ""
    _res_kind = "index"

    def __init__(self, terms: list[bytes], indptr, uids) -> None:
        self.terms = terms      # sorted; host-side (binary-searched)
        self._indptr_h = np.asarray(indptr)   # int32[T+1]
        self._uids_h = np.asarray(uids)       # int32[sum lens], sorted/row
        self._dev: tuple | None = None
        self._host: tuple | None = None       # lazy (indptr, uids64)

    def term_row(self, term: bytes) -> int:
        import bisect

        i = bisect.bisect_left(self.terms, term)
        return i if i < len(self.terms) and self.terms[i] == term else -1

    def device_arrays(self, prefetch: bool = False) -> tuple:
        from dgraph_tpu.storage import residency as resmod

        return resmod.ensure_device(
            self, "_dev",
            lambda: (jnp.asarray(self._indptr_h),
                     jnp.asarray(self._uids_h)),
            prefetch=prefetch)

    @property
    def indptr(self):
        return self.device_arrays()[0]

    @property
    def uids(self):
        return self.device_arrays()[1]

    def device_resident(self) -> bool:
        return self._dev is not None

    def drop_device(self) -> None:
        self._dev = None

    def device_nbytes(self) -> int:
        return int(self._indptr_h.nbytes + self._uids_h.nbytes)

    def host_nbytes(self) -> int:
        return self.device_nbytes()

    def prefer_host(self) -> bool:
        from dgraph_tpu.storage import residency as resmod

        return resmod.prefer_host(self)

    def host_arrays(self) -> tuple[np.ndarray, np.ndarray]:
        """(indptr, uids int64) host mirrors (index sorts / bucket walks
        are host-orchestrated and never touch the device)."""
        if self._host is None:
            self._host = (self._indptr_h,
                          self._uids_h.astype(np.int64))
        return self._host


@dataclass
class PredData:
    attr: str
    type_id: TypeID
    csr: PredCSR | None = None
    rev_csr: PredCSR | None = None
    value_subjects: jnp.ndarray | None = None    # int32[N] sorted uids with a value
    value_subjects_host: np.ndarray | None = None  # int64[N] host mirror (searches)
    num_values: jnp.ndarray | None = None        # float32[N] numeric mirror (NaN=non-numeric)
    num_values_host: np.ndarray | None = None    # float64[N] exact mirror (compares)
    host_values: dict[int, Val] = field(default_factory=dict)
    # [type] list predicates: every value per subject (host_values keeps the
    # first for single-value compare/sort paths)
    list_values: dict[int, list[Val]] = field(default_factory=dict)
    lang_values: dict[int, dict[str, Val]] = field(default_factory=dict)
    facets: dict[tuple[int, int], tuple] = field(default_factory=dict)  # (subj,obj/slot)->facets
    indexes: dict[str, TokenIndex] = field(default_factory=dict)
    # @index(vector) predicates: row-aligned embedding matrix + IVF
    # (storage/vecindex.VectorIndex, or VecOverlay when delta-stamped)
    vecindex: object | None = None

    def has_subjects(self) -> np.ndarray:
        """uids for has(attr): subjects with any edge or value (host
        mirrors — a device fetch per query would pay transfer latency for
        an array the host already holds)."""
        outs = []
        if self.csr is not None:
            sub_fn = getattr(self.csr, "subjects_host", None)
            if sub_fn is not None:
                # delta overlay (storage/delta.OverlayCSR): merged subjects
                # without forcing the full edge merge
                outs.append(sub_fn())
            elif hasattr(self.csr, "host_arrays"):
                outs.append(self.csr.host_arrays()[0])
            else:   # mesh-sharded tablet (DistPredCSR): device fetch
                outs.append(np.asarray(self.csr.subjects))
        if self.value_subjects_host is not None:
            outs.append(self.value_subjects_host)
        if not outs:
            return np.zeros(0, dtype=np.int32)
        return np.unique(np.concatenate(outs))


def _csr_from_rows(rows: list[tuple[int, np.ndarray]]) -> PredCSR | None:
    rows = [(s, o) for s, o in rows if len(o)]
    if not rows:
        return None
    rows.sort(key=lambda x: x[0])
    subjects = np.asarray([s for s, _ in rows], dtype=np.int64)
    if len(subjects) and subjects[-1] > MAX_DEVICE_UID:
        raise ValueError(f"uid {subjects[-1]} exceeds device uid space")
    lens = np.asarray([len(o) for _, o in rows], dtype=np.int64)
    indptr = np.zeros(len(rows) + 1, dtype=np.int32)
    np.cumsum(lens, out=indptr[1:])
    indices = np.concatenate([o for _, o in rows]).astype(np.int64)
    if len(indices) and indices.max() > MAX_DEVICE_UID:
        raise ValueError("object uid exceeds device uid space")
    return PredCSR(
        subjects.astype(np.int32),
        indptr,
        indices.astype(np.int32),
    )


def _token_index(rows: list[tuple[bytes, np.ndarray]]) -> TokenIndex:
    rows.sort(key=lambda x: x[0])
    terms = [t for t, _ in rows]
    lens = np.asarray([len(u) for _, u in rows], dtype=np.int64)
    indptr = np.zeros(len(rows) + 1, dtype=np.int32)
    if len(rows):
        np.cumsum(lens, out=indptr[1:])
        uids = np.concatenate([u for _, u in rows]).astype(np.int32)
    else:
        uids = np.zeros(0, dtype=np.int32)
    return TokenIndex(terms, indptr, uids)


class GraphSnapshot:
    """Immutable device-resident view of (a subset of) the graph at read_ts."""

    def __init__(self, read_ts: int) -> None:
        self.read_ts = read_ts
        self.preds: dict[str, PredData] = {}

    def pred(self, attr: str) -> PredData | None:
        return self.preds.get(attr)

    @property
    def nbytes(self) -> int:
        total = 0
        # memory accounting must never force folds: a lazy snapshot counts
        # only its materialized tablets (unfolded thunks hold no arrays)
        folded = getattr(self.preds, "folded_values", None)
        for pd in (folded() if folded is not None else self.preds.values()):
            for csr in (pd.csr, pd.rev_csr):
                if csr is not None:
                    est = getattr(csr, "approx_nbytes", None)
                    if est is not None:  # overlay: don't force a merge
                        total += est()
                        continue
                    hn = getattr(csr, "host_nbytes", None)
                    if hn is not None:   # host truth — never forces upload
                        total += hn()
                    else:                # mesh-sharded DistPredCSR
                        total += csr.subjects.nbytes + \
                            csr.indptr.nbytes + csr.indices.nbytes
            if pd.value_subjects is not None:
                total += pd.value_subjects.nbytes
            if pd.num_values is not None:
                total += pd.num_values.nbytes
            for ti in pd.indexes.values():
                hn = getattr(ti, "host_nbytes", None)
                total += hn() if hn is not None else \
                    (ti.indptr.nbytes + ti.uids.nbytes)
            if pd.vecindex is not None:
                total += pd.vecindex.nbytes()
        return total


# ---------------------------------------------------------------------------
# lazy on-demand snapshot folds (ISSUE 15)
# ---------------------------------------------------------------------------
#
# Eager assembly folded EVERY predicate at snapshot time — ~4 µs/list of
# Python on a CPU host, i.e. 13-20 s to the first query at 10M edges
# and minutes at LDBC-SNB SF10+. The scale-regime cold path instead
# registers unfolded tablets as fold-THUNKS: the first read of a predicate
# (task/engine seams via GraphSnapshot.pred / LazyPreds.get), a residency
# plan-driven prefetch (storage/residency.prefetch, overlapped through the
# shared fold pool), or an overlay-forced inline compaction triggers the
# fold, with singleflight per tablet so racing first readers share ONE
# fold. PredData identity is minted at first fold and then reused exactly
# like the eager path's, so qcache per-predicate tokens, the
# DeviceBatcher's same-CSR-object rule, and mesh placement caches behave
# identically — and the fold itself is byte-identical to eager assembly
# (same build_pred at the same effective read_ts).
#
# Consistency window (the one deliberate divergence from eager): an
# unresolved thunk folds against the LIVE store at its registration-time
# read_ts. Normal commits land above that ts and stay invisible — the
# fold is byte-identical to eager. The exceptions are the races the
# staleness machinery already polices: a predicate DROP resolves the
# pending tablet as empty (build_pred's dropped-mid-build contract —
# eager would have served the pre-drop fold), and a replication replay
# BELOW the watermark is included by a post-replay fold while tablets
# folded earlier excluded it; pred_replay_seq marks such snapshots stale
# and the next snapshot() call rebuilds, bounding the mixed view to
# queries already holding the snapshot — the same exposure the stamped
# eager cache accepts between _stale() checks.

# fold-trigger counters (pre-registered in utils/metrics.Registry; literal
# names so the analysis metric rule and the runtime audit both see them)
_FOLD_COUNTERS = {
    "lazy": "dgraph_fold_lazy_total",
    "eager": "dgraph_fold_eager_total",
    "prefetch": "dgraph_fold_prefetch_total",
    "inline": "dgraph_fold_inline_total",
}


def _note_fold(metrics, trigger: str, dt_ms: float | None) -> None:
    if metrics is None:
        return
    metrics.counter(_FOLD_COUNTERS.get(trigger,
                                       "dgraph_fold_lazy_total")).inc()
    if dt_ms is not None:
        metrics.histogram("dgraph_fold_ms").observe(dt_ms)


class _FoldThunk:
    """One unfolded tablet: fold-on-first-read with per-tablet
    singleflight. The claim lock is held only to elect a leader — the
    fold itself runs outside it (no nested lock acquisition, so
    lockdep-armed runs see no new edges). A failed fold propagates to the
    waiters of THAT attempt and resets leadership so a later read
    retries; a resolved thunk answers every subsequent caller (including
    LazyPreds copies sharing it) without re-folding."""

    __slots__ = ("attr", "eff", "pct", "seq", "inline", "fold",
                 "pd", "error", "_lock", "_event", "_claimed")

    def __init__(self, attr: str, eff: int, fold, pct: int = 0,
                 seq: int = 0, inline: bool = False) -> None:
        self.attr = attr
        self.eff = eff
        self.pct = pct
        self.seq = seq
        self.inline = inline      # fold forced by overlay depth/stamp miss
        self.fold = fold          # callable(thunk, trigger) -> PredData
        self.pd: PredData | None = None
        self.error: BaseException | None = None
        self._lock = threading.Lock()
        self._event = threading.Event()
        self._claimed = False

    def resolve(self, trigger: str = "lazy") -> PredData:
        pd = self.pd
        if pd is not None:
            return pd
        with self._lock:
            if self.pd is not None:
                return self.pd
            lead = not self._claimed
            if lead:
                self._claimed = True
            event = self._event
        if not lead:
            # racing first reader: share the leader's fold. Clamped to the
            # caller's own deadline budget (same contract as the task-cache
            # singleflight follower) — never an unbounded hang.
            from dgraph_tpu.utils import deadline as dl

            if not event.wait(dl.clamp(None)):
                dl.check("lazy fold follower")
                raise dl.DeadlineExceeded(
                    f"lazy fold of {self.attr} timed out")
            if self.pd is not None:
                return self.pd
            if self.error is not None:
                raise self.error
            return self.resolve(trigger)       # leader failed then reset
        try:
            pd = self.fold(self, "inline" if self.inline else trigger)
        except BaseException as e:
            with self._lock:
                self.error = e
                self._claimed = False
                self._event = threading.Event()
            event.set()
            raise
        self.pd = pd
        self.error = None
        event.set()
        return pd


class DelegateThunk:
    """Pass-through thunk: resolves another lazy map's entry (embedded
    Cluster assembly, mesh placement). The FOLD singleflight lives in the
    underlying map's own thunk; the claim lock here serializes the `wrap`
    transform too — racing first readers must receive ONE placed identity
    (and pay one sharding/upload), not two."""

    __slots__ = ("src", "attr", "wrap", "pd", "_lock")

    def __init__(self, src, attr: str, wrap=None) -> None:
        self.src = src
        self.attr = attr
        self.wrap = wrap          # optional post-fold transform (placement)
        self.pd = None
        self._lock = threading.Lock()

    def resolve(self, trigger: str = "lazy"):
        if self.pd is not None:
            return self.pd
        with self._lock:
            if self.pd is None:
                pd = self.src.get(self.attr)
                if pd is not None and self.wrap is not None:
                    pd = self.wrap(pd)
                if pd is None:
                    return None
                self.pd = pd
        return self.pd


class LazyPreds(dict):
    """attr → PredData where unfolded tablets are fold-thunks.

    The dict storage holds FOLDED entries only; `_thunks` holds the
    pending tablets. Key views (len / contains / iter / keys) see the
    union WITHOUT folding; `get`/`[]` fold exactly the requested tablet
    (the demand-driven seam every query path reads through); `values()` /
    `items()` materialize everything first — callers that genuinely need
    the whole world (mesh re-sharding, expand() known-uid validation)
    keep eager semantics, in parallel through the shared fold pool.
    Mutation (`[k] = v`, `update`) drops any shadowed thunk: an explicit
    entry (txn overlay, placed tablet) always wins."""

    __slots__ = ("_thunks", "hint_fn", "on_resolve")

    def __init__(self) -> None:
        super().__init__()
        self._thunks: dict[str, object] = {}
        self.hint_fn = None       # callable(attr) -> cardinality estimate
        self.on_resolve = None    # callback(attr, pd) per materialization

    # -- registration ---------------------------------------------------------

    def register(self, attr: str, thunk) -> None:
        if not dict.__contains__(self, attr):
            self._thunks[attr] = thunk

    # -- resolution -----------------------------------------------------------

    def resolve(self, attr: str, trigger: str = "lazy"):
        """Fold one pending tablet (or return the folded entry)."""
        pd = dict.get(self, attr)
        if pd is not None:
            return pd
        th = self._thunks.get(attr)
        if th is None:
            return dict.get(self, attr)   # raced another resolver
        pd = th.resolve(trigger)
        if pd is None:                    # delegate over an absent tablet
            self._thunks.pop(attr, None)
            return None
        dict.__setitem__(self, attr, pd)
        self._thunks.pop(attr, None)
        cb = self.on_resolve
        if cb is not None:
            try:
                cb(attr, pd)
            except Exception:
                pass          # gauges/bookkeeping must never fail a read
        return pd

    def materialize_all(self, trigger: str = "eager") -> int:
        """Fold every pending tablet, in parallel through the shared fold
        pool. Distinct attrs have distinct thunks and each pool task waits
        only on a leader that is already RUNNING (claims happen inside
        resolve), so pool-width saturation cannot deadlock."""
        pending = [a for a in list(self._thunks)
                   if not dict.__contains__(self, a)]
        if not pending:
            return 0
        if len(pending) > 1:
            from concurrent.futures import TimeoutError as _FutTimeout

            from dgraph_tpu.utils import deadline as dl

            pool = _fold_pool()
            # dgraph: allow(ctxvar-copy) folds build SHARED snapshot
            # state cached across requests — they must not inherit any
            # one request's deadline/trace context
            futs = [pool.submit(self.resolve, a, trigger) for a in pending]
            for f in futs:
                try:
                    # clamped to the CALLER's budget: a timed-out request
                    # raises typed instead of waiting out the whole fold
                    # wall; the pool keeps folding for the next reader
                    f.result(timeout=dl.clamp(None))
                except _FutTimeout:
                    dl.check("materialize_all fold")
                    raise dl.DeadlineExceeded(
                        "materialize-all folds timed out")
        else:
            self.resolve(pending[0], trigger)
        return len(pending)

    # -- mapping protocol -----------------------------------------------------

    def __getitem__(self, attr):
        pd = dict.get(self, attr)
        if pd is not None:
            return pd
        if attr in self._thunks:
            pd = self.resolve(attr)
            if pd is not None:
                return pd
        raise KeyError(attr)

    def get(self, attr, default=None):
        pd = dict.get(self, attr)
        if pd is not None:
            return pd
        if attr in self._thunks:
            pd = self.resolve(attr)
            if pd is not None:
                return pd
        return default

    def __contains__(self, attr) -> bool:
        return dict.__contains__(self, attr) or attr in self._thunks

    def __len__(self) -> int:
        return len(set(dict.keys(self)) | set(self._thunks))

    def __iter__(self):
        return iter(sorted(set(dict.keys(self)) | set(self._thunks)))

    def keys(self):
        return sorted(set(dict.keys(self)) | set(self._thunks))

    def values(self):
        # sorted-key order: eager assembly inserted in sorted
        # store.predicates() order, while on-demand resolution inserts in
        # completion order — iteration must stay deterministic (tablet
        # routing assigns groups in iteration order)
        self.materialize_all()
        return [v for _k, v in sorted(dict.items(self))]

    def items(self):
        self.materialize_all()
        return sorted(dict.items(self))

    def __setitem__(self, attr, pd) -> None:
        self._thunks.pop(attr, None)
        dict.__setitem__(self, attr, pd)

    def update(self, other=(), **kw) -> None:
        d = dict(other, **kw)
        for k in d:
            self._thunks.pop(k, None)
        dict.update(self, d)

    # -- lazy-aware views (planner / stats / residency / memory) --------------

    def folded_get(self, attr, default=None):
        """Folded entry or default — NEVER resolves a thunk (identity
        probes like compact()'s pinned-view scan must not fold)."""
        return dict.get(self, attr, default)

    def folded_items(self):
        return list(dict.items(self))

    def folded_values(self):
        return list(dict.values(self))

    def pending_attrs(self) -> list[str]:
        return [a for a in list(self._thunks)
                if not dict.__contains__(self, a)]

    def is_pending(self, attr: str) -> bool:
        return attr in self._thunks and not dict.__contains__(self, attr)

    def pending_card(self, attr: str) -> int:
        """Cardinality ESTIMATE for an unfolded tablet (planner universe
        normalization — order decisions only, never results)."""
        fn = self.hint_fn
        if fn is None:
            return 0
        try:
            return int(fn(attr))
        except Exception:
            return 0

    def lazy_copy(self) -> "LazyPreds":
        """Folded entries copied, pending thunks SHARED — the txn
        read-view copy (api/server._read_view). A fold through either
        map resolves the one shared thunk; `dict(base.preds)` would
        silently drop the pending tablets via the CPython dict fast
        path, which is why that call site uses this instead."""
        out = LazyPreds()
        dict.update(out, self)
        out._thunks = dict(self._thunks)
        out.hint_fn = self.hint_fn
        out.on_resolve = self.on_resolve
        return out


_UNPACK_CHUNK = 16384   # lists decoded per vectorized unpack_many call


def _tablet_uids(store: Store, kbs: list[bytes], read_ts: int,
                 own: int | None,
                 pls: list | None = None) -> list[np.ndarray]:
    """uids() for every key of a tablet, batching pure-base lists through one
    vectorized decode (packed.unpack_many) — per-list numpy overhead
    dominates a 100k-list snapshot build otherwise."""
    # .get: a predicate dropped mid-build (follower live-apply) reads as
    # empty rather than KeyError; the reader's version bump rebuilds after
    if pls is None:
        pls = [store.lists.get(kb) for kb in kbs]
    pls = [pl if pl is not None else PostingList() for pl in pls]
    out: list[np.ndarray | None] = [None] * len(pls)
    batch_idx: list[int] = []
    for i, pl in enumerate(pls):
        if pl._base_only(read_ts, own):
            batch_idx.append(i)
        else:
            out[i] = pl.uids(read_ts, own_start_ts=own)
    for lo in range(0, len(batch_idx), _UNPACK_CHUNK):
        part = batch_idx[lo : lo + _UNPACK_CHUNK]
        from dgraph_tpu.storage import native

        for i, u in zip(part, native.unpack_many(
                [pls[i].base_packed for i in part])):
            out[i] = u.astype(np.int64)
    return out


def _uids_of_keys(kbs: list[bytes]) -> np.ndarray:
    """Vectorized K.uid_of over a tablet's DATA/REVERSE keys (all the same
    length for one attr: kind + u32 len + attr + u64 uid, big-endian)."""
    n = len(kbs)
    if n == 0:
        return np.zeros(0, np.int64)
    buf = b"".join(kbs)
    L = len(kbs[0])
    arr = np.frombuffer(buf, dtype=np.uint8).reshape(n, L)
    return np.ascontiguousarray(arr[:, -8:]).view(">u8").ravel().astype(
        np.int64)


def _csr_from_flat(subjects: np.ndarray, counts: np.ndarray,
                   indices: np.ndarray) -> PredCSR:
    """Assemble a PredCSR from flat arrays, dropping empty rows."""
    keep = counts > 0
    subjects_k = subjects[keep]
    if len(subjects_k) and subjects_k[-1] > MAX_DEVICE_UID:
        raise ValueError(f"uid {subjects_k[-1]} exceeds device uid space")
    if len(indices) and indices.max() > MAX_DEVICE_UID:
        raise ValueError("object uid exceeds device uid space")
    indptr = np.zeros(int(keep.sum()) + 1, dtype=np.int32)
    np.cumsum(counts[keep], out=indptr[1:])
    return PredCSR(
        subjects_k.astype(np.int32),
        indptr,
        indices.astype(np.int32),
    )


def _fold_uid_tablet(store: Store, kbs: list[bytes], read_ts: int,
                     own: int | None, pd: PredData | None,
                     kind: int = int(K.KeyKind.DATA)) -> PredCSR | None:
    """Flat fold of a uid-edge tablet (the 10M-scale hot path): one
    vectorized key parse, one batched native decode into a single flat
    index array, bulk span copies — no per-key numpy slicing and no
    100k-array np.concatenate (reference predicate.go:84-176 streams a
    shard build the same way: key-ordered, single pass).

    pd: facet capture target for lists with live postings (None for
    reverse tablets — the forward fold owns facets)."""
    from dgraph_tpu.storage import native

    N = len(kbs)
    if N == 0:
        return None

    # COLD-OPEN FAST PATH: the snapshot loader captured this tablet's
    # packed columns contiguously (store.TabletPacked; entry survives only
    # while untouched by writes) — decode every list in ONE native call,
    # zero per-list Python. This is the >=10x lever at 10M-edge scale.
    attr = K.kind_attr_of(kbs[0])[1]
    tp = store.packed_tablet(kind, attr)
    if tp is not None and tp.pure and tp.n == N:
        if read_ts < tp.max_base_ts:
            raise ValueError(
                f"read at ts {read_ts} below rollup watermark "
                f"{tp.max_base_ts}")
        flat = native.unpack_columns(tp, int(tp.counts.sum()))
        if flat is not None:
            return _csr_from_flat(_uids_of_keys(kbs), tp.counts,
                                  flat.view(np.int64))

    pls = [store.lists.get(kb) for kb in kbs]
    subjects = _uids_of_keys(kbs)      # keys_of is sorted → ascending
    max_bts = max((pl.base_ts for pl in pls if pl is not None), default=0)
    if read_ts < max_bts:
        # same isolation guard the per-list path enforces
        # (PostingList._base_only): a rollup above read_ts folded
        # later commits into the base — this read cannot be served
        raise ValueError(
            f"read at ts {read_ts} below rollup watermark {max_bts}")
    pure = np.fromiter(
        ((pl is not None and not pl.layers and not pl.uncommitted
          and not pl.base_postings) for pl in pls), bool, N)
    comp_rows: dict[int, np.ndarray] = {}
    for i in np.flatnonzero(~pure).tolist():
        pl = pls[i]
        if pl is None:                 # dropped mid-build: reads as empty
            comp_rows[i] = np.zeros(0, np.int64)
            continue
        comp_rows[i] = pl.uids(read_ts, own_start_ts=own)
        if pd is not None:
            live = pl.live_map(read_ts, own_start_ts=own)
            subj = int(subjects[i])
            for p in live.values():
                if p.facets:
                    pd.facets[(subj, p.uid)] = p.facets
    pure_idx = np.flatnonzero(pure)
    flat, counts_pure = native.unpack_many_flat(
        [pls[i].base_packed for i in pure_idx.tolist()])
    counts = np.zeros(N, np.int64)
    counts[pure] = counts_pure
    for i, u in comp_rows.items():
        counts[i] = len(u)
    total = int(counts.sum())
    if total == 0:
        return None
    offs = np.zeros(N + 1, np.int64)
    np.cumsum(counts, out=offs[1:])
    indices = np.empty(total, np.int64)
    if not comp_rows:
        indices[:] = flat              # single bulk copy (casts u64→i64)
    else:
        pure_off = np.zeros(len(pure_idx) + 1, np.int64)
        np.cumsum(counts_pure, out=pure_off[1:])
        # consecutive pure keys form runs → one span copy per run
        starts = np.concatenate(
            [[0], np.flatnonzero(np.diff(pure_idx) != 1) + 1])
        ends = np.concatenate([starts[1:], [len(pure_idx)]])
        for j0, j1 in zip(starts.tolist(), ends.tolist()):
            if j0 == j1:
                continue
            i0, i_last = int(pure_idx[j0]), int(pure_idx[j1 - 1])
            indices[offs[i0]: offs[i_last + 1]] = \
                flat[pure_off[j0]: pure_off[j1]]
        for i, u in comp_rows.items():
            indices[offs[i]: offs[i + 1]] = u
    return _csr_from_flat(subjects, counts, indices)


def _fold_value_subject(pd: PredData, entry, tid: TypeID, subj: int, pl,
                        read_ts: int, own: int | None) -> tuple[bool, float | None]:
    """Per-subject value/facet fold — the ONE implementation shared by
    build_pred and the delta-overlay stamp (storage/delta.py), so a stamped
    entry is byte-identical to a full fold at the same read_ts.

    Mutates pd's value/facet dicts; returns (is_edge_row, num_mirror):
    is_edge_row means the subject's uids belong in the CSR (uid-typed, or
    DEFAULT with no value postings); num_mirror is the subject's
    value_subjects numeric-mirror entry (None = no entry)."""
    live = pl.live_map(read_ts, own_start_ts=own)
    # type heuristic for untyped predicates probes ANY value ("." tag);
    # host_values below still reads only the untagged slot
    has_value = any(p.value is not None for p in live.values())
    if tid == TypeID.UID or (tid == TypeID.DEFAULT and not has_value):
        for p in live.values():
            if p.facets:
                pd.facets[(subj, p.uid)] = p.facets
        return True, None
    p0 = live.get(VALUE_UID)
    v = p0.value if p0 is not None else None
    if v is None and entry is not None and entry.is_list:
        # [type] list predicate: values live at fingerprint slots;
        # surface the whole list plus the first as the compare/sort
        # representative
        lv = sorted((p.value for p in live.values()
                     if p.value is not None and not p.lang),
                    key=lambda x: str(x.value))
        if lv:
            pd.list_values[subj] = lv
            v = lv[0]
    num: float | None = None
    if v is not None:
        pd.host_values[subj] = v
        s = to_device_scalar(v)
        num = np.nan if s is None else float(s)
    # language-tagged values
    had_lang = False
    for p in live.values():
        if p.value is not None and p.lang:
            pd.lang_values.setdefault(subj, {})[p.lang] = p.value
            had_lang = True
        if p.facets:
            pd.facets[(subj, p.uid)] = p.facets
    if v is None and had_lang:
        # lang-only node: still a has(attr) subject (the reference's
        # data key exists), but carries no untagged value
        num = np.nan
    return False, num


def build_pred(store: Store, attr: str, read_ts: int,
               own_start_ts: int | None = None) -> PredData:
    """Fold one predicate's tablets at read_ts into a PredData.

    own_start_ts: when set, the caller's open txn's uncommitted layers are
    visible too (posting/list.go:528 — postings with StartTs == readTs are
    visible to their own txn). Such views must not be cached.
    """
    entry = store.schema.get(attr)
    tid = entry.type_id if entry else TypeID.DEFAULT
    pd = PredData(attr, tid)

    fwd_rows: list[tuple[int, np.ndarray]] = []
    val_subjects: list[int] = []
    num_vals: list[float] = []
    own = own_start_ts
    kbs = store.keys_of(K.KeyKind.DATA, attr)
    uid_typed = tid == TypeID.UID
    if uid_typed:
        # flat fold: no per-key loop at all for declared-uid predicates
        pd.csr = _fold_uid_tablet(store, kbs, read_ts, own, pd,
                                  kind=int(K.KeyKind.DATA))
        kbs = []
    tablet_pls = store.tablet_lists(int(K.KeyKind.DATA), attr, kbs)
    tablet_uids = _tablet_uids(store, kbs, read_ts, own, pls=tablet_pls)
    for kb, u, pl in zip(kbs, tablet_uids, tablet_pls):
        subj = K.uid_of(kb)        # DATA key: partial parse, hot loop
        if pl is None:             # predicate dropped mid-build (follower
            continue               # live-apply); version bump rebuilds
        if uid_typed and not pl.layers and not pl.uncommitted \
                and not pl.base_postings:
            # post-bulk fast path: a pure packed uid list carries no
            # values/facets — skip the live_map fold entirely (unlocked
            # peek is safe: a layer landing mid-check commits ABOVE this
            # snapshot's ts and is invisible to it anyway; replayed
            # below-watermark commits invalidate via pred_replay_seq)
            if len(u):
                fwd_rows.append((subj, u))
            continue
        is_edge, num = _fold_value_subject(pd, entry, tid, subj, pl,
                                           read_ts, own)
        if is_edge:
            if len(u):
                fwd_rows.append((subj, u))
        elif num is not None:
            val_subjects.append(subj)
            num_vals.append(num)
    if fwd_rows:                  # non-uid-typed heuristic edges only
        pd.csr = _csr_from_rows(fwd_rows)
    if val_subjects:
        order = np.argsort(np.asarray(val_subjects, dtype=np.int64))
        vs = np.asarray(val_subjects, dtype=np.int64)[order]
        if vs[-1] > MAX_DEVICE_UID:
            raise ValueError("value subject uid exceeds device uid space")
        pd.value_subjects_host = vs
        # the narrow value-table mirrors are host-resident: nothing reads
        # them on device (compares run on the float64 host mirror), so
        # eagerly uploading them only burned HBM the residency budget now
        # accounts for
        pd.value_subjects = vs.astype(np.int32)
        pd.num_values_host = np.asarray(num_vals, dtype=np.float64)[order]
        pd.num_values = pd.num_values_host.astype(np.float32)

    # reverse CSR (flat fold; facets belong to the forward tablet)
    if entry is not None and entry.reverse:
        rkbs = store.keys_of(K.KeyKind.REVERSE, attr)
        pd.rev_csr = _fold_uid_tablet(store, rkbs, read_ts, own, None,
                                      kind=int(K.KeyKind.REVERSE))

    # vector index: fold the predicate's embeddings into the row-aligned
    # device matrix (+ IVF coarse quantizer past the size threshold)
    if entry is not None and entry.vector is not None:
        from dgraph_tpu.storage import vecindex as vecmod

        pd.vecindex = vecmod.build_vecindex(
            attr, entry.vector, pd.host_values,
            knobs=getattr(store, "vector_knobs", None))

    # token indexes, split per tokenizer by the 1-byte term prefix
    if entry is not None and entry.indexed:
        from dgraph_tpu.utils import tok as tokmod

        by_tok: dict[str, list[tuple[bytes, np.ndarray]]] = {
            name: [] for name in entry.tokenizers}
        ident_to_name = {tokmod.get(n).ident: n for n in entry.tokenizers}
        ikbs = store.keys_of(K.KeyKind.INDEX, attr)
        ipls = store.tablet_lists(int(K.KeyKind.INDEX), attr, ikbs)
        for kb, u in zip(ikbs, _tablet_uids(store, ikbs, read_ts, own,
                                            pls=ipls)):
            key = K.parse_key(kb)
            if not key.term or not len(u):
                continue
            name = ident_to_name.get(key.term[0])
            if name is None:
                continue
            by_tok[name].append((key.term[1:], u))
        for name, rows in by_tok.items():
            pd.indexes[name] = _token_index(rows)

    # residency adoption: when the owning node runs a device working-set
    # manager (storage/residency.py), every device-buffer owner of this
    # fold admits against the node's budget and is demotable/evictable
    mgr = getattr(store, "residency", None)
    if mgr is not None:
        mgr.adopt_pred(pd)
    return pd


_FOLD_POOL = None
_FOLD_POOL_LOCK = __import__("threading").Lock()


def default_fold_workers() -> int:
    import os

    return max(1, min(8, (os.cpu_count() or 2) - 1))


def _fold_pool():
    """ONE process-wide fixed-width thread pool for parallel tablet folds
    (never resized or shut down — replacing a live pool would race other
    assemblers' submits). Per-predicate folds are independent reads (the
    same unlocked reads the serial path does under the owning node's lock)
    and mostly numpy/native work that releases the GIL, so a cold
    multi-predicate snapshot builds in ~max(tablet) instead of
    sum(tablet). Callers wanting fewer concurrent folds cap via a
    semaphore in _fold_attrs."""
    global _FOLD_POOL
    from concurrent.futures import ThreadPoolExecutor

    with _FOLD_POOL_LOCK:
        if _FOLD_POOL is None:
            _FOLD_POOL = ThreadPoolExecutor(
                max_workers=default_fold_workers(),
                thread_name_prefix="dgt-fold")
        return _FOLD_POOL


def _fold_attrs(store: Store, attrs: list[str], read_ts: int,
                own_start_ts: int | None, workers: int,
                metrics=None) -> list[PredData]:
    """build_pred over many attrs, through the fold pool when it pays;
    `workers` caps this call's concurrency without resizing the pool."""
    def one(a):
        t0 = time.perf_counter()
        pd = build_pred(store, a, read_ts, own_start_ts)
        # per COMPLETED fold, wall observed on dgraph_fold_ms — the same
        # accounting every lazy/prefetch/inline trigger gets
        _note_fold(metrics, "eager", (time.perf_counter() - t0) * 1e3)
        return pd

    if len(attrs) > 1 and workers > 1:
        pool = _fold_pool()
        sem = threading.Semaphore(workers)
        if metrics is not None:
            metrics.counter("dgraph_parallel_folds_total").inc(len(attrs))
            metrics.counter("dgraph_fold_pool_width").set(
                min(workers, default_fold_workers()))

        def run(a):
            with sem:
                return one(a)

        # dgraph: allow(ctxvar-copy) folds build SHARED snapshot state
        # cached across requests — they must not inherit any one
        # request's deadline/trace context
        futs = [pool.submit(run, a) for a in attrs]
        return [f.result() for f in futs]
    return [one(a) for a in attrs]


def build_snapshot(store: Store, read_ts: int,
                   attrs: Iterable[str] | None = None,
                   own_start_ts: int | None = None,
                   fold_workers: int | None = None,
                   lazy: bool = False) -> GraphSnapshot:
    """Fold the store at read_ts into a GraphSnapshot (upload to device).
    Folds run across the shared thread pool (per-predicate folds are
    independent); fold_workers=1 forces the serial path.

    lazy=True registers every tablet as a fold-thunk instead: the first
    read of a predicate folds exactly that tablet (singleflighted), with
    output byte-identical to the eager fold at the same read_ts. The
    serving path (SnapshotAssembler) is lazy by default; this one-shot
    utility stays eager by default because its callers (replication
    quorum reads, smoke-test reference builds) want the complete fold."""
    snap = GraphSnapshot(read_ts)
    todo = sorted(attrs) if attrs is not None else store.predicates()
    if lazy:
        metrics = getattr(store, "metrics", None)
        preds = LazyPreds()
        snap.preds = preds

        def bare_fold(th, trigger):
            t0 = time.perf_counter()
            pd = build_pred(store, th.attr, th.eff, own_start_ts)
            _note_fold(metrics, trigger,
                       (time.perf_counter() - t0) * 1e3)
            return pd

        for attr in todo:
            preds.register(attr, _FoldThunk(attr, read_ts, bare_fold))
        return snap
    workers = fold_workers if fold_workers is not None \
        else default_fold_workers()
    for attr, pd in zip(todo, _fold_attrs(store, todo, read_ts,
                                          own_start_ts, workers)):
        snap.preds[attr] = pd
    return snap


@dataclass
class _OverlayState:
    """Book-keeping for one predicate's live overlay: the TRUE folded base
    it stacks on (re-stamps always start from here — overlays never nest),
    its current depth in touched keys, and its birth time (age-triggered
    compaction)."""

    base_ts: int
    base_pd: PredData
    depth: int
    born: float


class SnapshotAssembler:
    """Incremental snapshot cache: per-predicate PredData reuse keyed on the
    store's per-predicate commit watermark (pred_commit_ts), plus a small
    per-read-ts snapshot cache. This is the read-through contract of
    posting/lists.go:243 — the world is never rebuilt — shared by the
    embedded Node, the worker wire service, and follower readers.

    Commit-to-visible is O(Δ): a commit whose touched keys are in the
    store's delta journal STAMPS the cached PredData with replacement rows
    (storage/delta.py) instead of re-folding the tablet — base device
    arrays keep identity, and only the touched subjects/terms are
    re-derived. Deep or old overlays compact back into folded bases
    (inline past OVERLAY_MAX_KEYS; in the background via compact())."""

    SNAP_CACHE = 4
    OVERLAY_MAX_KEYS = 512       # stamp depth ceiling: past it, fold inline
    OVERLAY_MAX_AGE_S = 30.0     # background compaction age trigger

    def __init__(self, store, on_pred_build=None, metrics=None,
                 overlay_enabled: bool = True,
                 overlay_max_keys: int | None = None,
                 overlay_max_age_s: float | None = None,
                 fold_workers: int | None = None,
                 lazy_folds: bool = True) -> None:
        self.store = store
        self.on_pred_build = on_pred_build       # callback(attr) per re-fold
        self.metrics = metrics                   # utils.metrics.Registry|None
        self.overlay_enabled = overlay_enabled
        if overlay_max_keys is not None:
            self.OVERLAY_MAX_KEYS = int(overlay_max_keys)
        if overlay_max_age_s is not None:
            self.OVERLAY_MAX_AGE_S = float(overlay_max_age_s)
        self.fold_workers = (fold_workers if fold_workers is not None
                             else default_fold_workers())
        # lazy on-demand folds (ISSUE 15): assembly registers fold-thunks
        # and the first read of a predicate folds exactly that tablet
        self.lazy_folds = bool(lazy_folds)
        # attr -> (built_ts, PredData, replay_seq at build)
        self._pred_cache: dict[str, tuple[int, PredData, int]] = {}
        self._overlays: dict[str, _OverlayState] = {}
        self._snaps: dict[int, GraphSnapshot] = {}
        # attr -> unresolved fold thunk: carried across assemblies while
        # the data window is unchanged so successive snapshots share one
        # pending fold exactly like they share one cached PredData
        self._pending: dict[str, _FoldThunk] = {}
        self._card_hints: dict[str, int] = {}    # attr -> DATA key count
        self._first_assembled = False
        # bumped by invalidate(): structural changes ('s'/'dp'/'dk'
        # records) don't move pred_commit_ts/pred_replay_seq, so a lazy
        # fold in flight across an alter needs its own stability check
        # before writing _pred_cache
        self._cache_gen = 0

    def snapshot(self, read_ts: int) -> GraphSnapshot:
        """Committed view at read_ts (clamped to the newest commit: two
        read_ts above it see identical data and share the cache entry)."""
        eff = min(read_ts, self.store.max_seen_commit_ts)
        snap = self._snaps.get(eff)
        if snap is None or self._stale(snap):
            snap = self._assemble(eff)
            self._snaps[eff] = snap
            while len(self._snaps) > self.SNAP_CACHE:
                self._snaps.pop(next(iter(self._snaps)))
        return snap

    def _stale(self, snap: GraphSnapshot) -> bool:
        # A cached snapshot at read_ts is immutable under NORMAL commits
        # (they land above read_ts and are invisible to it). The only way
        # it rots is a commit arriving AT/BELOW read_ts after assembly —
        # replication replay races — so compare each predicate's commit
        # watermark against the value stamped at assembly, and only when
        # the new watermark is visible at this read_ts. A plain
        # "watermark > read_ts" check would mark every old-ts snapshot
        # permanently stale the moment any newer commit lands.
        stamped = getattr(snap, "pred_watermarks", None)
        replays = getattr(snap, "pred_replays", None)
        if stamped is None:
            return True                   # built before stamping existed
        for attr in self.store.predicates():
            pct = self.store.pred_commit_ts.get(attr, 0)
            if pct <= snap.read_ts and stamped.get(attr) != pct:
                return True               # replayed/new commit now visible
            if self.store.pred_replay_seq.get(attr, 0) != \
                    (replays or {}).get(attr, 0):
                # a commit landed BELOW the predicate's watermark since
                # assembly — the max-only watermark can't place it relative
                # to read_ts, so treat every cached view as suspect
                return True
        return False

    def _stamp(self, snap: GraphSnapshot) -> None:
        snap.pred_watermarks = {
            a: self.store.pred_commit_ts.get(a, 0) for a in snap.preds}
        snap.pred_replays = {
            a: self.store.pred_replay_seq.get(a, 0) for a in snap.preds}

    def _assemble(self, eff: int) -> GraphSnapshot:
        t0 = time.perf_counter()
        snap = GraphSnapshot(eff)
        if self.lazy_folds:
            preds = LazyPreds()
            preds.hint_fn = self._card_hint
            snap.preds = preds
        reused = 0
        todo: list[tuple[str, int, int, bool]] = []
        for attr in self.store.predicates():
            pct = self.store.pred_commit_ts.get(attr, 0)
            seq = self.store.pred_replay_seq.get(attr, 0)
            cached = self._pred_cache.get(attr)
            if cached is not None and cached[2] != seq:
                # a commit landed BELOW the watermark after the cached fold
                # (replication replay): the cached view silently misses it —
                # the max-only watermark check alone would keep serving it
                self._pred_cache.pop(attr, None)
                self._overlays.pop(attr, None)
                cached = None
            if cached is not None and cached[0] >= pct and eff >= pct:
                # both views contain every commit to attr (all <= pct)
                snap.preds[attr] = cached[1]
                reused += 1
                continue
            pd = self._try_stamp(attr, cached, pct, seq, eff)
            if pd is not None:
                snap.preds[attr] = pd
            else:
                todo.append((attr, pct, seq, cached is not None))
        if todo and self.lazy_folds:
            # register fold-thunks instead of folding: the first read of
            # a predicate (or a residency prefetch) folds exactly that
            # tablet, singleflighted. A still-pending thunk from an
            # earlier assembly is reused while its data window matches —
            # the same both-views-complete rule as _pred_cache reuse
            for attr, pct, seq, had_cached in todo:
                th = self._pending.get(attr)
                if th is None or not (th.eff >= pct and eff >= pct
                                      and th.seq == seq):
                    th = _FoldThunk(attr, eff, self._fold_pending,
                                    pct=pct, seq=seq, inline=had_cached)
                    if eff >= pct:
                        self._pending[attr] = th
                snap.preds.register(attr, th)
            self._set_pending_gauge()
        elif todo:
            attrs = [a for a, _p, _s, _c in todo]
            for attr, pd in zip(attrs, _fold_attrs(
                    self.store, attrs, eff, None, self.fold_workers,
                    self.metrics)):
                if self.on_pred_build is not None:
                    self.on_pred_build(attr)
                pct = self.store.pred_commit_ts.get(attr, 0)
                if eff >= pct:
                    self._pred_cache[attr] = (
                        eff, pd, self.store.pred_replay_seq.get(attr, 0))
                    self._overlays.pop(attr, None)
                    self._set_depth(attr, 0)
                    self.store.prune_delta(attr, eff)
                snap.preds[attr] = pd
        if reused and len(snap.preds) > reused and self.metrics is not None:
            # clean predicates carried across a change to OTHER predicates:
            # exactly the task-cache invalidations per-predicate tokens avoid
            self.metrics.counter(
                "dgraph_cache_invalidations_avoided_total").inc(reused)
        # query-time instrumentation that lives below the Node (vector
        # searches in query/task.py) reads the owning registry off the
        # snapshot — per-node correct, no module globals
        snap.metrics = self.metrics
        self._stamp(snap)
        if not self._first_assembled:
            # the cold-open lever: under eager folds this wall covered
            # EVERY tablet's fold; lazy assembly is O(predicates)
            self._first_assembled = True
            if self.metrics is not None:
                self.metrics.counter("dgraph_cold_open_ms").set(
                    (time.perf_counter() - t0) * 1e3)
        return snap

    def _fold_pending(self, th: _FoldThunk, trigger: str) -> PredData:
        """On-demand fold of one registered thunk (the _FoldThunk leader
        runs this OUTSIDE the claim lock) plus the cache bookkeeping the
        eager assembly tail performs. pct/seq are read around the fold
        and the cache entry written only when nothing moved mid-fold (the
        compact() pattern), so a racing commit or replication replay can
        never pin a view whose delta the journal can't reproduce."""
        store = self.store
        gen0 = self._cache_gen
        pct0 = store.pred_commit_ts.get(th.attr, 0)
        seq0 = store.pred_replay_seq.get(th.attr, 0)
        t0 = time.perf_counter()
        pd = build_pred(store, th.attr, th.eff)
        _note_fold(self.metrics, trigger, (time.perf_counter() - t0) * 1e3)
        if self.on_pred_build is not None:
            self.on_pred_build(th.attr)
        pct = store.pred_commit_ts.get(th.attr, 0)
        seq = store.pred_replay_seq.get(th.attr, 0)
        if th.eff >= pct and (pct0, seq0) == (pct, seq) \
                and gen0 == self._cache_gen:
            self._pred_cache[th.attr] = (th.eff, pd, seq)
            self._overlays.pop(th.attr, None)
            self._set_depth(th.attr, 0)
            store.prune_delta(th.attr, th.eff)
        if self._pending.get(th.attr) is th:
            self._pending.pop(th.attr, None)
        self._set_pending_gauge()
        return pd

    def _card_hint(self, attr: str) -> int:
        """DATA key count of one tablet — the planner's universe
        normalization for unfolded tablets (order decisions only, never
        results; exact post-bulk via the packed-tablet count, a decode-free
        key scan otherwise). Cached until invalidate()."""
        h = self._card_hints.get(attr)
        if h is None:
            tp = self.store.packed_tablet(int(K.KeyKind.DATA), attr)
            h = int(tp.n) if tp is not None else \
                len(self.store.keys_of(K.KeyKind.DATA, attr))
            self._card_hints[attr] = h
        return h

    def _set_pending_gauge(self) -> None:
        if self.metrics is not None:
            self.metrics.counter("dgraph_fold_pending_tablets").set(
                len(self._pending))

    def _set_depth(self, attr: str, depth: int) -> None:
        if self.metrics is not None:
            self.metrics.keyed("dgraph_overlay_depth").set(attr, depth)

    def _try_stamp(self, attr: str, cached, pct: int, seq: int,
                   eff: int) -> PredData | None:
        """O(Δ) overlay stamp of the cached PredData; None = not stampable
        (caller folds). Never stacks: re-stamps start from the true base."""
        if not self.overlay_enabled or cached is None:
            return None
        if eff < pct or cached[0] > eff:
            return None       # old-ts view: fold it (and don't cache)
        st = self._overlays.get(attr)
        base_ts, base_pd = (st.base_ts, st.base_pd) if st is not None \
            else (cached[0], cached[1])
        dmap = self.store.delta_since(attr, base_ts)
        if dmap is None:
            return None       # journal can't prove completeness: fold
        dkeys = [kb for kb, cts in dmap.items() if cts <= eff]
        if len(dkeys) > self.OVERLAY_MAX_KEYS:
            return None       # deep overlay: inline compaction via fold
        from dgraph_tpu.storage import delta as dmod

        try:
            pd = dmod.stamp_pred(self.store, attr, base_pd, eff, dkeys)
        except Exception:
            if self.metrics is not None:
                self.metrics.counter(
                    "dgraph_overlay_fold_fallbacks_total").inc()
            return None
        self._pred_cache[attr] = (eff, pd, seq)
        import time as _time

        born = st.born if st is not None else _time.monotonic()
        self._overlays[attr] = _OverlayState(base_ts, base_pd,
                                             len(dkeys), born)
        if self.metrics is not None:
            self.metrics.counter("dgraph_overlay_stamps_total").inc()
        self._set_depth(attr, len(dkeys))
        return pd

    # -- background compaction (rollup) --------------------------------------

    def overlay_stats(self) -> dict[str, int]:
        """attr -> overlay depth in touched keys. An ops readout: callers
        (e.g. /debug/metrics handler threads) may race assembly, so retry
        the briefly-inconsistent iteration instead of requiring the lock."""
        for _ in range(4):
            try:
                return {attr: st.depth
                        for attr, st in list(self._overlays.items())}
            except RuntimeError:
                continue
        return {}

    def overlay_bytes(self) -> int:
        """Host bytes held by live overlay rows (enforce_memory input).
        Same lock-free-readout contract as overlay_stats."""
        from dgraph_tpu.storage import delta as dmod

        for _ in range(4):
            try:
                return sum(dmod.overlay_nbytes(c[1])
                           for c in list(self._pred_cache.values()))
            except RuntimeError:
                continue
        return 0

    def compact_candidates(self, force: bool = False) -> list[str]:
        import time as _time

        now = _time.monotonic()
        # lazy folds pop _overlays from query threads (_fold_pending runs
        # lock-free); retry the briefly-inconsistent iteration like
        # overlay_stats does instead of requiring the node lock
        for _ in range(4):
            try:
                return [attr for attr, st in list(self._overlays.items())
                        if force or st.depth >= self.OVERLAY_MAX_KEYS
                        or now - st.born >= self.OVERLAY_MAX_AGE_S]
            except RuntimeError:
                continue
        return []

    def compact(self, lock, attrs: list[str] | None = None,
                force: bool = False) -> int:
        """Merge overlays back into folded bases OFF the query path (the
        background rollup): fold outside `lock` at a pinned watermark, swap
        under `lock` only if nothing moved meanwhile. After a successful
        compaction the predicate's overlay is empty, the delta journal is
        pruned, and reads serve the fresh base — results unchanged (the
        overlay and the fold describe the same data). Returns the number of
        predicates compacted."""
        import time as _time

        with lock:
            cands = (list(attrs) if attrs is not None
                     else self.compact_candidates(force=force))
            pinned = {
                attr: (self.store.pred_commit_ts.get(attr, 0),
                       self.store.pred_replay_seq.get(attr, 0))
                for attr in cands if attr in self._overlays}
        done = 0
        for attr, (ts, seq) in pinned.items():
            t0 = _time.perf_counter()
            try:
                pd = build_pred(self.store, attr, ts)
            except Exception:
                continue      # store moved under us: the next tick retries
            with lock:
                if (self.store.pred_commit_ts.get(attr, 0),
                        self.store.pred_replay_seq.get(attr, 0)) != (ts, seq):
                    continue  # commit/replay raced the fold: retry later
                old = self._pred_cache.get(attr)
                if attr not in self._overlays:
                    continue
                self._pred_cache[attr] = (ts, pd, seq)
                self._overlays.pop(attr, None)
                self.store.prune_delta(attr, ts)
                # cached snapshots pinning the stamped view: drop them so
                # the next read reassembles over the fresh base (cheap — all
                # predicates are cache hits) and the overlay memory frees
                if old is not None:
                    # folded-only peek: a pinned stamped view is always a
                    # materialized entry — .get here would FOLD pending
                    # tablets of every cached snapshot just to compare
                    for k in [k for k, s in self._snaps.items()
                              if getattr(s.preds, "folded_get",
                                         s.preds.get)(attr) is old[1]]:
                        self._snaps.pop(k, None)
                done += 1
                self._set_depth(attr, 0)
                if self.metrics is not None:
                    self.metrics.counter("dgraph_compactions_total").inc()
                    self.metrics.histogram("dgraph_compaction_s").observe(
                        _time.perf_counter() - t0)
        return done

    def invalidate(self) -> int:
        """Structural change (schema, drop, predicate delete): every cached
        view may be wrong — rebuild from scratch on next read. Returns the
        number of dropped cache entries (memory accounting)."""
        n = len(self._pred_cache) + len(self._snaps)
        for attr in self._overlays:
            self._set_depth(attr, 0)
        self._pred_cache.clear()
        self._overlays.clear()
        self._snaps.clear()
        # outstanding lazy thunks (held by handed-out snapshots) still
        # resolve against the live store at their own read_ts; the
        # assembler just stops reusing them — and the generation bump
        # keeps an in-flight fold (started pre-alter) from writing its
        # stale view back into _pred_cache after this clear
        self._cache_gen += 1
        self._pending.clear()
        self._card_hints.clear()
        self._set_pending_gauge()
        return n

    def cache_size(self) -> int:
        return len(self._pred_cache) + len(self._snaps)


# WAL record types that change visible structure beyond the per-predicate
# commit watermark: schema lines, predicate/kind drops
STRUCTURAL_RECORDS = frozenset({"s", "dp", "dk"})
