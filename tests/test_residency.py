"""HBM working-set manager (ISSUE 11, storage/residency.py): tiered
device residency — budget admission, LRU-of-score eviction, pin floors,
hysteresis/thrash accounting, plan-driven prefetch, cold-tier host
serving, and the identity contracts (qcache tokens, DeviceBatcher
same-CSR-object compatibility, mesh placement caches) across an
evict → re-admit cycle of the same tablet."""

import gc

import numpy as np
import pytest

from dgraph_tpu.api.server import Node
from dgraph_tpu.query import batch as batchmod
from dgraph_tpu.query import qcache
from dgraph_tpu.query import task as taskmod
from dgraph_tpu.query.task import TaskQuery
from dgraph_tpu.storage import residency as resmod
from dgraph_tpu.storage.csr_build import PredCSR
from dgraph_tpu.storage.residency import ResidencyManager
from dgraph_tpu.utils import faults
from dgraph_tpu.utils.metrics import Registry


# ---------------------------------------------------------------------------
# unit level: manager policy over stub owners
# ---------------------------------------------------------------------------

class _StubOwner:
    """Minimal residency owner: a named device-buffer group."""

    _res = None
    _res_attr = ""
    _res_kind = "csr"

    def __init__(self, mgr, attr, nbytes):
        self._res = mgr
        self._res_attr = attr
        self.nbytes = nbytes
        self._dev = None
        self.drops = 0

    def device_nbytes(self):
        return self.nbytes

    def device_resident(self):
        return self._dev is not None

    def drop_device(self):
        self._dev = None
        self.drops += 1

    def upload(self, prefetch=False):
        return resmod.ensure_device(self, "_dev", lambda: ("dev",),
                                    prefetch=prefetch)


class _Clock:
    def __init__(self):
        self.t = 1000.0

    def __call__(self):
        return self.t


@pytest.fixture
def clock():
    return _Clock()


def _mgr(clock, budget=1000, **kw):
    kw.setdefault("min_resident_s", 0.0)
    return ResidencyManager(budget_bytes=budget, metrics=Registry(),
                            clock=clock, **kw)


def test_admission_under_budget_and_eviction(clock):
    mgr = _mgr(clock, budget=1000)
    a = _StubOwner(mgr, "a", 400)
    b = _StubOwner(mgr, "b", 400)
    a.upload()
    b.upload()
    assert mgr.usage()["hbm_bytes"] == 800
    # c needs 400 -> one of a/b must go; touch b so a is the cold victim
    mgr.touch("b")
    c = _StubOwner(mgr, "c", 400)
    c.upload()
    assert a.drops == 1 and b.drops == 0
    assert a._dev is None and b._dev is not None and c._dev is not None
    assert mgr.usage()["hbm_bytes"] == 800
    m = mgr.metrics
    assert m.counter("dgraph_residency_admissions_total").value == 3
    assert m.counter("dgraph_residency_evictions_total").value == 1


def test_eviction_order_is_lru_of_score(clock):
    mgr = _mgr(clock, budget=1200)
    owners = {n: _StubOwner(mgr, n, 400) for n in ("x", "y", "z")}
    for o in owners.values():
        o.upload()
    # x is hottest, z warm, y idle -> y is the lowest-score victim
    for _ in range(10):
        mgr.touch("x")
    mgr.touch("z")
    w = _StubOwner(mgr, "w", 400)
    w.upload()
    assert owners["y"].drops == 1
    assert owners["x"].drops == 0 and owners["z"].drops == 0


def test_pin_floor_never_evicts(clock):
    mgr = _mgr(clock, budget=800, pins=("keep",))
    kept = _StubOwner(mgr, "keep", 400)
    other = _StubOwner(mgr, "other", 400)
    kept.upload()
    other.upload()
    # hammer "other" so only the pin (not the score) can save "keep"
    for _ in range(20):
        mgr.touch("other")
    c = _StubOwner(mgr, "c", 400)
    c.upload()
    assert kept.drops == 0 and other.drops == 1


def test_hysteresis_skips_young_entries_when_possible(clock):
    mgr = _mgr(clock, budget=800, min_resident_s=5.0)
    old = _StubOwner(mgr, "old", 400)
    old.upload()
    clock.t += 10.0                   # old is past the hysteresis floor
    young = _StubOwner(mgr, "young", 400)
    young.upload()
    clock.t += 1.0                    # young is NOT
    c = _StubOwner(mgr, "c", 400)
    c.upload()
    assert old.drops == 1 and young.drops == 0


def test_thrash_counter_on_fast_readmit(clock):
    mgr = _mgr(clock, budget=400, thrash_window_s=10.0)
    a = _StubOwner(mgr, "a", 400)
    b = _StubOwner(mgr, "b", 400)
    a.upload()
    clock.t += 1.0
    b.upload()                        # evicts a
    clock.t += 1.0
    a.upload()                        # re-admit within the window
    assert mgr.metrics.counter(
        "dgraph_residency_thrash_total").value >= 1


def test_cold_tablet_never_admits(clock):
    mgr = _mgr(clock, budget=100)
    big = _StubOwner(mgr, "big", 400)
    assert not mgr.allows_device(big.device_nbytes())
    # prefer_host is a pure consult — a fused-shape check probing several
    # owners must not inflate cold_serves; serve sites count explicitly
    assert resmod.prefer_host(big)
    assert mgr.metrics.counter(
        "dgraph_residency_cold_serves_total").value == 0
    mgr.note_cold_serve()
    assert mgr.metrics.counter(
        "dgraph_residency_cold_serves_total").value == 1
    assert mgr.tier_of("big", 400) == resmod.TIER_COLD
    assert mgr.tier_of("big", 50) == resmod.TIER_WARM


def test_evict_to_and_weakref_unregister(clock):
    mgr = _mgr(clock, budget=1000)
    a = _StubOwner(mgr, "a", 300)
    b = _StubOwner(mgr, "b", 300)
    a.upload()
    b.upload()
    assert mgr.evict_to(300) == 1
    assert mgr.usage()["hbm_bytes"] == 300
    # dropping the last strong ref unregisters via the weakref callback
    del a, b
    gc.collect()
    assert mgr.usage()["hbm_bytes"] == 0


# ---------------------------------------------------------------------------
# node level: tiers through the real query path
# ---------------------------------------------------------------------------

N_PREDS = 16
N_SUBJ = 48
FANOUT = 8
PREDS = [f"p{i:02d}" for i in range(N_PREDS)]


def _build_node(**kw):
    """Node over N_PREDS uid tablets of ~equal size (so a budget between
    one tablet and the total forces real admission/eviction churn) plus
    an exact-indexed name predicate. Task/result caches off by default:
    these tests probe the dispatch seam, not the cache tiers."""
    kw.setdefault("task_cache_mb", 0)
    kw.setdefault("result_cache_mb", 0)
    # planner off: its estimated-frontier cutover would route these small
    # expands host-side regardless of the shrunken HOST_EXPAND_MAX
    kw.setdefault("planner", False)
    n = Node(**kw)
    schema = ["name: string @index(exact) ."]
    schema += [f"{p}: [uid] ." for p in PREDS]
    n.alter(schema_text="\n".join(schema))
    rng = np.random.default_rng(11)
    quads = []
    for i in range(1, N_SUBJ + 1):
        quads.append(f'<{i:#x}> <name> "s{i}" .')
    for p in PREDS:
        for i in range(1, N_SUBJ + 1):
            for t in rng.choice(N_SUBJ, FANOUT, replace=False) + 1:
                quads.append(f"<{i:#x}> <{p}> <{int(t):#x}> .")
    n.mutate(set_nquads="\n".join(quads), commit_now=True)
    return n


QUERIES = [f"{{ q(func: has({p})) {{ {p} {{ uid }} }} }}" for p in PREDS]


def _run_all(node, queries=QUERIES):
    return [node.query(q)[0] for q in queries]


def _graph_device_bytes(node) -> int:
    snap = node.snapshot()
    return sum(resmod.pred_host_nbytes(pd) for pd in snap.preds.values())


@pytest.fixture
def force_device(monkeypatch):
    """Shrink the host/device cutover so every multi-row expand takes the
    device path (the tier the manager governs)."""
    monkeypatch.setattr(taskmod, "HOST_EXPAND_MAX", 8)


def test_tiered_serving_byte_identical_10x_budget(force_device):
    """The tiering gate at test scale: a budget ~10x smaller than the
    graph's device footprint serves the battery byte-identically, with
    real admission/eviction churn underneath."""
    resident = _build_node()
    want = _run_all(resident)
    tiered = _build_node(device_budget_mb=1)
    # refine the MB-granular flag to exactly graph/10: bigger than one
    # tablet, 10x smaller than the graph
    total = _graph_device_bytes(tiered)
    tiered.residency.budget = total // 10
    tiered.residency.evict_to(tiered.residency.budget)
    got = _run_all(tiered)
    assert got == want
    m = tiered.residency.metrics
    assert m.counter("dgraph_residency_admissions_total").value > 0
    assert m.counter("dgraph_residency_evictions_total").value > 0
    assert tiered.residency.usage()["hbm_bytes"] <= \
        tiered.residency.budget
    resident.close()
    tiered.close()


def test_cold_tablet_serves_host_path(force_device):
    """A tablet bigger than the WHOLE budget never uploads: the expand
    takes the host gather at any frontier size, byte-identically."""
    want_node = _build_node()
    expect = _run_all(want_node)
    node = _build_node(device_budget_mb=1)
    node.residency.budget = 64          # smaller than any tablet here
    got = _run_all(node)
    assert got == expect
    snap = node.snapshot()
    assert snap.preds["p00"].csr._dev is None      # never uploaded
    assert node.residency.metrics.counter(
        "dgraph_residency_cold_serves_total").value > 0
    assert node.residency.usage()["hbm_bytes"] == 0
    node.close()
    want_node.close()


def test_evict_readmit_identity_rotation(force_device):
    """Satellite: qcache per-predicate tokens, DeviceBatcher
    same-CSR-object keys, and results must all survive an evict →
    re-admit cycle of the same tablet — and re-key only on a real
    commit."""
    node = _build_node(device_budget_mb=512)
    q0 = QUERIES[0]
    want = node.query(q0)[0]
    snap = node.snapshot()
    pd = snap.preds["p00"]
    csr = pd.csr
    assert csr._dev is not None          # device path ran
    tq = TaskQuery("p00", frontier=np.arange(1, 33, dtype=np.int64))
    tok0 = qcache.task_token(snap, tq)
    key0, kind0, _w = batchmod.classify(snap, node.store.schema, tq)
    assert kind0 == "expand" and key0 == ("expand", id(csr))

    # evict: device buffers drop, identity stays
    assert node.residency.evict_to(0) > 0
    assert csr._dev is None
    snap2 = node.snapshot()
    assert snap2.preds["p00"] is pd                # same PredData
    assert qcache.task_token(snap2, tq) == tok0    # token survives
    assert node.query(q0)[0] == want               # re-admits on demand
    assert csr._dev is not None                    # re-uploaded
    key1, kind1, _w = batchmod.classify(node.snapshot(),
                                        node.store.schema, tq)
    assert kind1 == "expand" and key1 == key0      # same batch bucket

    # a REAL commit must rotate the token (the invalidation half)
    node.mutate(set_nquads=f"<{1:#x}> <p00> <{47:#x}> .",
                commit_now=True)
    snap3 = node.snapshot()
    assert qcache.task_token(snap3, tq) != tok0
    node.close()


def test_mesh_placement_cache_survives_evict_cycle(force_device):
    """Mesh placement is identity-keyed on PredData: an evict/re-admit
    cycle must neither rotate the placement nor change results."""
    node = _build_node(device_budget_mb=512, mesh_devices=4,
                       mesh_min_edges=64)
    qs = QUERIES[:4]
    want = _run_all(node, qs)
    snap = node.snapshot()              # mesh-placed snapshot
    placed0 = snap.preds["p00"].csr
    node.residency.evict_to(0)
    snap2 = node.snapshot()
    assert snap2.preds["p00"].csr is placed0       # placement cache hit
    assert _run_all(node, qs) == want
    node.close()


def test_mesh_placement_defers_to_budget():
    """A tablet whose per-device row-shard would not fit the budget stays
    on the host path instead of sharding (placement defers)."""
    from dgraph_tpu.parallel.dist import DistPredCSR
    from dgraph_tpu.parallel.mesh_exec import MeshExecutor

    reg = Registry()
    mgr = ResidencyManager(budget_bytes=64, metrics=reg)
    mex = MeshExecutor(n_devices=4, metrics=reg, shard_min_edges=16,
                       residency=mgr)
    subjects = np.arange(1, 65, dtype=np.int32)
    indptr = np.arange(0, 65 * 8, 8, dtype=np.int32)
    indices = (np.arange(64 * 8, dtype=np.int32) % 64) + 1
    csr = PredCSR(subjects, indptr, indices)
    assert mex._place_csr(csr) is csr          # deferred: budget too small
    assert reg.counter("dgraph_mesh_residency_deferred_total").value == 1
    mgr.budget = 0                              # unbounded: shards again
    assert isinstance(mex._place_csr(csr), DistPredCSR)


def test_prefetch_hits_and_wasted(force_device):
    node = _build_node(device_budget_mb=512)
    snap = node.snapshot()
    assert node.residency.prefetch(["p00"], snap, sync=True) >= 1
    csr = snap.preds["p00"].csr
    assert csr._dev is not None                  # prefetched into HBM
    _ = node.query(QUERIES[0])                   # touches p00
    m = node.residency.metrics
    assert m.counter("dgraph_residency_prefetch_hits_total").value >= 1
    # prefetch another tablet, then evict it untouched -> wasted
    assert node.residency.prefetch(["p01"], snap, sync=True) >= 1
    node.residency.evict_to(0)
    assert m.counter("dgraph_residency_prefetch_wasted_total").value >= 1
    node.close()


def test_upload_fault_serves_host_byte_identical(force_device):
    """residency.h2d_upload chaos point: an injected upload failure must
    never fail or corrupt a read — the host gather serves it."""
    clean = _build_node()
    want = _run_all(clean, QUERIES[:4])
    node = _build_node(device_budget_mb=512)
    try:
        faults.GLOBAL.reseed(7)
        faults.GLOBAL.install("residency.h2d_upload", "error", p=1.0)
        got = _run_all(node, QUERIES[:4])
        assert got == want
        m = node.residency.metrics
        assert m.counter(
            "dgraph_residency_upload_failures_total").value > 0
        snap = node.snapshot()
        assert snap.preds["p00"].csr._dev is None
        # clearing the fault lets the next read promote again
        faults.GLOBAL.clear()
        assert _run_all(node, QUERIES[:4]) == want
        assert node.snapshot().preds["p00"].csr._dev is not None
    finally:
        faults.GLOBAL.clear()
        node.close()
        clean.close()


def test_vector_evict_readmit_rank_identical():
    """VectorIndex device matrices: identical ranking across an evict /
    re-admit cycle, and a cold vector tablet serves the exact host
    scan."""
    import dgraph_tpu.storage.vecindex as vx

    node = Node(device_budget_mb=512, task_cache_mb=0, result_cache_mb=0)
    node.alter(
        schema_text="emb: float32vector @index(vector(dim: 8)) .")
    rng = np.random.default_rng(5)
    quads = []
    for i in range(1, 200):
        v = ", ".join(f"{x:.4f}" for x in rng.normal(size=8))
        quads.append(f'<{i:#x}> <emb> "[{v}]" .')
    node.mutate(set_nquads="\n".join(quads), commit_now=True)
    qv = "[" + ", ".join(["0.1"] * 8) + "]"
    q = f'{{ q(func: similar_to(emb, "{qv}", 5)) {{ uid }} }}'
    # force the device path (tiny tablets host-scan by default)
    old = vx.HOST_SCAN_MAX
    vx.HOST_SCAN_MAX = 1
    try:
        want, _ = node.query(q)
        vi = node.snapshot().preds["emb"].vecindex
        assert vi._dev is not None
        node.residency.evict_to(0)
        assert vi._dev is None
        got, _ = node.query(q)
        assert got == want                     # re-admitted, same ranks
        # cold: budget below the matrix -> host float64 scan, same ranks
        node.residency.budget = 64
        node.residency.evict_to(64)
        cold, _ = node.query(q)
        assert cold == want
        assert vi._dev is None
    finally:
        vx.HOST_SCAN_MAX = old
        node.close()


def test_vector_heavy_snapshot_triggers_eviction():
    """Satellite regression (the undercount): vector embedding matrices
    were invisible to enforce_memory — a vector-heavy snapshot must now
    count toward the budget and trigger cache eviction."""
    node = Node()
    node.alter(
        schema_text="emb: float32vector @index(vector(dim: 64)) .")
    rng = np.random.default_rng(9)
    quads = []
    for i in range(1, 400):
        v = ", ".join(f"{x:.3f}" for x in rng.normal(size=64))
        quads.append(f'<{i:#x}> <emb> "[{v}]" .')
    node.mutate(set_nquads="\n".join(quads), commit_now=True)
    # fold the vector matrix (lazy snapshots fold on first READ)
    node.snapshot().pred("emb")
    vec_bytes = 399 * 64 * 4
    report = node.enforce_memory(
        budget_bytes=node.store.memory_stats()["bytes"] + vec_bytes // 4)
    # the fold accounting SEES the matrix ...
    assert report["fold_bytes"] >= vec_bytes
    # ... and the over-budget snapshot was dropped (the old code returned
    # dropped_caches == 0 here: store bytes alone were under budget)
    assert report["dropped_caches"] > 0
    node.close()


def test_residency_metrics_on_surfaces(force_device):
    """/metrics prom exposition + /debug/metrics residency section."""
    from dgraph_tpu.api.http import _serving_metrics
    from dgraph_tpu.obs import prom

    node = _build_node(device_budget_mb=512)
    _run_all(node, QUERIES[:4])
    node.residency.usage()
    text = prom.render(node.metrics)
    parsed = prom.parse(text)
    for name in ("dgraph_residency_admissions_total",
                 "dgraph_residency_evictions_total",
                 "dgraph_residency_prefetch_hits_total",
                 "dgraph_residency_prefetch_wasted_total",
                 "dgraph_residency_thrash_total",
                 "dgraph_residency_hbm_bytes",
                 "dgraph_residency_host_bytes"):
        assert name in parsed, name
    tiers = {lbl.get("tier") for lbl, _v in
             parsed.get("dgraph_residency_tier_bytes", [])}
    assert "hbm" in tiers
    section = _serving_metrics(node)["residency"]
    assert section["enabled"] is True
    assert section["admissions"] > 0
    assert set(section["tiers"]) == {"hbm", "warm", "cold"}
    assert isinstance(section["resident"], dict)
    node.close()


def test_unbounded_budget_is_accounting_only(force_device):
    """budget 0 (the default): no admission control, no eviction — the
    fully-resident fast path with accounting, so pre-existing deployments
    see zero behavior change."""
    node = _build_node()
    _run_all(node, QUERIES[:4])
    assert not node.residency.enabled
    m = node.residency.metrics
    assert m.counter("dgraph_residency_evictions_total").value == 0
    assert m.counter("dgraph_residency_cold_serves_total").value == 0
    snap = node.snapshot()
    assert snap.preds["p00"].csr._dev is not None
    node.close()


def test_tier_transition_span_events(force_device):
    """Admissions emit residency_tier span events — the span active at
    promotion time carries the warm->hbm transition it caused. Driven
    through process_task directly (not Node.query) so the async
    prefetcher can't win the upload race outside any span."""
    node = _build_node(device_budget_mb=512, span_sample=1.0)
    snap = node.snapshot()
    node.residency.evict_to(0)
    with node.tracer.root("probe", force=True):
        taskmod.process_task(
            snap, TaskQuery("p00", frontier=np.arange(1, 33,
                                                      dtype=np.int64)),
            node.store.schema)
    evs = []
    for rec in node.tracer.sink.index():
        full = node.tracer.sink.get(rec["trace_id"])
        for sp in full["spans"]:
            for ev in sp.get("events", []):
                if ev["name"] == "residency_tier":
                    evs.append(ev["attrs"])
    assert any(e.get("transition") == "warm->hbm" for e in evs)
    node.close()


def test_batcher_classifies_cold_tablet_out(force_device):
    """Review fix: the batched-dispatch classifier must consult the tier —
    a COLD tablet classifies out to the solo path (which serves the host
    gather) instead of being uploaded by a batched kernel."""
    node = _build_node(device_budget_mb=1)
    node.residency.budget = 64          # everything cold
    snap = node.snapshot()
    tq = TaskQuery("p00", frontier=np.arange(1, 33, dtype=np.int64))
    key, kind, work = batchmod.classify(snap, node.store.schema, tq)
    assert key is None and kind == "cold_tier"
    # warm again under an ample budget: classifies back to a batch bucket
    node.residency.budget = 512 << 20
    key, kind, _w = batchmod.classify(snap, node.store.schema, tq)
    assert kind == "expand" and key is not None
    node.close()


def test_batched_expand_upload_fault_host_fallback(force_device):
    """Review fix: a residency.h2d_upload fault inside a FORMED batch
    must not fail every member — the batched runner falls back to the
    per-slot host gather, byte-identical to solo execution."""
    from dgraph_tpu.query.batch import DeviceBatcher, _Entry

    node = _build_node(device_budget_mb=512)
    snap = node.snapshot()
    frontiers = [np.arange(1, 25, dtype=np.int64),
                 np.arange(9, 41, dtype=np.int64)]
    want = [taskmod.process_task(
        snap, TaskQuery("p01", frontier=f), node.store.schema)
        for f in frontiers]
    node.residency.evict_to(0)          # force a fresh upload attempt
    batcher = DeviceBatcher(metrics=Registry(), idle_fire=False)
    entries = []
    for f in frontiers:
        tq = TaskQuery("p01", frontier=f)
        _key, kind, work = batchmod.classify(snap, node.store.schema, tq)
        assert kind == "expand"
        entries.append(_Entry(work))
    try:
        faults.GLOBAL.reseed(1)
        faults.GLOBAL.install("residency.h2d_upload", "error", p=1.0)
        batcher._run_expand(entries)
        for e, w in zip(entries, want):
            assert e.error is None
            assert [m.tolist() for m in e.result.uid_matrix] == \
                [m.tolist() for m in w.uid_matrix]
            assert e.result.dest_uids.tolist() == w.dest_uids.tolist()
    finally:
        faults.GLOBAL.clear()
        node.close()
