"""Device-runtime observatory (ISSUE 19, obs/devprof.py): XLA
compile/retrace tracking attributed to program families, HBM telemetry
with budget-headroom pressure flags, and the dispatch-timeline
utilization profiler fed from DispatchGate — plus the --no_devprof
disarm contract and the /debug/compiles + /debug/timeline surfaces."""

import json
import random
import threading
import urllib.request

import pytest

from dgraph_tpu.api.http import make_server
from dgraph_tpu.api.server import Node
from dgraph_tpu.obs import costs
from dgraph_tpu.obs import devprof as devprof_mod
from dgraph_tpu.obs.devprof import DevProfiler
from dgraph_tpu.utils import metrics as metrics_mod

SCHEMA = """
    name: string @index(exact) .
    age: int @index(int) .
    follows: [uid] @reverse .
"""


@pytest.fixture
def node():
    n = Node(span_sample=1.0, trace_rng=random.Random(11))
    n.alter(schema_text=SCHEMA)
    n.mutate(set_nquads="""
        _:a <name> "ann" .
        _:b <name> "bob" .
        _:c <name> "cid" .
        _:a <age> "30" .
        _:a <follows> _:b .
        _:a <follows> _:c .
    """, commit_now=True)
    yield n
    n.close()


def _get(base, path):
    with urllib.request.urlopen(base + path, timeout=10) as r:
        assert r.status == 200
        return json.loads(r.read())


# ---------------------------------------------------------------------------
# profiler unit behavior (no Node)
# ---------------------------------------------------------------------------

def _mk_prof(slow_log=None, budget_bytes=0, residency=None):
    return DevProfiler(metrics_mod.Registry(), slow_log=slow_log,
                       budget_bytes=budget_bytes, residency=residency)


class _RecordingLog:
    def __init__(self):
        self.entries = []

    def record(self, entry):
        self.entries.append(entry)


def test_retrace_storm_detector_flags_shape_churn():
    """The seeded storm fixture: one family rebuilt under >= 3 distinct
    shape signatures within the window must flag exactly once (rate
    limited to one flag per window per family)."""
    log = _RecordingLog()
    prof = _mk_prof(slow_log=log)
    # warmup below the floor: 2 distinct shapes is a normal cache warm
    prof.on_build("mesh.plan", ("plan", 64))
    prof.on_build("mesh.plan", ("plan", 128))
    assert prof._m.counter("dgraph_xla_retrace_storms_total").value == 0
    # churn past both floors
    for cap in (256, 512, 1024):
        prof.on_build("mesh.plan", ("plan", cap))
    assert prof._m.counter("dgraph_xla_retrace_storms_total").value == 1
    assert len(log.entries) == 1
    e = log.entries[0]
    assert e["root"] == "retrace_storm"
    assert e["family"] == "mesh.plan"
    assert e["distinct_shapes"] >= 3
    # rate limit: more churn inside the same window does NOT re-flag
    for cap in (2048, 4096, 8192):
        prof.on_build("mesh.plan", ("plan", cap))
    assert prof._m.counter("dgraph_xla_retrace_storms_total").value == 1
    assert len(log.entries) == 1
    # a different family has its own window
    for cap in (1, 2, 3, 4):
        prof.on_build("mesh.bfs", ("bfs", cap))
    assert prof._m.counter("dgraph_xla_retrace_storms_total").value == 2
    snap = prof.compiles_snapshot()
    assert snap["families"]["mesh.plan"]["storms"] == 1
    assert snap["families"]["mesh.plan"]["builds"] == 8
    assert snap["retrace_storms"] == 2


def test_compile_listener_attributes_family_and_books_ledger():
    """The jax.monitoring callback: compile ms lands on the TLS family's
    row, on every armed profiler, and on the current cost ledger's
    compile_ms (kept SEPARATE from device_ms so first-touch compiles
    don't poison regression baselines)."""
    prof = _mk_prof()
    devprof_mod.register(prof)
    try:
        lg = costs.CostLedger(endpoint="query", shape="{ q }")
        with costs.scope(lg):
            devprof_mod.push_family("pb.bfs_dist")
            try:
                devprof_mod._on_duration_event(
                    "/jax/core/compile/backend_compile_duration", 0.025)
            finally:
                devprof_mod.pop_family()
        f = prof.compiles_snapshot()["families"]["pb.bfs_dist"]
        assert f["compiles"] == 1
        assert f["compile_ms"] == pytest.approx(25.0)
        assert lg.compile_ms == pytest.approx(25.0)
        assert lg.device_ms == 0.0          # separation contract
        # other event names are ignored
        devprof_mod._on_duration_event("/jax/core/trace_duration", 1.0)
        assert prof._m.counter("dgraph_xla_compiles_total").value == 1
        # no family pushed -> attributed to the catch-all row
        devprof_mod._on_duration_event(
            "/jax/core/compile/backend_compile_duration", 0.001)
        assert "unattributed" in prof.compiles_snapshot()["families"]
    finally:
        devprof_mod.unregister(prof)


def test_listener_is_noop_when_disarmed(monkeypatch):
    # force the module fan-out empty regardless of other tests' live
    # nodes sharing the process
    monkeypatch.setattr(devprof_mod, "_PROFILERS", ())
    # must not raise, must not book anywhere
    lg = costs.CostLedger(endpoint="query")
    with costs.scope(lg):
        devprof_mod._on_duration_event(
            "/jax/core/compile/backend_compile_duration", 0.5)
    assert lg.compile_ms == 0.0


def test_hbm_pressure_latches_against_budget():
    class _Residency:
        bytes_live = 0

        def usage(self):
            return self.bytes_live

        def host_bytes(self):
            return 0

    res = _Residency()
    prof = _mk_prof(budget_bytes=1000, residency=res)
    t = 0.0
    # below headroom: no pressure
    res.bytes_live = 500
    prof.record_dispatch("mesh", t, t, t + 0.001)
    assert prof._m.counter("dgraph_devprof_hbm_pressure_total").value == 0
    # crossing 0.9 * budget: one pressure event, then latched
    res.bytes_live = 950
    prof.record_dispatch("mesh", t, t, t + 0.001)
    prof.record_dispatch("mesh", t, t, t + 0.001)
    assert prof._m.counter("dgraph_devprof_hbm_pressure_total").value == 1
    assert prof.hbm_snapshot()["high_water"]["hbm"] == 950
    # back off below 0.8 * budget re-arms the latch
    res.bytes_live = 100
    prof.record_dispatch("mesh", t, t, t + 0.001)
    res.bytes_live = 980
    prof.record_dispatch("mesh", t, t, t + 0.001)
    assert prof._m.counter("dgraph_devprof_hbm_pressure_total").value == 2
    # high-water never regresses
    assert prof.hbm_snapshot()["high_water"]["hbm"] == 980


def test_timeline_ring_and_chrome_trace_shape():
    prof = _mk_prof()
    prof.record_dispatch("host", 1.0, 1.002, 1.010, bytes_moved=64)
    devprof_mod.register(prof)
    try:
        with costs.scope(costs.CostLedger(endpoint="query")):
            with costs.kernel("vector.topk"):
                prof.record_dispatch("mesh", 2.0, 2.001, 2.005)
    finally:
        devprof_mod.unregister(prof)
    recs = prof.timeline_snapshot()
    assert [r["seq"] for r in recs] == [1, 2]
    assert recs[0]["family"] == "host" and recs[0]["bytes"] == 64
    assert recs[0]["queue_ms"] == pytest.approx(2.0)
    assert recs[0]["run_ms"] == pytest.approx(8.0)
    # the kernel-timer TLS family wins over the coarse gate class
    assert recs[1]["family"] == "vector.topk"
    ct = prof.timeline_chrome()
    assert ct["displayTimeUnit"] == "ms"
    names = [e["name"] for e in ct["traceEvents"]]
    assert "host" in names and "vector.topk" in names
    assert "host (queued)" in names
    xs = [e for e in ct["traceEvents"] if e["ph"] == "X"]
    assert all(e["dur"] > 0 for e in xs)
    assert ct["otherData"]["records"] == 2
    assert ct["otherData"]["dispatches"] == 2


# ---------------------------------------------------------------------------
# node integration: every dispatch exactly once, families on records
# ---------------------------------------------------------------------------

def test_every_gated_dispatch_lands_exactly_once(node):
    for i in range(4):
        node.query('{ q(func: eq(name, "ann")) { name follows { name } } }')
    disp = node.metrics.counter("dgraph_devprof_dispatches_total").value
    assert disp > 0
    recs = node.devprof.timeline_snapshot(n=4096)
    # ring small enough here to hold everything: counter == ring length
    assert len(recs) == disp
    assert [r["seq"] for r in recs] == list(range(1, disp + 1))
    assert all(r["family"] for r in recs)
    assert all(r["run_ms"] >= 0.0 and r["queue_ms"] >= 0.0 for r in recs)


def test_shed_and_failed_dispatches_do_not_record(node):
    """Raises out of the gated fn still fence exactly once; admission
    rejections (before the gate's run window opens) record nothing."""
    before = node.metrics.counter("dgraph_devprof_dispatches_total").value

    def boom():
        raise RuntimeError("kernel exploded")

    with pytest.raises(RuntimeError):
        node.dispatch_gate.run(boom, klass="host")
    after = node.metrics.counter("dgraph_devprof_dispatches_total").value
    assert after == before + 1          # the dispatch DID run and fence
    assert len(node.devprof.timeline_snapshot(n=4096)) == after


# ---------------------------------------------------------------------------
# disarm contract
# ---------------------------------------------------------------------------

def test_no_devprof_disarms_every_seam():
    n = Node(devprof=False)
    try:
        n.alter(schema_text=SCHEMA)
        n.mutate(set_nquads='_:a <name> "ann" .', commit_now=True)
        assert n.devprof is None
        assert n.dispatch_gate.profiler is None
        assert n.mesh_exec is None or n.mesh_exec._prof is None
        r, _ = n.query('{ q(func: eq(name, "ann")) { name } }')
        assert r["q"] == [{"name": "ann"}]
        assert n.metrics.counter(
            "dgraph_devprof_dispatches_total").value == 0
        # runtime toggle arms and disarms the same seams
        n.set_devprof(True)
        prof = n.devprof
        assert prof is not None
        assert n.dispatch_gate.profiler is prof
        assert prof in devprof_mod._PROFILERS
        # a distinct query — the identical one would be served from the
        # task cache without ever reaching the dispatch gate
        n.query('{ q(func: has(name)) { name } }')
        assert n.metrics.counter(
            "dgraph_devprof_dispatches_total").value > 0
        n.set_devprof(False)
        assert n.devprof is None and n.dispatch_gate.profiler is None
        assert prof not in devprof_mod._PROFILERS
    finally:
        n.close()


def test_close_unregisters_from_module_fanout(node):
    prof = node.devprof
    assert prof in devprof_mod._PROFILERS
    node.close()
    assert prof not in devprof_mod._PROFILERS


# ---------------------------------------------------------------------------
# /debug surfaces
# ---------------------------------------------------------------------------

def test_debug_compiles_and_timeline_endpoints(node):
    srv = make_server(node, "127.0.0.1", 0)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    base = f"http://127.0.0.1:{srv.server_address[1]}"
    try:
        node.query('{ q(func: eq(name, "ann")) { name follows { name } } }')
        node.devprof.on_build("mesh.plan", ("plan", 64))
        c = _get(base, "/debug/compiles")
        assert c["enabled"] is True
        assert c["families"]["mesh.plan"]["builds"] == 1
        assert "(" in c["families"]["mesh.plan"]["last_shape"]
        assert isinstance(c["cache_sizes"], dict)
        t = _get(base, "/debug/timeline")
        assert t["displayTimeUnit"] == "ms"
        assert t["otherData"]["records"] > 0
        assert any(e["ph"] == "X" for e in t["traceEvents"])
        raw = _get(base, "/debug/timeline?view=raw&n=8")
        assert isinstance(raw, list) and len(raw) <= 8
        assert all("family" in r for r in raw)
        # the index names both
        idx = _get(base, "/debug")["endpoints"]
        assert "/debug/compiles" in idx and "/debug/timeline" in idx
        # /debug/metrics carries the summary section
        dm = _get(base, "/debug/metrics")
        assert dm["devprof"]["enabled"] is True
        assert dm["devprof"]["dispatches"] > 0
        assert "analytics" in dm["endpoints"]
    finally:
        srv.shutdown()


def test_debug_surfaces_honest_when_disarmed():
    n = Node(devprof=False)
    srv = make_server(n, "127.0.0.1", 0)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    base = f"http://127.0.0.1:{srv.server_address[1]}"
    try:
        # the observatory's sections are gone; `runtime` (where the
        # process runs) is served whether or not the profiler is armed
        comp = _get(base, "/debug/compiles")
        assert comp.pop("runtime")["platform"] == "cpu"
        assert comp == {"enabled": False}
        assert _get(base, "/debug/timeline") == {"enabled": False}
        assert _get(base, "/debug/metrics")["devprof"] == {
            "enabled": False}
    finally:
        srv.shutdown()
        n.close()


# ---------------------------------------------------------------------------
# satellites: per-subscription + analytics cost attribution
# ---------------------------------------------------------------------------

def test_subscription_costs_group_by_sub(node):
    sub = node.subscribe('{ q(func: has(name)) { name } }')
    try:
        ev = sub.next(5)
        assert ev["type"] == "init"
        # the initial eval ran through the cost ledger tagged with the
        # subscription id; /debug/top?group=sub apportions it
        top = node.cost_book.top(group="sub", endpoint="live")
        keys = [row["key"] for row in top["top"]]
        assert sub.id in keys, top
        row = top["top"][keys.index(sub.id)]
        assert row["records"] >= 1
        assert row["wall_ms"] > 0
        # re-evals after a delta keep attributing
        node.mutate(set_nquads='_:z <name> "zed" .', commit_now=True)
        assert sub.next(5)["type"] == "diff"
        top2 = node.cost_book.top(group="sub", endpoint="live")
        row2 = [r for r in top2["top"] if r["key"] == sub.id][0]
        assert row2["records"] >= row["records"]
    finally:
        sub.cancel()


def test_analytics_rides_the_cost_ledger(node):
    node.analytics("pagerank", "follows")
    top = node.cost_book.top(group="endpoint")
    keys = [row["key"] for row in top["top"]]
    assert "analytics" in keys, top
