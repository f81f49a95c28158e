"""End-to-end distributed tracing (obs/otrace.py): one trace id spans
client -> every group's serve_task -> Zero coordinator calls -> device
kernels, with parent/child links intact; traces export as Chrome
trace-event JSON (Perfetto-loadable, validated structurally); /metrics
serves a parseable Prometheus exposition; the slow-query log captures
plan + span tree for threshold-crossing queries."""

import json
import random
import urllib.request

import pytest

grpc = pytest.importorskip("grpc")

from dgraph_tpu.api.http import make_server
from dgraph_tpu.api.server import Node
from dgraph_tpu.coord.zero import Zero
from dgraph_tpu.coord.zero_service import serve_zero
from dgraph_tpu.obs import otrace, prom
from dgraph_tpu.parallel.client import ClusterClient
from dgraph_tpu.parallel.remote import serve_worker
from dgraph_tpu.query import task as taskmod
from dgraph_tpu.storage.store import Store
from dgraph_tpu.utils.schema import parse_schema

SCHEMA = """
    name: string @index(exact) .
    age: int @index(int) .
    follows: [uid] @reverse .
"""


def _mk_store():
    s = Store()
    for e in parse_schema(SCHEMA):
        s.set_schema(e)
    return s


@pytest.fixture
def wire_cluster():
    """2 worker groups + a zero, all over real loopback gRPC; name lives
    on group 0, follows/age on group 1, so a 2-hop query fans to both."""
    zero = Zero(2)
    zero.move_tablet("name", 0)
    zero.move_tablet("follows", 1)
    zero.move_tablet("age", 1)
    zsrv, zport, _zsvc = serve_zero(zero, "localhost:0")
    stores = [_mk_store(), _mk_store()]
    w0, p0 = serve_worker(stores[0], "localhost:0")
    w1, p1 = serve_worker(stores[1], "localhost:0")
    client = ClusterClient(f"localhost:{zport}",
                           {0: [f"localhost:{p0}"], 1: [f"localhost:{p1}"]},
                           span_sample=1.0, trace_rng=random.Random(7))
    client.mutate(set_nquads="""
        _:a <name> "ann" .
        _:b <name> "bob" .
        _:c <name> "cid" .
        _:a <age> "30" .
        _:b <age> "41" .
        _:a <follows> _:b .
        _:a <follows> _:c .
    """)
    yield client, (f"localhost:{p0}", f"localhost:{p1}"), (w0, w1)
    client.close()
    w0.stop(0)
    w1.stop(0)
    zsrv.stop(0)


def _links_intact(spans):
    ids = {s["span_id"] for s in spans}
    roots = [s for s in spans if not s["parent_id"]]
    assert len(roots) == 1, f"expected one root, got {roots}"
    for s in spans:
        if s["parent_id"]:
            assert s["parent_id"] in ids, \
                f"dangling parent {s['parent_id']} for {s['name']}"
    return roots[0]


def test_single_trace_spans_client_workers_zero_device(wire_cluster,
                                                       monkeypatch):
    client, addrs, _srvs = wire_cluster
    # force the device expand path for tiny frontiers so the trace carries
    # a real device-kernel span with transfer bytes
    monkeypatch.setattr(taskmod, "HOST_EXPAND_MAX", 0)
    out = client.query(
        '{ q(func: eq(name, "ann")) { name age follows { name } } }')
    assert out["q"][0]["name"] == "ann"
    assert len(out["q"][0]["follows"]) == 2

    idx = client.tracer.sink.index()
    rec = client.tracer.sink.get(
        next(r["trace_id"] for r in idx if r["root"] == "query"))
    spans = rec["spans"]
    # exactly one trace id across every span
    assert {s["trace_id"] for s in spans} == {rec["trace_id"]}
    root = _links_intact(spans)
    assert root["name"] == "query" and root["proc"] == "client"

    by_name = {}
    for s in spans:
        by_name.setdefault(s["name"], []).append(s)
    # client-side fan-out spans hit BOTH workers
    rpc_addrs = {s["attrs"]["addr"] for s in by_name["rpc:ServeTask"]}
    assert set(addrs) <= rpc_addrs
    # each worker's server span arrived over trailing metadata, with its
    # proc naming the worker
    worker_procs = {s["proc"] for s in by_name["serve_task"]}
    assert len(worker_procs) == 2
    # Zero coordinator calls are part of the same trace
    assert any(n.startswith("zero:") for n in by_name), by_name.keys()
    assert any(s["proc"] == "zero" for s in spans)
    # at least one device-kernel span with transfer bytes, under a worker
    kernels = by_name.get("device_kernel", [])
    assert kernels, f"no device span; names={sorted(by_name)}"
    assert any(k["attrs"].get("transfer_d2h_bytes", 0) > 0 for k in kernels)
    assert all(k["proc"].startswith("worker:") for k in kernels)
    # no span buffers left behind anywhere
    assert client.tracer.active_traces() == 0


def test_failed_fanout_leaks_no_spans(wire_cluster):
    client, _addrs, (w0, w1) = wire_cluster
    client.query('{ q(func: eq(name, "ann")) { name follows { name } } }')
    w1.stop(0)            # group 1 (follows/age) dies mid-cluster
    client.task_cache.clear()   # don't let cached tasks mask the dead group
    with pytest.raises(Exception):
        client.query(
            '{ q(func: eq(name, "bob")) { name follows { name } } }')
    # the root span finished with the error and the trace assembled —
    # nothing lingers in the per-trace buffers
    assert client.tracer.active_traces() == 0
    failed = [r for r in client.tracer.sink.index() if r["error"]]
    assert failed, "failed query should still produce an assembled trace"


def test_deterministic_sampling_with_injected_rng():
    class FlipFlop:
        def __init__(self):
            self.i = 0

        def random(self):
            self.i += 1
            return 0.0 if self.i % 2 else 0.99

        def getrandbits(self, n):
            return random.getrandbits(n)

    tr = otrace.Tracer(fraction=0.5, rng=FlipFlop())
    kinds = [bool(tr.root("q")) for _ in range(6)]
    assert kinds == [True, False, True, False, True, False]
    # finish the sampled roots so nothing leaks
    # (roots 0/2/4 were real spans)


def test_join_take_roundtrip_and_remote_merge():
    a = otrace.Tracer(fraction=1.0, proc="caller", rng=random.Random(1))
    b = otrace.Tracer(proc="callee", rng=random.Random(2))
    with a.root("query") as root:
        wire = otrace.wire_context()
        assert wire and wire.startswith(root.trace_id)
        with b.join(wire, "serve_task") as srv:
            with b.start("device", parent=srv):
                pass
        shipped = b.take(root.trace_id)
        assert len(shipped) == 2 and b.active_traces() == 0
        a.add_remote(shipped)
    rec = a.sink.get(root.trace_id)
    assert rec["nspans"] == 3
    tree = otrace.span_tree(rec)
    q = tree["tree"][0]
    assert q["name"] == "query"
    assert q["children"][0]["name"] == "serve_task"
    assert q["children"][0]["children"][0]["name"] == "device"


# ---------------------------------------------------------------------------
# embedded node: HTTP surface + Chrome JSON + Prometheus + slow log
# ---------------------------------------------------------------------------

@pytest.fixture
def http_node():
    node = Node(span_sample=1.0, trace_rng=random.Random(3),
                slow_query_ms=0.0001)   # everything is "slow": log fills
    node.alter(schema_text=SCHEMA)
    node.mutate(set_nquads='_:a <name> "ann" .\n_:b <name> "bob" .\n'
                           '_:a <follows> _:b .', commit_now=True)
    srv = make_server(node, "127.0.0.1", 0)
    import threading

    threading.Thread(target=srv.serve_forever, daemon=True).start()
    yield node, f"http://127.0.0.1:{srv.server_address[1]}"
    srv.shutdown()
    node.close()


def _get(base, path):
    with urllib.request.urlopen(base + path) as r:
        return r.status, r.read()


def _post(base, path, body):
    req = urllib.request.Request(base + path, data=body.encode(),
                                 method="POST")
    with urllib.request.urlopen(req) as r:
        return json.loads(r.read())


def test_chrome_trace_export_loads_structurally(http_node):
    node, base = http_node
    _post(base, "/query", '{ q(func: eq(name, "ann")) { name follows '
                          '{ name } } }')
    _closed(node, 1)     # the root span finishes after the answer is written
    st, body = _get(base, "/debug/traces")
    assert st == 200
    idx = json.loads(body)
    tid = next(r["trace_id"] for r in idx if r["root"] == "query")
    st, body = _get(base, f"/debug/traces/{tid}")
    assert st == 200
    ct = json.loads(body)
    # the Perfetto/chrome://tracing JSON object-format contract
    assert isinstance(ct["traceEvents"], list) and ct["traceEvents"]
    assert ct["otherData"]["trace_id"] == tid
    phases = {e["ph"] for e in ct["traceEvents"]}
    assert "X" in phases and "M" in phases
    for e in ct["traceEvents"]:
        assert {"name", "ph", "pid", "tid"} <= set(e)
        if e["ph"] == "X":
            assert e["ts"] >= 0 and e["dur"] > 0
    # thread names label the processes
    names = [e["args"]["name"] for e in ct["traceEvents"]
             if e["ph"] == "M" and e["name"] == "thread_name"]
    assert "node" in names
    # tree view renders too
    st, body = _get(base, f"/debug/traces/{tid}?view=tree")
    tree = json.loads(body)
    assert tree["tree"][0]["name"] == "query"
    # unknown id 404s
    with pytest.raises(urllib.error.HTTPError):
        _get(base, "/debug/traces/ffffffffffffffff")


def test_prometheus_exposition_parses(http_node):
    node, base = http_node
    _post(base, "/query", '{ q(func: has(name)) { name } }')
    st, body = _get(base, "/metrics")
    assert st == 200
    series = prom.parse(body.decode())      # raises on malformed output
    assert series["dgraph_num_queries_total"][0][1] >= 1
    # fixed-bucket histogram shape (ISSUE 13): cumulative le buckets +
    # _sum/_count — the OLD quantile-label summary rows are gone from
    # /metrics (they can't be aggregated across nodes; the ring
    # percentiles stay on /debug/metrics)
    buckets = series.get("dgraph_query_latency_s_bucket", [])
    assert buckets and any(lbl.get("le") == "+Inf" for lbl, _ in buckets)
    assert "dgraph_query_latency_s_count" in series
    assert not any("quantile" in lbl for samples in series.values()
                   for lbl, _ in samples)
    # bucket counts are cumulative and monotone
    vals = [v for lbl, v in buckets]
    assert vals == sorted(vals)
    # meters render as labeled endpoint gauges
    assert any(lbl.get("endpoint") == "query"
               for lbl, _ in series.get("dgraph_endpoint_qps", []))


def test_slow_query_log_captures_plan_and_tree(http_node):
    node, base = http_node
    _post(base, "/query", '{ q(func: eq(name, "ann")) { name follows '
                          '{ name } } }')
    _closed(node, 1)
    st, body = _get(base, "/debug/slow")
    entries = json.loads(body)
    assert entries, "threshold 0.1us should log every query"
    e = next(x for x in entries if x["root"] == "query")
    assert e["trace_id"] and e["elapsed_ms"] > 0
    assert e["query"].startswith("{ q(func:")
    assert e["plan"] is not None and "root_swaps" in e["plan"]
    names = set()

    def walk(nodes):
        for n in nodes:
            names.add(n["name"])
            walk(n.get("children", ()))

    walk(e["tree"])
    assert "query" in names and any(n.startswith("task:") for n in names)


def test_slow_query_log_jsonl_file(tmp_path):
    path = tmp_path / "slow.jsonl"
    node = Node(span_sample=1.0, trace_rng=random.Random(5),
                slow_query_ms=0.0001, slow_query_log=str(path))
    node.alter(schema_text=SCHEMA)
    node.mutate(set_nquads='_:a <name> "ann" .', commit_now=True)
    node.query('{ q(func: eq(name, "ann")) { name } }')
    node.close()
    lines = [json.loads(ln) for ln in path.read_text().splitlines()]
    assert any(e["root"] == "query" for e in lines)


def test_debug_index_names_new_endpoints(http_node):
    _node, base = http_node
    st, body = _get(base, "/debug")
    eps = json.loads(body)["endpoints"]
    for p in ("/debug/traces", "/debug/slow", "/metrics"):
        assert p in eps


def test_unsampled_query_costs_no_trace():
    # no slow log armed (an armed slow log force-samples every root)
    node = Node(span_sample=0.0)
    node.alter(schema_text=SCHEMA)
    node.mutate(set_nquads='_:a <name> "ann" .', commit_now=True)
    before = len(node.tracer.sink)
    node.query('{ q(func: has(name)) { name } }')
    assert len(node.tracer.sink) == before
    assert node.tracer.active_traces() == 0
    node.close()


def test_slow_log_fires_even_when_span_sampling_is_off():
    """An armed slow-query log force-samples roots: the threshold must be
    honored even at the production 1% (here 0%) span_sample default."""
    node = Node(span_sample=0.0, slow_query_ms=0.0001)
    node.alter(schema_text=SCHEMA)
    node.mutate(set_nquads='_:a <name> "ann" .', commit_now=True)
    node.query('{ q(func: eq(name, "ann")) { name } }')
    assert any(e["root"] == "query" for e in node.slow_log.recent())
    node.close()


def test_prom_level_shaped_totals_render_as_gauges():
    """pending/active '_total' names are inc/dec levels — a counter TYPE
    would make Prometheus read every decrease as a reset."""
    from dgraph_tpu.utils import metrics as metrics_mod

    text = prom.render(metrics_mod.Registry())
    assert "# TYPE dgraph_pending_queries_total gauge" in text
    assert "# TYPE dgraph_active_mutations_total gauge" in text
    assert "# TYPE dgraph_num_queries_total counter" in text


# ---------------------------------------------------------------------------
# the stage clock (obs/costs.py StageClock): one clock inside a request,
# read by /metrics counters, the sampled spans and the profiler's host plane
# ---------------------------------------------------------------------------

import threading
import time

from dgraph_tpu.obs import costs
from dgraph_tpu.query import recurse as recmod
from dgraph_tpu.query import shortest as shmod

CHAIN = "\n".join(f"<0x{i:x}> <follows> <0x{i + 1:x}> ." for i in range(1, 9)) \
    + '\n<0x1> <name> "ann" .\n<0x9> <name> "zed" .'
SHORTEST = ("{ p as shortest(from: 0x1, to: 0x6) { follows } "
            "r(func: uid(p)) { uid } }")
KHOP = ("{ var(func: uid(0x6)) @recurse(depth: 5) { v as follows } "
        "khop(func: uid(v)) { count(uid) } }")


def _chain_node(**kw):
    node = Node(**kw)
    node.alter(schema_text=SCHEMA)
    node.mutate(set_nquads=CHAIN, commit_now=True)
    return node


def _serve(node):
    srv = make_server(node, "127.0.0.1", 0)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    return srv, f"http://127.0.0.1:{srv.server_address[1]}"


def _closed(node, n, timeout=5.0):
    """Wait until `n` request clocks have closed: an HTTP client has its
    answer before the handler finishes the root span and flushes."""
    deadline = time.monotonic() + timeout
    c = node.metrics.counter("dgraph_stage_requests_total")
    while c.value < n and time.monotonic() < deadline:
        time.sleep(0.002)
    assert c.value == n, (c.value, n)


def _force_tier(monkeypatch, tier):
    """`shortest` on the Pallas kernel tier (interpret mode off-TPU) or on
    the Bellman-Ford tier, or `@recurse` on its kernel tier, on a graph
    far below every floor."""
    if tier == "recurse":
        monkeypatch.setattr(recmod, "KERNEL_MIN_EDGES", 0)
        return
    monkeypatch.setattr(shmod, "DEVICE_SSSP_MIN_EDGES", 0)
    monkeypatch.setattr(shmod, "SSSP_KERNEL_MIN",
                        0 if tier == "kernel" else 1 << 62)


TILE_CASES = {
    # case -> (query, tier, stages that must show, stages that must not)
    "parse_error": ("{ q(func: bogus~~ }", None, {"parse"}, {"exec"}),
    "result_cache_hit": ('{ q(func: eq(name, "ann")) { name } }', None,
                         {"parse", "plan"}, {"exec", "encode"}),
    "schema_request": ("schema {}", None, {"parse", "plan"}, {"exec"}),
    "shortest_kernel": (SHORTEST, "kernel",
                        {"parse", "plan", "exec", "exec.prep",
                         "dev.dispatch", "dev.wait", "dev.post", "encode"},
                        {"dev.window"}),
    "shortest_sssp": (SHORTEST, "sssp",
                      {"parse", "plan", "exec", "dev.dispatch", "dev.wait",
                       "dev.post", "encode"}, {"dev.window", "exec.prep"}),
    "khop_fused": (KHOP, "recurse",
                   {"parse", "plan", "exec", "exec.prep", "dev.dispatch",
                    "dev.wait", "dev.post", "encode"}, {"dev.window"}),
}


@pytest.mark.parametrize("case", sorted(TILE_CASES))
def test_stages_tile_the_entry_points_wall_time(case, monkeypatch):
    """Segments never overlap and leave no hole: the stages' nanoseconds
    add up to the time between the clock's opening and its closing, which
    is the entry point's own wall time but for opening and closing it."""
    q, tier, want, never = TILE_CASES[case]
    if tier:
        _force_tier(monkeypatch, tier)
    node = _chain_node(span_sample=0.0)
    try:
        if case == "result_cache_hit":
            node.query(q)                      # fill the tier
        if tier:
            node.query(q.replace("0x6", "0x5"))   # compile outside the clock
        closed = node.metrics.counter("dgraph_stage_requests_total")
        before = closed.value
        t0 = time.perf_counter_ns()
        with node.clocked("query", "owner") as clk:
            t1 = time.perf_counter_ns()
            try:
                node.query(q)
                failed = False
            except Exception:       # noqa: BLE001 — the parse-error case
                failed = True
            t2 = time.perf_counter_ns()
        t3 = time.perf_counter_ns()
    finally:
        node.close()
    assert failed == (case == "parse_error")
    total = sum(clk.ns.values())
    inner, outer = t2 - t1, t3 - t0
    assert inner <= total <= outer
    # within 2% of the owner's wall time, or by less than opening the
    # clock and flushing it cost on a loaded box
    assert outer - total <= max(0.02 * outer, 200_000), (clk.ns, outer)
    assert want <= set(clk.ns), clk.ns
    assert not never & set(clk.ns), clk.ns
    assert all(v >= 0 for v in clk.ns.values())
    # Node.query joined the open clock: one request, flushed by the owner
    assert closed.value == before + 1


def test_stage_closes_on_exception_and_restores_the_previous():
    clk = costs.StageClock("a", otrace.NULL_SPAN)
    with clk:
        with costs.stage("b"):
            with pytest.raises(ValueError):
                with costs.stage("c"):
                    assert clk._cur == "c"
                    raise ValueError("boom")
            assert clk._cur == "b"
        assert clk._cur == "a"
    assert set(clk.ns) == {"a", "b", "c"}
    assert costs.clock() is None
    # no clock open: a stage is a no-op
    with costs.stage("nowhere"):
        pass


def test_kernel_window_is_a_stage_and_restores_on_exception():
    clk = costs.StageClock("exec", otrace.NULL_SPAN)
    with clk, costs.scope(costs.CostLedger()) as lg:
        with costs.kernel("csr.expand"):
            assert clk._cur == "dev.window"
        assert clk._cur == "exec"
        with pytest.raises(RuntimeError):
            with costs.kernel("pb.bfs_dist", stage="dev.dispatch"):
                with costs.stage("dev.wait"):
                    raise RuntimeError("device fell over")
        assert clk._cur == "exec"
    assert {"exec", "dev.window", "dev.dispatch", "dev.wait"} == set(clk.ns)
    # one window, two clocks: the ledger's ms and the stages' ns agree
    dev_ns = clk.ns["dev.dispatch"] + clk.ns["dev.wait"]
    assert abs(lg.kernels["pb.bfs_dist"] * 1e6 - dev_ns) < 200_000
    assert lg.kernel_calls == {"csr.expand": 1, "pb.bfs_dist": 1}
    # a context copied to another thread sees the clock and leaves it alone
    clk2 = costs.StageClock("exec", otrace.NULL_SPAN)
    with clk2:
        import contextvars

        ctx = contextvars.copy_context()
        seen = []
        t = threading.Thread(target=lambda: ctx.run(
            lambda: (seen.append(costs.clock()),
                     costs.stage("dev.window").__enter__())))
        t.start()
        t.join()
        assert seen == [None] and clk2._cur == "exec"
        assert costs.clock() is clk2


class _Counting:
    """Stands in for a class and counts its constructions."""

    def __init__(self, real):
        self.real, self.n = real, 0

    def __call__(self, *a, **kw):
        self.n += 1
        return self.real(*a, **kw)


def test_unsampled_request_builds_no_span_and_no_annotation(monkeypatch):
    import jax.profiler

    spans = _Counting(otrace.Span)
    anns = _Counting(jax.profiler.TraceAnnotation)
    monkeypatch.setattr(otrace, "Span", spans)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", anns)
    _force_tier(monkeypatch, "sssp")
    for sample, some in ((0.0, False), (1.0, True)):
        node = _chain_node(span_sample=sample)
        srv, base = _serve(node)
        spans.n = anns.n = 0
        try:
            out = _post(base, "/query", SHORTEST)
            _closed(node, 1)
            traces = node.tracer.sink.index()
        finally:
            srv.shutdown()
            node.close()
        assert out["data"]["_path_"]
        assert bool(spans.n) == some and bool(anns.n) == some, \
            (sample, spans.n, anns.n)
        if some:        # one annotation a segment, and only segments:
            #             never the root, task:* or device_kernel spans —
            #             nor http.accept and http.head, which were over
            #             when the clock opened: spans only
            rec = node.tracer.sink.get(traces[0]["trace_id"])
            kinds = [s["kind"] for s in rec["spans"]]
            assert anns.n == kinds.count("stage") - 2 > 0
            assert spans.n == len(kinds) - kinds.count("stage") >= 2


def test_sampled_request_holds_its_stage_spans(monkeypatch):
    _force_tier(monkeypatch, "kernel")
    monkeypatch.setattr(costs, "CPU_EVERY", 1)     # every request's CPU
    node = _chain_node(span_sample=1.0, trace_rng=random.Random(5))
    srv, base = _serve(node)
    try:
        node.query(SHORTEST.replace("0x6", "0x5"))       # compile first
        _post(base, "/query", SHORTEST)
        _closed(node, 2)
        rec = node.tracer.sink.get(node.tracer.sink.index(1)[0]["trace_id"])
    finally:
        srv.shutdown()
        node.close()
    spans = rec["spans"]
    root = _links_intact(spans)
    assert root["name"] == "query" and rec["root"] == "query"
    assert root["attrs"]["query"].startswith("{ p as shortest")
    assert [s["name"] for s in spans].count("query") == 1
    assert {s["trace_id"] for s in spans} == {rec["trace_id"]}
    stages = sorted((s for s in spans if s["kind"] == "stage"),
                    key=lambda s: s["start"])
    names = [s["name"] for s in stages]
    # the clock reaches back to the accept; then the handler's own time is
    # http.read: the body before the query, and its latency histogram
    # after the answer is written
    assert names[:3] == ["http.accept", "http.head", "http.read"] \
        and names[-2:] == ["http.write", "http.read"]
    assert [s["parent_id"] for s in stages[:2]] == [root["span_id"]] * 2
    # every segment says what its thread worked of it: none more than it
    # lasted (but for a clock's tick), the hand-over between two threads
    # nothing by definition
    assert stages[0]["attrs"] == {"cpu_us": 0}
    for s in stages:
        assert 0 <= s["attrs"]["cpu_us"] <= s["dur"] * 1e6 + 1000, s
    order = [names.index(n) for n in ("parse", "exec", "exec.prep",
                                      "dev.dispatch", "dev.wait",
                                      "dev.post", "encode")]
    assert order == sorted(order), names
    eps = 200e-6       # start is the wall clock, dur the monotonic one
    for a, b in zip(stages, stages[1:]):
        assert a["start"] + a["dur"] <= b["start"] + eps, (a, b)
    assert root["start"] - eps <= stages[0]["start"]
    assert stages[-1]["start"] + stages[-1]["dur"] <= \
        root["start"] + root["dur"] + eps
    dk = next(s for s in spans if s["name"] == "device_kernel")
    for s in stages:
        if s["name"].startswith("dev."):
            assert s["parent_id"] == dk["span_id"]
            assert dk["start"] - eps <= s["start"] and \
                s["start"] + s["dur"] <= dk["start"] + dk["dur"] + eps


def test_metrics_carry_stage_kernel_and_startup_series(monkeypatch):
    from dgraph_tpu.__main__ import _record_startup

    _force_tier(monkeypatch, "kernel")
    monkeypatch.setattr(taskmod, "HOST_EXPAND_MAX", 0)   # csr.expand window
    monkeypatch.setattr(costs, "CPU_EVERY", 1)     # every request's CPU
    node = _chain_node(span_sample=0.0, planner=False)
    srv, base = _serve(node)
    try:
        _post(base, "/query", SHORTEST)
        _post(base, "/query", '{ q(func: uid(0x1, 0x2)) { follows { uid } } }')
        _closed(node, 2)
        _record_startup(node, 1.25, [10.0, 10.5, 12.0, 12.25])
        series = prom.parse(_get(base, "/metrics")[1].decode())
    finally:
        srv.shutdown()
        node.close()

    def labelled(name, label):
        return {lb[label]: v for lb, v in series[name]}

    stage_us = labelled("dgraph_stage_us_total", "stage")
    assert {"http.read", "parse", "plan", "exec", "exec.prep",
            "dev.dispatch", "dev.wait", "dev.post", "dev.window", "encode",
            "http.write"} <= set(stage_us)
    assert {"http.accept", "http.head"} <= set(stage_us)
    # the two waits of a request that shares the device show at 0: one
    # request at a time never queues for the gate or rides a batch; nor
    # did a full collection fall into these two requests
    assert stage_us.pop("batch.wait") == stage_us.pop("gate.wait") == \
        stage_us.pop("gc") == 0
    assert all(v > 0 and v == int(v) for v in stage_us.values())
    # the CPU series: the same stages (one whose CPU rounds to no whole
    # microsecond shows nothing), never more than the wall but for a tick
    cpu_us = labelled("dgraph_stage_cpu_us_total", "stage")
    assert cpu_us["http.accept"] == cpu_us["gc"] == 0 < cpu_us["http.head"]
    assert set(cpu_us) <= set(stage_us) | {"batch.wait", "gate.wait", "gc"}
    assert cpu_us["exec"] > 0 and cpu_us["parse"] > 0
    assert sum(cpu_us.values()) <= sum(stage_us.values()) + 2000
    assert series["dgraph_stage_requests_total"][0][1] == \
        series["dgraph_stage_cpu_requests_total"][0][1] == 2
    kernel_us = labelled("dgraph_kernel_us_total", "kernel")
    calls = labelled("dgraph_kernel_calls_total", "kernel")
    assert calls["pb.bfs_dist"] == 1 and kernel_us["pb.bfs_dist"] > 0
    assert set(kernel_us) == set(calls) and len(calls) >= 2
    # one window, timed by the ledger and by the stage clock
    dev = sum(stage_us[s] for s in ("dev.dispatch", "dev.wait", "dev.post"))
    assert abs(kernel_us["pb.bfs_dist"] - dev) <= max(0.02 * dev, 200)
    assert labelled("dgraph_startup_ms", "phase") == {
        "import": 1250, "backend_init": 500, "store_open": 1500,
        "listen": 250}


def test_fused_recurse_span_says_what_the_traversal_did(monkeypatch):
    """The pb.recurse_fused window is split like pb.bfs_dist's, and its
    device_kernel span carries depth, levels_live and reached."""
    _force_tier(monkeypatch, "recurse")
    node = _chain_node(span_sample=1.0, trace_rng=random.Random(5))
    srv, base = _serve(node)
    try:
        node.query(KHOP.replace("0x6", "0x5"))            # compile first
        out = _post(base, "/query", KHOP)
        _closed(node, 2)
        rec = node.tracer.sink.get(node.tracer.sink.index(1)[0]["trace_id"])
        series = prom.parse(_get(base, "/metrics")[1].decode())
    finally:
        srv.shutdown()
        node.close()
    # 0x6 -> 0x7 -> 0x8 -> 0x9, which has no out-edge: the fourth level's
    # frontier is {0x9}, the fifth's is empty
    assert out["data"] == {"khop": [{"count": 3}]}
    spans = rec["spans"]
    dk = [s for s in spans if s["name"] == "device_kernel"]
    assert [s["attrs"]["kernel"] for s in dk] == ["pb.recurse_fused"]
    attrs = dk[0]["attrs"]
    assert (attrs["depth"], attrs["levels_live"], attrs["reached"]) == \
        (5, 4, 3)
    inside = [s["name"] for s in sorted(
        (s for s in spans if s["kind"] == "stage"
         and s["parent_id"] == dk[0]["span_id"]), key=lambda s: s["start"])]
    # a scope that ends hands back to the one around it for an instant
    assert list(dict.fromkeys(inside))[:3] == ["dev.dispatch", "dev.wait",
                                               "dev.post"]
    assert "dev.window" not in inside
    levels = {lb["state"]: v
              for lb, v in series["dgraph_recurse_levels_total"]}
    # two requests: from 0x5 all five levels hold a vertex
    assert levels == {"live": 9, "empty": 1}
    assert series["dgraph_recurse_materialized_total"][0][1] == 0


def test_first_hop_is_counted_by_mode_and_named_on_the_span(monkeypatch):
    """dgraph_bfs_first_hop_total{mode=} grows by one a search, under the
    branch the root's out-degree picks; the device_kernel span says the
    same; the modes sum to the pb.bfs_dist windows."""
    from dgraph_tpu.ops import pallas_bfs as pb

    _force_tier(monkeypatch, "kernel")
    monkeypatch.setattr(pb, "FIRST_HOP_CAP", 2)
    node = Node(span_sample=1.0, trace_rng=random.Random(13))
    node.alter(schema_text=SCHEMA)
    node.mutate(set_nquads=CHAIN + "\n<0x2> <follows> <0x7> ."
                "\n<0x2> <follows> <0x8> .", commit_now=True)
    srv, base = _serve(node)

    def modes():
        series = prom.parse(_get(base, "/metrics")[1].decode())
        got = {lb["mode"]: v
               for lb, v in series.get("dgraph_bfs_first_hop_total", [])}
        calls = {lb["kernel"]: v
                 for lb, v in series.get("dgraph_kernel_calls_total", [])}
        return got, calls.get("pb.bfs_dist", 0)

    try:
        # both modes show from the start, at 0: a reader can tell "no
        # search yet" from "a program without the counter"
        want = {"push": 0, "stream": 0}
        assert modes() == (want, 0)
        # 0x2 has three out-edges, over the cap of 2; the others one
        for n, (src, dst, mode) in enumerate(
                [("0x1", "0x6", "push"), ("0x2", "0x5", "stream"),
                 ("0x3", "0x6", "push"), ("0x2", "0x8", "stream")], 1):
            out = _post(base, "/query", SHORTEST.replace("0x1", src)
                        .replace("0x6", dst))
            assert out["data"]["_path_"]
            _closed(node, n)
            want[mode] += 1
            got, calls = modes()
            assert got == want and calls == n == sum(got.values())
            rec = node.tracer.sink.get(
                node.tracer.sink.index(1)[0]["trace_id"])
            dk = [s for s in rec["spans"] if s["name"] == "device_kernel"]
            assert [s["attrs"]["first_hop"] for s in dk] == [mode]
            assert dk[0]["attrs"]["kernel"] == "pb.bfs_dist"
    finally:
        srv.shutdown()
        node.close()


def test_server_latency_parts_sum_below_the_total(http_node):
    _node, base = http_node
    out = _post(base, "/query", '{ q(func: has(name)) { name follows '
                                '{ name } } }')
    lat = out["extensions"]["server_latency"]
    assert set(lat) == {"parsing_ns", "processing_ns", "encoding_ns",
                        "total_ns"}
    assert lat["parsing_ns"] > 0 and lat["processing_ns"] > 0 \
        and lat["encoding_ns"] > 0
    assert lat["parsing_ns"] + lat["processing_ns"] + lat["encoding_ns"] \
        <= lat["total_ns"]


def test_grpc_handler_owns_the_clock_and_fills_latency():
    from dgraph_tpu.api.grpc_server import DgraphService
    from dgraph_tpu.protos import api_pb2 as pb

    node = _chain_node(span_sample=1.0, trace_rng=random.Random(9))
    try:
        resp = DgraphService(node).query(
            pb.Request(query='{ q(func: has(name)) { name } }',
                       read_only=True), None)
        rec = node.tracer.sink.get(node.tracer.sink.index(1)[0]["trace_id"])
    finally:
        node.close()
    lat = resp.latency
    assert lat.parsing_ns > 0 and lat.processing_ns > 0 \
        and lat.encoding_ns > 0
    assert lat.parsing_ns + lat.processing_ns + lat.encoding_ns \
        <= lat.total_ns
    assert rec["root"] == "query"
    assert {"grpc", "parse", "plan", "exec", "encode"} <= \
        {s["name"] for s in rec["spans"] if s["kind"] == "stage"}


def test_bfs_dist_lowering_holds_the_scope_names():
    import numpy as np

    from dgraph_tpu.ops import pallas_bfs as pb

    subjects = np.arange(1, 9, dtype=np.int64)
    indptr = np.arange(9, dtype=np.int64)
    indices = np.arange(2, 10, dtype=np.int64)
    g = pb.prep_pull(subjects, indptr, indices, 10)
    text = pb.bfs_dist.lower(
        g.in_src_pad, g.in_src_pad_d, g.in_iptr_rank, g.row_ends,
        g.subjects, g.in_subjects, g.fwd_indptr, g.fwd_dst_rank,
        np.asarray([1, 0, 0, 4], dtype=np.int32),
        chunks=g.chunks, chunks_d=g.chunks_d
    ).as_text(debug_info=True)
    for scope in ("seed", "push", "prefix", "bounds", "visit"):
        assert f"/{scope}/" in text, scope
    # one output, the uint8 labels; no pack of bit planes behind the loop
    assert "/pack_dist/" not in text


# the behaviours the removed breadcrumb store (utils/metrics.TraceStore)
# was tested for, on the span tracer that replaces it

def test_every_error_path_leaves_its_error_on_a_finished_trace():
    node = Node(span_sample=1.0, trace_rng=random.Random(11))
    node.alter(schema_text=SCHEMA)
    with pytest.raises(Exception):
        node.query("{ q(func: bogus~~ }")                  # parse error
    with pytest.raises(Exception):
        node.mutate(set_nquads='<0x1> <name> "x" .', start_ts=999999)
    with pytest.raises(Exception):
        node.alter(schema_text="name: notatype .")
    kinds = {(t["root"], t["error"] != "") for t in node.tracer.sink.index()}
    assert {("query", True), ("mutate", True), ("alter", True)} <= kinds
    assert ("alter", False) in kinds
    # the failed query still shows where its time went
    tid = next(t["trace_id"] for t in node.tracer.sink.index()
               if t["root"] == "query")
    assert "parse" in {s["name"] for s in node.tracer.sink.get(tid)["spans"]}
    assert node.tracer.active_traces() == 0
    # over HTTP the handler answers 400 and swallows the exception: the
    # error still lands on the root span
    srv, base = _serve(node)
    try:
        with pytest.raises(urllib.error.HTTPError):
            _post(base, "/query", "{ q(func: bogus~~ }")
        _closed(node, 2)
        newest = node.tracer.sink.index(1)[0]
    finally:
        srv.shutdown()
        node.close()
    assert newest["root"] == "query" and newest["error"]
    assert node.tracer.active_traces() == 0


def test_span_sampling_off_records_nothing():
    node = Node(span_sample=0.0)
    node.alter(schema_text="name: string .")
    node.mutate(set_nquads='<0x1> <name> "x" .', commit_now=True)
    node.query("{ q(func: has(name)) { name } }")
    assert len(node.tracer.sink) == 0 and node.tracer.active_traces() == 0
    node.close()


def test_owner_takes_the_one_sampling_decision_from_an_injected_rng():
    class Seq:
        def __init__(self, vals):
            self.vals, self.asked, self.ids = list(vals), 0, 0

        def random(self):
            self.asked += 1
            return self.vals.pop(0)

        def getrandbits(self, n):
            self.ids += 1
            return self.ids

    rng = Seq([0.1, 0.9, 0.4, 0.6])
    node = _chain_node(span_sample=0.0)
    node.tracer.fraction, node.tracer.rng = 0.5, rng
    srv, base = _serve(node)
    try:
        before = len(node.tracer.sink)
        picks = []
        for i in range(4):
            # an upsert block runs mutate + commit under the query: nothing
            # below the owner rolls the dice again
            _post(base, "/query", 'upsert { query { v as var(func: eq(name, '
                  '"ann")) } mutation { set { uid(v) <age> "%d" . } } }' % i)
            _closed(node, i + 1)
            picks.append(len(node.tracer.sink) - before)
            before = len(node.tracer.sink)
    finally:
        srv.shutdown()
        node.close()
    assert picks == [1, 0, 1, 0]
    assert rng.asked == 4


# ---------------------------------------------------------------------------
# work beside waiting, the stages before do_POST, the collector (PR 38)
# ---------------------------------------------------------------------------

import gc
import http.client

from dgraph_tpu.utils.metrics import Registry


def _labelled(series, name, label):
    return {lb[label]: v for lb, v in series[name]}


def test_a_stage_that_sleeps_has_no_cpu_and_one_that_spins_has_its_wall():
    reg = Registry()
    clk = costs.StageClock("owner", otrace.NULL_SPAN, reg, cpu=True)
    with clk:
        with costs.stage("sleeps"):
            time.sleep(0.05)
        with costs.stage("spins"):
            end = time.perf_counter() + 0.05
            while time.perf_counter() < end:
                pass
    ns, cpu = clk.ns, clk.cpu
    assert set(cpu) == set(ns) == {"owner", "sleeps", "spins"}
    assert ns["sleeps"] >= 50e6 and cpu["sleeps"] < 5e6
    # a spinning thread is on a core for its wall time, less what a loaded
    # box takes away from it
    assert ns["spins"] >= 50e6 and cpu["spins"] > 0.5 * ns["spins"]
    tick = 1_000_000
    for s in ns:
        assert 0 <= cpu[s] <= ns[s] + tick, (s, cpu[s], ns[s])
    assert sum(cpu.values()) <= sum(ns.values()) + tick
    series = prom.parse(prom.render(reg))
    wall_us = _labelled(series, "dgraph_stage_us_total", "stage")
    cpu_us = _labelled(series, "dgraph_stage_cpu_us_total", "stage")
    assert wall_us["sleeps"] == ns["sleeps"] // 1000
    assert cpu_us["spins"] == cpu["spins"] // 1000
    assert cpu_us.get("sleeps", 0) == cpu["sleeps"] // 1000 < 5000
    assert series["dgraph_stage_requests_total"][0][1] == \
        series["dgraph_stage_cpu_requests_total"][0][1] == 1


def test_one_request_in_cpu_every_reads_the_cpu_clock(monkeypatch):
    """The CPU clock is a system call a read: one request in CPU_EVERY
    pays it, and says so in its own count; the wall clock is every
    request's."""
    import itertools

    monkeypatch.setattr(costs, "CPU_EVERY", 4)
    monkeypatch.setattr(costs, "_cpu_turns", itertools.count())
    node = _chain_node(span_sample=1.0, trace_rng=random.Random(23))
    try:
        for _ in range(8):
            node.query('{ q(func: has(name)) { name } }')
        series = prom.parse(prom.render(node.metrics))
        recs = [node.tracer.sink.get(t["trace_id"])
                for t in node.tracer.sink.index(8)]
    finally:
        node.close()
    assert series["dgraph_stage_requests_total"][0][1] == 8
    assert series["dgraph_stage_cpu_requests_total"][0][1] == 2
    wall_us = _labelled(series, "dgraph_stage_us_total", "stage")
    cpu_us = _labelled(series, "dgraph_stage_cpu_us_total", "stage")
    # two requests' CPU against eight requests' wall
    assert 0 < cpu_us["plan"] < wall_us["plan"]
    with_cpu = [all("cpu_us" in s["attrs"] for s in r["spans"]
                    if s["kind"] == "stage") for r in recs]
    without = [all(s["attrs"] == {} for s in r["spans"]
                   if s["kind"] == "stage") for r in recs]
    assert with_cpu.count(True) == 2 and without.count(True) == 6
    # a clock told its turn does not take one
    assert costs.StageClock("a", otrace.NULL_SPAN, cpu=False).cpu is None
    assert costs.StageClock("a", otrace.NULL_SPAN, cpu=True).cpu == {}
    assert next(costs._cpu_turns) == 8


def test_clock_is_backfilled_with_the_stages_before_its_owner():
    """`before`: each segment runs to the next one's start, the last to
    the clock's opening; only the last can have CPU time."""
    t0, c0 = time.perf_counter_ns(), time.thread_time_ns()
    end = time.perf_counter() + 0.01
    while time.perf_counter() < end:
        pass
    node = _chain_node(span_sample=1.0, trace_rng=random.Random(11))
    try:
        before = (("http.accept", t0 - 3_000_000, None),
                  ("http.head", t0, c0))
        with node.clocked("query", "http.read", before, True) as clk:
            opened = clk._t0
        rec = node.tracer.sink.get(node.tracer.sink.index(1)[0]["trace_id"])
    finally:
        node.close()
    assert clk.ns["http.accept"] == 3_000_000 and clk.cpu["http.accept"] == 0
    assert clk.ns["http.head"] == opened - t0 >= 10e6
    assert 5e6 < clk.cpu["http.head"] <= clk.ns["http.head"] + 1e6
    root = _links_intact(rec["spans"])
    stages = sorted((s for s in rec["spans"] if s["kind"] == "stage"),
                    key=lambda s: s["start"])
    assert [s["name"] for s in stages] == ["http.accept", "http.head",
                                           "http.read"]
    eps = 200e-6
    assert abs(root["start"] - stages[0]["start"]) < eps
    assert root["dur"] >= 0.013
    assert stages[-1]["start"] + stages[-1]["dur"] <= \
        root["start"] + root["dur"] + eps


def test_http_round_trip_grows_accept_and_head_inside_the_root_span(
        monkeypatch):
    monkeypatch.setattr(costs, "CPU_EVERY", 1)     # every request's CPU
    node = _chain_node(span_sample=1.0, trace_rng=random.Random(13))
    srv, base = _serve(node)
    try:
        _post(base, "/query", '{ q(func: has(name)) { name } }')
        _closed(node, 1)
        series = prom.parse(_get(base, "/metrics")[1].decode())
        rec = node.tracer.sink.get(node.tracer.sink.index(1)[0]["trace_id"])
    finally:
        srv.shutdown()
        node.close()
    wall_us = _labelled(series, "dgraph_stage_us_total", "stage")
    cpu_us = _labelled(series, "dgraph_stage_cpu_us_total", "stage")
    assert wall_us["http.accept"] > 0 and wall_us["http.head"] > 0
    assert cpu_us["http.accept"] == 0 < cpu_us["http.head"] <= \
        wall_us["http.head"] + 1000
    # the loop's own counters: the query's connection and this scrape's
    assert series["dgraph_http_connections_total"][0][1] == 2
    assert series["dgraph_http_accept_loop_us_total"][0][1] > 0
    assert series["dgraph_process_cpu_seconds_total"][0][1] > 0
    root = _links_intact(rec["spans"])
    eps = 200e-6
    stages = [s for s in rec["spans"] if s["kind"] == "stage"]
    first = min(stages, key=lambda s: s["start"])
    assert first["name"] == "http.accept"
    assert root["start"] <= first["start"] + eps
    for s in stages:
        assert root["start"] - eps <= s["start"] and \
            s["start"] + s["dur"] <= root["start"] + root["dur"] + eps, s
    # the envelope's split is the clock's as before: no new field
    tree = otrace.span_tree(rec)["tree"]
    assert len(tree) == 1 and tree[0]["name"] == "query"
    assert {"http.accept", "http.head"} <= \
        {c["name"] for c in tree[0]["children"]}
    assert otrace.chrome_trace(rec)["traceEvents"]


def test_second_request_of_a_kept_alive_connection_has_no_accept():
    node = _chain_node(span_sample=1.0, trace_rng=random.Random(17))
    srv, base = _serve(node)
    # http.server speaks HTTP/1.0 and closes after every answer unless its
    # handler class says 1.1 (every answer here has a Content-Length)
    srv.RequestHandlerClass.protocol_version = "HTTP/1.1"
    conn = http.client.HTTPConnection("127.0.0.1", srv.server_address[1])
    accept_us = node.metrics.keyed("dgraph_stage_us_total")
    try:
        seen = []
        for i in (1, 2):
            conn.request("POST", "/query", body='{ q(func: has(name)) '
                                                '{ name } }')
            assert conn.getresponse().read()
            _closed(node, i)
            seen.append((accept_us.get("http.accept"),
                         accept_us.get("http.head")))
        recs = [node.tracer.sink.get(t["trace_id"])
                for t in node.tracer.sink.index(2)]      # newest first
    finally:
        conn.close()
        srv.shutdown()
        node.close()
    assert srv.connections == 1
    (a1, h1), (a2, h2) = seen
    assert a1 > 0 and a2 == a1            # one accept, charged once
    assert h2 > h1 > 0                    # a head a request
    names = [[s["name"] for s in sorted(r["spans"],
                                        key=lambda s: s["start"])
              if s["kind"] == "stage"] for r in recs]
    assert names[1][:3] == ["http.accept", "http.head", "http.read"]
    assert names[0][:2] == ["http.head", "http.read"]
    # the later request's head starts at its request line, not at the
    # connection's set-up: it cannot hold the first request
    heads = [next(s for s in r["spans"] if s["name"] == "http.head")
             for r in recs]
    first_root = _links_intact(recs[1]["spans"])
    assert heads[0]["start"] >= first_root["start"] + first_root["dur"] - 1e-3


def test_full_collection_is_counted_timed_and_a_stage_of_its_request():
    pauses = costs.GcPauses()
    reg = Registry()
    node = _chain_node(span_sample=1.0, trace_rng=random.Random(19))
    pauses.install()
    try:
        assert pauses in gc.callbacks
        pauses.install()                          # once only
        assert gc.callbacks.count(pauses) == 1
        before = list(pauses.collections)
        gc.collect()                              # no clock open: counted
        assert pauses.collections[2] == before[2] + 1
        assert pauses.pause_ns[2] > 0
        with node.clocked("query", "owner", cpu=True) as clk:
            with costs.stage("exec"):
                gc.collect()
                assert clk._cur == "exec"         # back where it was
            gc.collect(0)                         # a young one: no stage
        rec = node.tracer.sink.get(node.tracer.sink.index(1)[0]["trace_id"])
    finally:
        pauses.remove()
        node.close()
    assert pauses not in gc.callbacks
    assert pauses.collections[2] == before[2] + 2
    assert pauses.collections[0] >= before[0] + 1
    assert 0 < clk.ns["gc"] <= pauses.pause_ns[2]
    assert clk.cpu["gc"] > 0
    stages = [s["name"] for s in sorted(rec["spans"],
                                        key=lambda s: s["start"])
              if s["kind"] == "stage"]
    assert stages == ["owner", "exec", "gc", "exec", "owner"]
    root = _links_intact(rec["spans"])
    ev = [e for e in root["events"] if e["name"] == "gc"]
    assert len(ev) == 1 and ev[0]["attrs"]["generation"] == 2
    assert ev[0]["attrs"]["ms"] > 0 and "collected" in ev[0]["attrs"]
    pauses.publish(reg)
    series = prom.parse(prom.render(reg))
    count = _labelled(series, "dgraph_gc_collections_total", "generation")
    pause = _labelled(series, "dgraph_gc_pause_us_total", "generation")
    assert set(count) == set(pause) == {"0", "1", "2"}
    assert count["2"] == pauses.collections[2]
    assert pause["2"] == pauses.pause_ns[2] // 1000 > 0


def test_collector_hook_takes_no_lock_its_thread_may_hold():
    """A collection starts at any allocation, also one made under the
    metrics registry's lock (not re-entrant): the hook must come back."""
    pauses = costs.GcPauses()
    reg = Registry()
    done = []

    locks = [reg._lock, reg.counter("dgraph_stage_requests_total")._lock,
             reg.keyed_gauges["dgraph_stage_us_total"]._lock,
             reg.keyed_gauges["dgraph_gc_pause_us_total"]._lock]

    def collect_under_the_locks():
        clk = costs.StageClock("owner", otrace.NULL_SPAN)
        with clk, locks[0], locks[1], locks[2], locks[3]:
            gc.collect()
            done.append(dict(clk.ns))

    pauses.install()
    try:
        t = threading.Thread(target=collect_under_the_locks, daemon=True)
        t.start()
        t.join(timeout=20)
        assert not t.is_alive(), "gc.collect() under the registry's lock hung"
    finally:
        pauses.remove()
    assert done and done[0]["gc"] > 0
    assert pauses.collections[2] >= 1


NEW_AT_ZERO = {
    "dgraph_stage_cpu_us_total": ("stage", {"batch.wait", "gate.wait",
                                            "http.accept", "http.head",
                                            "gc"}),
    "dgraph_stage_us_total": ("stage", {"batch.wait", "gate.wait",
                                        "http.accept", "http.head", "gc"}),
    "dgraph_gc_pause_us_total": ("generation", {"0", "1", "2"}),
    "dgraph_gc_collections_total": ("generation", {"0", "1", "2"}),
    "dgraph_stage_cpu_requests_total": None,
    "dgraph_http_connections_total": None,
    "dgraph_http_accept_loop_us_total": None,
    "dgraph_process_cpu_seconds_total": None,
}


@pytest.mark.parametrize("name", sorted(NEW_AT_ZERO))
def test_new_series_show_at_zero_on_a_fresh_node(name):
    node = Node()
    try:
        series = prom.parse(prom.render(node.metrics))
    finally:
        node.close()
    if NEW_AT_ZERO[name] is None:
        assert series[name] == [({}, 0.0)]
    else:
        label, keys = NEW_AT_ZERO[name]
        assert _labelled(series, name, label) == dict.fromkeys(keys, 0.0)
