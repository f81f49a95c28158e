"""Observability: dgraph_* counters, latency histograms, request traces,
and the /debug HTTP surface (reference: x/metrics.go, net/trace sampling in
edgraph/server.go:289,388)."""

import json
import threading
import urllib.request

import pytest

from dgraph_tpu.api.http import make_server
from dgraph_tpu.api.server import Node
from dgraph_tpu.coord.zero import TxnConflict
from dgraph_tpu.obs import prom
from dgraph_tpu.utils import metrics


def test_counters_and_latency():
    n = Node()
    n.alter(schema_text="name: string @index(exact) .")
    n.mutate(set_nquads='_:a <name> "m" .', commit_now=True)
    n.query('{ q(func: eq(name, "m")) { name } }')
    c = n.metrics.counters
    assert c["dgraph_num_queries_total"].value == 1
    assert c["dgraph_num_mutations_total"].value == 1
    assert c["dgraph_num_commits_total"].value == 1
    assert c["dgraph_num_alters_total"].value == 1
    assert c["dgraph_posting_writes_total"].value > 0
    assert c["dgraph_posting_reads_total"].value > 0
    assert c["dgraph_pending_queries_total"].value == 0   # dec in finally
    h = n.metrics.histograms["dgraph_query_latency_s"].snapshot()
    assert h["count"] == 1 and h["p50"] > 0


def test_abort_counter():
    n = Node()
    n.alter(schema_text="name: string @index(exact) .")
    t1, t2 = n.new_txn(), n.new_txn()
    n.mutate(set_nquads='<0x9> <name> "x" .', start_ts=t1.start_ts)
    n.mutate(set_nquads='<0x9> <name> "y" .', start_ts=t2.start_ts)
    n.commit(t1.start_ts)
    with pytest.raises(TxnConflict):
        n.commit(t2.start_ts)
    assert n.metrics.counters["dgraph_num_aborts_total"].value == 1


def test_debug_http_endpoints():
    n = Node()
    n.alter(schema_text="name: string @index(exact) .")
    srv = make_server(n, "127.0.0.1", 0)
    port = srv.server_address[1]
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    try:
        body = json.dumps({"query": '{ q(func: has(name)) { name } }'}).encode()
        urllib.request.urlopen(urllib.request.Request(
            f"http://127.0.0.1:{port}/query", body,
            {"Content-Type": "application/json"}), timeout=5).read()
        v = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/debug/vars", timeout=5).read())
        assert v["dgraph_num_queries_total"] >= 1
        assert "dgraph_query_latency_s" in v
    finally:
        srv.shutdown()


def test_histogram_percentiles():
    h = metrics.Histogram(cap=100)
    for i in range(1, 101):
        h.observe(float(i))
    s = h.snapshot()
    assert s["count"] == 100 and s["p50"] == 51.0 and s["max"] == 100.0


def test_meter_rate_prunes_expired_marks():
    m = metrics.Meter(window=10.0, cap=8192)
    import time as _time

    now = _time.monotonic()
    with m._lock:
        # 500 expired marks + 3 live ones, planted directly in the ring
        for dt in range(500):
            m._ring.append(now - 20.0 - dt * 0.01)
        for _ in range(3):
            m._ring.append(now)
    assert m.rate() == pytest.approx(3 / 10.0)
    # expired timestamps were dropped from the ring, not rescanned forever
    assert len(m._ring) == 3
    # a NARROWER window must not evict marks the default window still needs
    with m._lock:
        m._ring.appendleft(now - 5.0)       # inside 10s, outside 1s
    assert m.rate(window=1.0) == pytest.approx(3 / 1.0)
    assert len(m._ring) == 4
    assert m.rate() == pytest.approx(4 / 10.0)


@pytest.mark.parametrize("op", ["set", "inc", "inc_many"])
def test_keyed_gauge_keeps_its_closed_keys_at_zero(op):
    """A zero drops its key, except a key of the closed set the gauge was
    made with: those show from the start and never leave."""
    g = metrics.KeyedGauge(labels=("mode",), keep=("push", "stream"))
    assert g.snapshot() == {"push": 0, "stream": 0}

    def put(key, n):        # move `key` by n from where it stands
        if op == "set":
            g.set(key, g.get(key) + n)
        elif op == "inc":
            g.inc(key, n)
        else:
            g.inc_many({key: n})

    for key in ("push", "other"):
        put(key, 2)
        assert g.get(key) == 2
        put(key, -2)
    assert g.snapshot() == {"push": 0, "stream": 0}      # "other" dropped
    text = prom.render(metrics.Registry())
    assert 'dgraph_bfs_first_hop_total{mode="push"} 0' in text
    assert 'dgraph_bfs_first_hop_total{mode="stream"} 0' in text
    assert 'dgraph_recurse_first_hop_total{mode="push"} 0' in text
    assert 'dgraph_recurse_first_hop_total{mode="stream"} 0' in text


def test_keyed_gauge_get_is_locked_and_consistent():
    g = metrics.KeyedGauge()
    stop = threading.Event()
    errors = []

    def writer():
        i = 0
        while not stop.is_set():
            g.set(f"k{i % 50}", i % 7)      # zero values delete keys
            i += 1

    def reader():
        try:
            while not stop.is_set():
                g.get("k3")
                g.snapshot()
        except Exception as e:              # torn dict state surfaces here
            errors.append(e)

    ts = [threading.Thread(target=writer) for _ in range(2)] + \
         [threading.Thread(target=reader) for _ in range(2)]
    for t in ts:
        t.start()
    stop.wait(0.3)
    stop.set()
    for t in ts:
        t.join()
    assert not errors
    g.set("x", 5)
    assert g.get("x") == 5 and g.get("missing") == 0


def test_meter_rate_wider_window_clamps_to_retention():
    """Pruning keeps only self.window of history, so a wider request
    clamps instead of silently undercounting over the longer divisor."""
    m = metrics.Meter(window=10.0)
    import time as _time

    now = _time.monotonic()
    with m._lock:
        for _ in range(5):
            m._ring.append(now - 1.0)
    assert m.rate(window=60.0) == pytest.approx(5 / 10.0)
