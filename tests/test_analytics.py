"""Whole-graph analytics under their older names: `pagerank`, `cc` and
`triangles` are PR, WCC and LCC (query/analytics.KINDS) with answers of
their own. Checked against NetworkX through Node.analytics on a tablet
stored both ways (the one-chip device programs) and on a one-way tablet
with source-only vertices (the host references); the stop rule that only
`pagerank` has; dangling mass; empty and one-vertex tablets; and the
Node / HTTP surface with its metrics."""

import json

import numpy as np
import pytest

nx = pytest.importorskip("networkx")

from dgraph_tpu.api.server import Node
from dgraph_tpu.query import analytics as an
from dgraph_tpu.storage.csr_build import PredCSR
from test_graphalytics import _dangling_digraph, _load

OLD = ("pagerank", "cc", "triangles")


def _random_digraph(n, m, seed):
    """Distinct edges over uids 1..n, no self-loops: some vertices are
    sources only, some sinks."""
    rng = np.random.default_rng(seed)
    e = rng.integers(1, n + 1, size=(m, 2))
    e = np.unique(e[e[:, 0] != e[:, 1]], axis=0)
    return e[:, 0], e[:, 1]


def _digraph(src, dst):
    g = nx.DiGraph()
    g.add_edges_from(zip(src.tolist(), dst.tolist()))
    return g


@pytest.fixture(scope="module", params=["both_ways", "one_way"])
def tablet(request):
    """(stored both ways, src, dst, node): both ways, every source is a
    destination and the device programs answer; one way, some source is
    not and the host references answer."""
    src, dst = _random_digraph(300, 1200, 7)
    if request.param == "both_ways":
        src, dst = np.concatenate([src, dst]), np.concatenate([dst, src])
        e = np.unique(np.stack([src, dst], 1), axis=0)
        src, dst = e[:, 0], e[:, 1]
    else:
        assert not np.isin(src, dst).all()
    return request.param == "both_ways", src, dst, _load(src, dst)


@pytest.mark.parametrize("kind", OLD)
def test_old_names_match_networkx(tablet, kind):
    """pagerank within 1e-6 of NetworkX at every vertex, cc's components
    and triangles exact, each with its documented answer keys, on the
    device (both ways) and on the host (one way)."""
    device, src, dst, node = tablet
    g = _digraph(src, dst)
    n = g.number_of_nodes()
    out = node.analytics(kind, "follows", tol=1e-9, max_iters=200, top=n)
    assert out["device"] is device
    assert (out["nodes"], out["edges"]) == (n, len(src))
    own = {"pagerank": {"iterations", "top"},
           "cc": {"iterations", "components", "largest"},
           "triangles": {"triangles"}}[kind]
    assert set(out) == {"kind", "pred", "nodes", "edges", "device"} | own
    if kind == "pagerank":
        oracle = nx.pagerank(g, alpha=0.85, tol=1e-12, max_iter=500)
        assert len(out["top"]) == n and 0 < out["iterations"] <= 200
        for row in out["top"]:
            assert abs(row["score"] - oracle[int(row["uid"], 16)]) < 1e-6
        scores = [row["score"] for row in out["top"]]
        assert scores == sorted(scores, reverse=True)
    elif kind == "cc":
        sizes = [len(c) for c in nx.connected_components(g.to_undirected())]
        assert (out["components"], out["largest"]) == (len(sizes),
                                                      max(sizes))
        assert out["iterations"] >= 1
    else:
        want = sum(nx.triangles(g.to_undirected()).values()) // 3
        assert want > 0 and out["triangles"] == want


def test_pagerank_stops_once_a_step_moves_less_than_tol(tablet):
    """pagerank stops before max_iters, at the first step whose L1 delta
    is <= tol: the float64 reference's delta at that step is within tol
    and the step before's is not (a tol far above float32's noise)."""
    device, _, _, node = tablet
    tol = 1e-4
    out = node.analytics("pagerank", "follows", tol=tol, max_iters=100)
    assert out["device"] is device
    it = out["iterations"]
    assert 2 < it < 100
    csr = node.snapshot().preds["follows"].csr
    _, esrc, edst = an.graph_arrays(csr)
    n = out["nodes"]
    r = [an.pagerank_host(esrc, edst, n, tol=-1.0, max_iters=k)[0]
         for k in (it - 2, it - 1, it)]
    assert np.abs(r[2] - r[1]).sum() <= tol * (1 + 1e-3)
    assert np.abs(r[1] - r[0]).sum() > tol * (1 - 1e-3)


def test_pr_runs_every_step_whatever_tol(tablet):
    """pr sent tol = 0.5 (which stops pagerank after its first step) still
    runs exactly `iterations` steps, with the reference's ranks."""
    device, _, _, node = tablet
    stopped = node.analytics("pagerank", "follows", tol=0.5, max_iters=7)
    assert stopped["iterations"] == 1
    csr = node.snapshot().preds["follows"].csr
    nodes, esrc, edst = an.graph_arrays(csr)
    want, _ = an.pagerank_host(esrc, edst, len(nodes), tol=-1.0,
                               max_iters=7)
    probes = [hex(int(u)) for u in nodes]
    out = node.analytics("pr", "follows", iterations=7, tol=0.5,
                         uids=probes)
    assert out["device"] is device and out["iterations"] == 7
    got = np.asarray([out["values"][h] for h in probes])
    assert np.abs(got - want).max() < 1e-6


def test_pagerank_conserves_dangling_mass_on_the_device():
    """Sinks hand their rank to every vertex: on the device path the ranks
    sum to 1 and match NetworkX's (which treats sinks the same way)."""
    src, dst = _dangling_digraph(3)
    node = _load(src, dst)
    g = _digraph(src, dst)
    n = g.number_of_nodes()
    assert any(d == 0 for _, d in g.out_degree())
    out = node.analytics("pagerank", "follows", tol=1e-9, max_iters=200,
                         top=n)
    assert out["device"] is True and len(out["top"]) == n
    assert abs(sum(row["score"] for row in out["top"]) - 1.0) < 1e-5
    oracle = nx.pagerank(g, alpha=0.85, tol=1e-12, max_iter=500)
    for row in out["top"]:
        assert abs(row["score"] - oracle[int(row["uid"], 16)]) < 1e-6


@pytest.mark.parametrize("shape", ["empty", "one_vertex"])
@pytest.mark.parametrize("kind", OLD)
def test_empty_and_one_vertex_tablets(kind, shape):
    """A tablet with no edge left answers 400 through the node and zeros
    from the host path; one vertex with a self-loop holds the whole rank,
    is one component and closes no triangle."""
    if shape == "empty":
        node = _load([1], [2])
        node.mutate(del_nquads="<0x1> <follows> <0x2> .", commit_now=True)
        with pytest.raises(ValueError):
            node.analytics(kind, "follows")
        nothing = np.zeros(0, np.int32)
        out = an.run(kind, PredCSR(nothing, np.zeros(1, np.int32), nothing))
        assert (out["nodes"], out["edges"], out["device"]) == (0, 0, False)
        want = {"pagerank": {"iterations": 0, "top": []},
                "cc": {"iterations": 1, "components": 0, "largest": 0},
                "triangles": {"triangles": 0}}[kind]
    else:
        out = _load([1], [1]).analytics(kind, "follows")
        assert (out["nodes"], out["edges"], out["device"]) == (1, 1, True)
        want = {"pagerank": {"iterations": 1,
                             "top": [{"uid": "0x1",
                                      "score": pytest.approx(1.0, abs=1e-6)}]},
                "cc": {"components": 1, "largest": 1},
                "triangles": {"triangles": 0}}[kind]
    assert {k: out[k] for k in want} == want


# ---------------------------------------------------------------------------
# Node.analytics + HTTP surface
# ---------------------------------------------------------------------------

SCHEMA = """
name: string @index(exact) .
follows: [uid] @reverse .
"""


def _social_quads(n=60, seed=3):
    """Random follows over a ring, each stored both ways: every vertex is
    a source and a destination, and the device path answers all three
    kinds over `follows` and `~follows` alike."""
    rng = np.random.default_rng(seed)
    quads = [f'<0x{i:x}> <name> "u{i}" .' for i in range(1, n + 1)]
    pairs = set()
    for i in range(1, n + 1):
        for j in set(int(x) for x in rng.integers(1, n + 1, 4)) | {i % n + 1}:
            if j != i:
                pairs |= {(i, j), (j, i)}
    quads += [f"<0x{i:x}> <follows> <0x{j:x}> ." for i, j in sorted(pairs)]
    return quads


@pytest.fixture(scope="module")
def social_pair():
    """(host, dev): the same graph, on `host` as a delta overlay — its
    last edge committed after the tablet's first read — which the host
    references answer, on `dev` as a base tablet the device answers."""
    quads = _social_quads()
    nodes = []
    for last in (True, False):
        node = Node()
        node.alter(schema_text=SCHEMA)
        node.mutate(set_nquads="\n".join(quads[:-1] if last else quads),
                    commit_now=True)
        if last:
            node.analytics("cc", "follows")
            node.mutate(set_nquads=quads[-1], commit_now=True)
        nodes.append(node)
    return nodes


def test_node_analytics_device_and_host_agree(social_pair):
    host, dev = social_pair
    for kind in ("cc", "triangles"):
        a = host.analytics(kind, "follows")
        b = dev.analytics(kind, "follows")
        assert a["device"] is False and b["device"] is True
        a.pop("device"), b.pop("device")
        a.pop("iterations", None), b.pop("iterations", None)
        assert json.dumps(a, sort_keys=True) == json.dumps(b, sort_keys=True)
    a = host.analytics("pagerank", "follows", tol=1e-10, max_iters=300)
    b = dev.analytics("pagerank", "follows", tol=1e-10, max_iters=300)
    assert [r["uid"] for r in a["top"][:5]] == \
        [r["uid"] for r in b["top"][:5]]
    for ra, rb in zip(a["top"], b["top"]):
        assert abs(ra["score"] - rb["score"]) < 1e-6


def test_node_analytics_reverse_pred_and_oracle(social_pair):
    _host, dev = social_pair
    out = dev.analytics("pagerank", "~follows", tol=1e-10, max_iters=300)
    assert out["pred"] == "~follows" and out["device"] is True
    # oracle over the reversed edge set
    g = nx.DiGraph()
    q, _ = dev.query('{ q(func: has(name)) { uid follows { uid } } }')
    for row in q["q"]:
        for t in row.get("follows", []):
            g.add_edge(int(t["uid"], 16), int(row["uid"], 16))
    oracle = nx.pagerank(g, alpha=0.85, tol=1e-13, max_iter=1000)
    best = max(oracle, key=oracle.get)
    assert int(out["top"][0]["uid"], 16) == best


def test_node_analytics_metrics_and_errors(social_pair):
    host, dev = social_pair
    c_runs = dev.metrics.counter("dgraph_analytics_runs_total")
    c_host = host.metrics.counter("dgraph_analytics_host_fallbacks_total")
    r0, h0 = c_runs.value, c_host.value
    dev.analytics("cc", "follows")
    host.analytics("cc", "follows")
    assert c_runs.value > r0
    assert c_host.value > h0
    with pytest.raises(ValueError):
        dev.analytics("betweenness", "follows")
    with pytest.raises(ValueError):
        dev.analytics("pagerank", "name")    # value pred: no uid edges


def test_overlay_tablet_falls_back_to_host(social_pair):
    _host, dev = social_pair
    dev.mutate(set_nquads="<0x1> <follows> <0x2> .", commit_now=True)
    try:
        out = dev.analytics("cc", "follows")
        assert out["device"] is False       # delta overlay → host oracle
    finally:
        pass


def test_http_analytics_endpoint(social_pair):
    import urllib.error
    import urllib.request

    from dgraph_tpu.api.http import serve_forever

    _host, dev = social_pair
    srv = serve_forever(dev, port=0)
    try:
        port = srv.server_address[1]
        body = json.dumps({"kind": "pagerank", "pred": "follows",
                           "maxIters": 200, "top": 3}).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/analytics", data=body)
        with urllib.request.urlopen(req) as r:
            env = json.loads(r.read())
        out = env["data"]["analytics"]
        assert out["kind"] == "pagerank" and out["pred"] == "follows"
        assert len(out["top"]) == 3
        assert "server_latency" in env["extensions"]
        # bad request maps to 400 like every other endpoint
        bad = urllib.request.Request(
            f"http://127.0.0.1:{port}/analytics",
            data=json.dumps({"kind": "pagerank"}).encode())
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(bad)
        assert ei.value.code == 400
    finally:
        srv.shutdown()
