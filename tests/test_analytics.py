"""Whole-graph analytics (ISSUE 17): PageRank / connected components /
triangle counting as device-resident while_loop programs on the mesh,
checked against NetworkX oracles; host fallbacks byte-identical where the
math is exact (CC labels, triangle counts); Node.analytics + /analytics
surfaces with metrics and the LDBC SF10 scale gate.

Needs the conftest-provided 8-virtual-device CPU mesh."""

import json
import time

import numpy as np
import pytest
import jax

nx = pytest.importorskip("networkx")

from dgraph_tpu.api.server import Node
from dgraph_tpu.parallel.mesh_exec import MeshExecutor
from dgraph_tpu.query import analytics as an

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs the conftest-provided 8-virtual-device CPU mesh")


@pytest.fixture(scope="module")
def mesh():
    return MeshExecutor()


def _random_digraph(n, m, seed):
    rng = np.random.default_rng(seed)
    e = rng.integers(0, n, size=(m, 2))
    e = np.unique(e[e[:, 0] != e[:, 1]], axis=0)
    return e[:, 0].astype(np.int32), e[:, 1].astype(np.int32)


# ---------------------------------------------------------------------------
# device kernels vs NetworkX oracles
# ---------------------------------------------------------------------------

def test_pagerank_device_matches_networkx(mesh):
    n = 500
    esrc, edst = _random_digraph(n, 3000, 7)
    r, it = mesh.run_pagerank(esrc, edst, n, tol=1e-9, max_iters=200)
    assert 0 < it < 200
    g = nx.DiGraph()
    g.add_nodes_from(range(n))
    g.add_edges_from(zip(esrc.tolist(), edst.tolist()))
    oracle = nx.pagerank(g, alpha=0.85, tol=1e-12, max_iter=500)
    want = np.asarray([oracle[i] for i in range(n)])
    assert np.abs(np.asarray(r, np.float64) - want).max() < 1e-6
    assert abs(float(np.sum(r)) - 1.0) < 1e-4


def test_pagerank_dangling_mass_conserved(mesh):
    # a sink chain: dangling mass must redistribute, not vanish
    esrc = np.asarray([0, 1, 2], np.int32)
    edst = np.asarray([1, 2, 3], np.int32)
    r, _ = mesh.run_pagerank(esrc, edst, 4, tol=1e-12, max_iters=300)
    g = nx.DiGraph()
    g.add_nodes_from(range(4))
    g.add_edges_from(zip(esrc.tolist(), edst.tolist()))
    oracle = nx.pagerank(g, alpha=0.85, tol=1e-14, max_iter=1000)
    want = np.asarray([oracle[i] for i in range(4)])
    assert np.abs(np.asarray(r, np.float64) - want).max() < 1e-6


def test_cc_device_exact_vs_networkx(mesh):
    n = 400
    esrc, edst = _random_digraph(n, 260, 11)   # sparse → many components
    lab, it = mesh.run_cc(esrc, edst, n)
    assert it >= 1
    g = nx.Graph()
    g.add_nodes_from(range(n))
    g.add_edges_from(zip(esrc.tolist(), edst.tolist()))
    want = np.arange(n, dtype=np.int64)
    for comp in nx.connected_components(g):
        mn = min(comp)
        for v in comp:
            want[v] = mn
    assert np.array_equal(np.asarray(lab, np.int64), want)


def test_triangles_device_exact_vs_networkx(mesh):
    n = 300
    esrc, edst = _random_digraph(n, 4000, 13)
    tri = mesh.run_triangles(esrc, edst, n)
    g = nx.Graph()
    g.add_nodes_from(range(n))
    g.add_edges_from(zip(esrc.tolist(), edst.tolist()))
    want = sum(nx.triangles(g).values()) // 3
    assert tri == want


def test_host_fallbacks_match_device(mesh):
    n = 350
    esrc, edst = _random_digraph(n, 2200, 17)
    lab_d, _ = mesh.run_cc(esrc, edst, n)
    lab_h = an.cc_host(esrc, edst, n)
    assert np.array_equal(np.asarray(lab_d, np.int64),
                          np.asarray(lab_h, np.int64))
    assert mesh.run_triangles(esrc, edst, n) == \
        an.lcc_host(esrc, edst, n)[0].sum() // 3
    r_d, _ = mesh.run_pagerank(esrc, edst, n, tol=1e-9, max_iters=200)
    r_h, _ = an.pagerank_host(esrc, edst, n, tol=1e-9, max_iters=200)
    assert np.abs(np.asarray(r_d, np.float64) - r_h).max() < 1e-6


def test_empty_and_single_node_graphs(mesh):
    r, it = mesh.run_pagerank(np.zeros(0, np.int32), np.zeros(0, np.int32),
                              1, tol=1e-9, max_iters=50)
    assert len(r) == 1 and abs(float(r[0]) - 1.0) < 1e-6
    lab, _ = mesh.run_cc(np.zeros(0, np.int32), np.zeros(0, np.int32), 3)
    assert np.array_equal(np.asarray(lab), [0, 1, 2])
    assert an.pagerank_host(np.zeros(0, np.int32),
                            np.zeros(0, np.int32), 0)[0].shape == (0,)
    assert an.lcc_host(np.zeros(0, np.int32),
                       np.zeros(0, np.int32), 0)[0].sum() == 0


# ---------------------------------------------------------------------------
# Node.analytics + HTTP surface
# ---------------------------------------------------------------------------

SCHEMA = """
name: string @index(exact) .
follows: [uid] @reverse .
"""


def _social_quads(n=60, seed=3):
    rng = np.random.default_rng(seed)
    quads = [f'<0x{i:x}> <name> "u{i}" .' for i in range(1, n + 1)]
    for i in range(1, n + 1):
        for j in sorted(set(int(x) for x in rng.integers(1, n + 1, 4))):
            if j != i:
                quads.append(f"<0x{i:x}> <follows> <0x{j:x}> .")
    return "\n".join(quads)


@pytest.fixture(scope="module")
def social_pair():
    nodes = []
    for dev in (0, 8):
        node = Node(mesh_devices=dev, mesh_min_edges=1)
        node.alter(schema_text=SCHEMA)
        node.mutate(set_nquads=_social_quads(), commit_now=True)
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
    uids, _, _ = dev._read_view(None)[1].pred("follows").csr.host_arrays()
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


# ---------------------------------------------------------------------------
# scale gate: LDBC SF10 person_knows_person PageRank in seconds
# ---------------------------------------------------------------------------

def test_pagerank_ldbc_sf10_scale(tmp_path, mesh):
    """The acceptance claim: PageRank over the LDBC SF10 knows graph
    (~70k persons, ~1.5M edges) converges on the mesh in seconds and
    matches the NetworkX oracle."""
    from dgraph_tpu.models.ldbc import generate_ldbc

    d = tmp_path / "ldbc"
    st = generate_ldbc(str(d), sf=10)
    assert st.persons > 50_000 and st.knows > 1_000_000
    raw = np.loadtxt(d / "person_knows_person_0_0.csv", delimiter="|",
                     skiprows=1, usecols=(0, 1), dtype=np.int64)
    ids = np.unique(raw)
    esrc = np.searchsorted(ids, raw[:, 0]).astype(np.int32)
    edst = np.searchsorted(ids, raw[:, 1]).astype(np.int32)
    n = len(ids)
    t0 = time.perf_counter()
    r, it = mesh.run_pagerank(esrc, edst, n, tol=1e-8, max_iters=200)
    dt = time.perf_counter() - t0
    assert 0 < it < 200
    assert dt < 120.0, f"SF10 PageRank took {dt:.1f}s"
    g = nx.DiGraph()
    g.add_nodes_from(range(n))
    g.add_edges_from(zip(esrc.tolist(), edst.tolist()))
    oracle = nx.pagerank(g, alpha=0.85, tol=1e-11, max_iter=500)
    want = np.asarray([oracle[i] for i in range(n)])
    got = np.asarray(r, np.float64)
    assert np.abs(got - want).max() < 1e-5
    # the top of the ranking is stable across device/oracle
    assert set(np.argsort(-got)[:10].tolist()) == \
        set(np.argsort(-want)[:10].tolist())
