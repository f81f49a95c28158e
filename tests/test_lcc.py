"""LDBC Graphalytics' LCC on a one-chip node (query/analytics.py kind `lcc`,
ops/lcc.analytics_lcc over the degree-ordered rows built from the resident
PullGraph), held to the plain reference (tests/graphalytics_ref.lcc,
numpy / float64, nothing of dgraph_tpu) for EVERY vertex: t(v) exactly,
lcc(v) within relative 1e-4 (Graphalytics' epsilon-match), through
Node.analytics and POST /analytics.

Why float32 stays far inside 1e-4: the counts are int32 and exact, and the
ratio is one float32 division of two exactly-converted numbers (a few
units of 2^-24). bfloat16 counts (8 bits of mantissa) cannot pass it:
checked below."""

import json
import time
import urllib.request

import jax.numpy as jnp
import numpy as np
import pytest

import graphalytics_ref as ref
from dgraph_tpu.ops import lcc as lcc_ops
from dgraph_tpu.ops import pallas_bfs as pb
from dgraph_tpu.query import analytics as an
from test_graphalytics import _hexes, _load

TOL = 1e-4


def _check(out, nodes, tri, want):
    """Every vertex's t exact and lcc within TOL; the total and the sum."""
    h = _hexes(nodes)
    assert [out["triangles"][x] for x in h] == tri.tolist()
    got = np.asarray([out["values"][x] for x in h], dtype=np.float64)
    nz = want > 0
    assert np.all(got[~nz] == 0)
    if nz.any():
        assert ref.rel_error(got[nz], want[nz]) <= TOL
    assert out["total"] == tri.sum() // 3
    assert out["sum"] == pytest.approx(want.sum(), rel=TOL, abs=1e-9)
    assert out["nodes"] == len(nodes)


@pytest.mark.parametrize("scale,seed", [(8, 11), (9, 2147484907),
                                        (10, 5), (11, 77)])
def test_lcc_matches_the_reference_at_every_vertex(scale, seed):
    src, dst = ref.kronecker(scale, seed)
    node = _load(src, dst)
    nodes, tri, want = ref.lcc(src, dst)
    out = node.analytics("lcc", "follows", uids=_hexes(nodes))
    assert out["device"] is True and out["kind"] == "lcc"
    assert out["edges"] == len(src)
    _check(out, nodes, tri, want)


@pytest.mark.parametrize("scale,seed", [(10, 5), (11, 77)])
def test_a_partial_core_matches_the_reference_at_every_vertex(
        monkeypatch, scale, seed):
    """The served program with the core held to 512 ranks, fewer than the
    graph has: the product and the compares each find a part of the
    triangles, and every vertex's t and lcc still hold."""
    monkeypatch.setattr(lcc_ops, "core_size", lambda pairs: 512)
    src, dst = ref.kronecker(scale, seed)
    node = _load(src, dst)
    nodes, tri, want = ref.lcc(src, dst)
    out = node.analytics("lcc", "follows", uids=_hexes(nodes))
    assert out["device"] is True and out["kind"] == "lcc"
    lay = an.layouts(node.snapshot().preds["follows"].csr)["lcc"]
    assert len(nodes) > lay.core == 512 and lay.core_edges > 0
    assert lay.compares > lay.merge > 0
    _check(out, nodes, tri, want)


def _both_ways(pairs):
    e = np.asarray(pairs, dtype=np.int64)
    return (np.concatenate([e[:, 0], e[:, 1]]),
            np.concatenate([e[:, 1], e[:, 0]]))


def _clique(n, first=1):
    return [(first + i, first + j) for i in range(n) for j in range(i + 1, n)]


SHAPES = {
    # name: (undirected pairs, {uid: (t, lcc)} checked beside the reference)
    "clique_of_12": (_clique(12), {1: (55, 1.0), 12: (55, 1.0)}),
    "triangle_free_cycle_and_grid": (
        [(i, i % 9 + 1) for i in range(1, 10)]
        + [(20 + r * 4 + c, 20 + r * 4 + c + 1) for r in range(4)
           for c in range(3)]
        + [(20 + r * 4 + c, 24 + r * 4 + c) for r in range(3)
           for c in range(4)], {1: (0, 0.0), 25: (0, 0.0)}),
    "star_with_degree_one_leaves": (
        [(100, 100 + k) for k in range(1, 30)], {100: (0, 0.0),
                                                  101: (0, 0.0)}),
    "two_cliques_joined_by_a_bridge": (
        _clique(6) + _clique(7, first=40) + [(6, 40)],
        {6: (10, 10 / 15), 40: (15, 15 / 21), 41: (15, 1.0)}),
}


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_small_shapes_every_vertex(shape):
    pairs, known = SHAPES[shape]
    src, dst = _both_ways(pairs)
    node = _load(src, dst)
    nodes, tri, want = ref.lcc(src, dst)
    out = node.analytics("lcc", "follows", uids=_hexes(nodes))
    assert out["device"] is True
    _check(out, nodes, tri, want)
    for u, (t, ratio) in known.items():
        assert out["triangles"][hex(u)] == t
        assert out["values"][hex(u)] == pytest.approx(ratio, rel=TOL)


def test_duplicates_and_self_loops_in_the_stored_tablet():
    """A self-loop is no neighbour and a repeated triple one edge: a vertex
    whose only edge is a self-loop is in the vertex set with d = 0."""
    src, dst = _both_ways(_clique(5) + [(7, 8), (8, 9), (9, 7), (3, 8)])
    src = np.concatenate([src, [2, 2, 8, 9, 50], src[:6]])
    dst = np.concatenate([dst, [2, 3, 8, 9, 50], dst[:6]])
    node = _load(src, dst)
    csr = node.snapshot().preds["follows"].csr
    assert an.layout(csr, "pull")[1:] == (None, True)
    nodes, tri, want = ref.lcc(src, dst)
    assert 50 in nodes.tolist()
    out = node.analytics("lcc", "follows", uids=_hexes(nodes))
    assert out["device"] is True
    _check(out, nodes, tri, want)
    assert out["triangles"]["0x32"] == 0 and out["values"]["0x32"] == 0
    assert out["triangles"]["0x8"] == 1            # 7-8-9 alone
    assert out["edges"] == len(set(zip(src.tolist(), dst.tolist())))


def test_a_hub_beyond_every_row_class_keeps_a_short_row():
    """A hub of degree 1,500 (three times the widest class a Graph500
    scale-18 graph fills) over a ring of leaves: every ring edge closes a
    triangle through the hub, and the orientation leaves the hub no
    out-row at all."""
    leaves = list(range(2, 1502))
    ring = [(leaves[i], leaves[(i + 1) % len(leaves)])
            for i in range(len(leaves))]
    src, dst = _both_ways([(1, v) for v in leaves] + ring)
    node = _load(src, dst)
    nodes, tri, want = ref.lcc(src, dst)
    out = node.analytics("lcc", "follows", uids=_hexes(nodes))
    _check(out, nodes, tri, want)
    assert out["triangles"]["0x1"] == 1500
    assert out["values"]["0x1"] == pytest.approx(1500 / (1500 * 1499 / 2),
                                                 rel=TOL)
    lay = an.layouts(node.snapshot().preds["follows"].csr)["lcc"]
    assert lay.max_out == 3 and lay.oriented_edges == len(src) // 2


def _run_ops(lay, n):
    return lcc_ops.analytics_lcc(
        lay.tables, lay.members, lay.tails, lay.heads, lay.head_ids,
        lay.order, lay.degree, lay.adjacency, np.arange(n, dtype=np.int32),
        buckets=lay.buckets, core=lay.core, spread=lay.spread)


def _rank_rows(src, dst):
    """(iptr, nbrs, n) of the stored edges src -> dst in rank space: the
    in-row of each rank sorted, as the PullGraph hands them to build."""
    nodes = np.unique(np.concatenate([src, dst]))
    s, d = np.searchsorted(nodes, src), np.searchsorted(nodes, dst)
    o = np.lexsort((s, d))
    iptr = np.zeros(len(nodes) + 1, dtype=np.int64)
    np.cumsum(np.bincount(d, minlength=len(nodes)), out=iptr[1:])
    return iptr, s[o], len(nodes)


def _split_holds(lay, iptr, nbrs, n):
    """Every vertex's t and lcc of the layout's program against lcc_host,
    and the split of the oriented edges: those inside the core, and the
    live ones outside it that the compare path holds."""
    v = np.repeat(np.arange(n), np.diff(iptr))
    tri, want = an.lcc_host(nbrs, v, n)
    got = _run_ops(lay, n)
    assert np.asarray(got[0]).tolist() == tri.tolist()
    lcc = np.asarray(got[1], dtype=np.float64)
    nz = want > 0
    assert np.all(lcc[~nz] == 0)
    if nz.any():
        assert ref.rel_error(lcc[nz], want[nz]) <= TOL
    assert int(got[2]) == tri.sum() // 3
    order = np.asarray(lay.order)
    a, b = order[nbrs[nbrs != v]], order[v[nbrs != v]]
    a, b = a[a < b], b[a < b]
    a, b = np.unique(np.stack([a, b]), axis=1)
    od = np.bincount(a, minlength=n)
    live = (od[a] > 1) & (od[b] > 0)
    inner = a >= n - lay.core
    compared = sum(int((np.asarray(h) < n).sum()) for h in lay.head_ids)
    assert lay.oriented_edges == len(a)
    assert lay.core_edges == inner.sum()
    assert compared == (live & ~inner).sum()
    assert lay.core_edges + compared == (live | inner).sum()
    assert (lay.compares > lay.merge > 0) == (compared > 0)
    assert lay.adjacency.shape == (-(-lay.core // lcc_ops.TILE)
                                   * lcc_ops.TILE,) * 2
    assert int(np.asarray(lay.adjacency, dtype=np.int64).sum()) == \
        2 * lay.core_edges


@pytest.mark.parametrize("core", [0, 512, "all", "rule"])
def test_the_core_split_at_every_vertex(core):
    """An R-MAT scale-11 graph with the core forced to nothing, to 512 of
    its ranks, to all of them (the product alone), and as the rule picks:
    every vertex's t exact and the edges split between the two paths."""
    src, dst = ref.kronecker(11, 77)
    iptr, nbrs, n = _rank_rows(src, dst)
    k = {"all": n, "rule": None}.get(core, core)
    lay = lcc_ops.build(iptr, nbrs, core=k)
    assert lay.core == k if k is not None else 0 < lay.core <= n
    assert n > 512
    _split_holds(lay, iptr, nbrs, n)


CORE_SHAPES = {
    # name: (undirected pairs, the rule's K: None where it is the
    # product's price against the compares' that decides)
    "clique_of_40": (_clique(40), None),
    "star_of_600": ([(1, 1 + k) for k in range(1, 601)], 0),
    "ring_of_3000": ([(i, i % 3000 + 1) for i in range(1, 3001)], 0),
    "two_cliques_under_512": (_clique(6) + _clique(7, first=40)
                              + [(6, 40)], None),
}


@pytest.mark.parametrize("core", [0, "all", "rule"])
@pytest.mark.parametrize("shape", sorted(CORE_SHAPES))
def test_the_core_split_on_small_shapes(shape, core):
    """A clique, a star, a ring and a graph of fewer than TILE vertices,
    with no core, all of it in the core and as the rule picks; a graph
    with no dense part (a star, a ring) gets no core from the rule."""
    pairs, rule = CORE_SHAPES[shape]
    src, dst = _both_ways(pairs)
    iptr, nbrs, n = _rank_rows(src, dst)
    lay = lcc_ops.build(iptr, nbrs, core={"all": n, "rule": None}.get(
        core, core))
    if core == "rule" and rule is not None:
        assert lay.core == rule
    _split_holds(lay, iptr, nbrs, n)


def test_core_size_prices_the_compares_against_the_product():
    """K = 0 where the compares cost less than the smallest product, the
    whole graph where they all cost more than its product, and the TILE
    multiple in between that the two prices balance at."""
    n = 4096
    size = lcc_ops.core_size
    tile3 = lcc_ops.C_MAC * lcc_ops.TILE ** 3
    assert size(np.zeros(n)) == 0
    few = np.zeros(n)
    few[-1] = 0.5 * tile3 / lcc_ops.C_PAIR
    assert size(few) == 0
    small = np.full(300, 2 * tile3 / lcc_ops.C_PAIR)
    assert size(small) == 300
    dense = np.zeros(n)
    dense[-1024:] = 64 * tile3 / lcc_ops.C_PAIR / 1024
    assert size(dense) == 1024


def test_every_row_class_and_class_pair_of_a_clique():
    """A clique of 130 orients its k-th vertex to a row of 129 - k ids:
    every class from 8 to 256 holds rows, every pair of them with the
    tail's class at least the head's holds edges (the one 256-wide row
    heads no edge), and every vertex closes C(129, 2) triangles (ops
    level)."""
    n = 130
    nbrs = np.concatenate([np.delete(np.arange(n), v) for v in range(n)])
    iptr = np.arange(n + 1) * (n - 1)
    lay = lcc_ops.build(iptr, nbrs, core=0)
    assert [int(t.shape[1]) for t in lay.tables] == [8, 16, 32, 64, 128, 256]
    assert lay.max_out == n - 1 and len(lay.buckets) == 20
    got = _run_ops(lay, n)
    assert np.all(np.asarray(got[0]) == (n - 1) * (n - 2) // 2)
    assert np.allclose(np.asarray(got[1]), 1.0, rtol=TOL)
    assert int(got[2]) == n * (n - 1) * (n - 2) // 6
    assert float(got[3]) == pytest.approx(n, rel=TOL)


def test_probes_that_are_not_vertices_answer_null():
    src, dst = _both_ways(_clique(4))
    node = _load(src, dst)
    out = node.analytics("lcc", "follows", uids=["0x1", "0xfffff", 3])
    assert out["values"] == {"0x1": 1.0, "0xfffff": None, "0x3": 1.0}
    assert out["triangles"] == {"0x1": 3, "0xfffff": None, "0x3": 3}
    assert out["total"] == 4 and out["sum"] == pytest.approx(4.0)


def test_a_one_way_tablet_declines_to_the_host_and_is_counted():
    """A tablet not stored in both directions has in-rows that are not
    neighbourhoods: the host answers over the symmetrised graph, and the
    run is counted under reason one_way."""
    s = np.asarray([1, 2, 3, 3, 4, 5, 5])
    t = np.asarray([2, 3, 1, 4, 5, 3, 1])        # every source a dst
    node = _load(s, t)
    csr = node.snapshot().preds["follows"].csr
    assert an.layout(csr, "pull")[1:] == (None, False)
    nodes, tri, want = ref.lcc(s, t)
    out = node.analytics("lcc", "follows", uids=_hexes(nodes))
    assert out["device"] is False
    _check(out, nodes, tri, want)
    assert node.metrics.keyed("dgraph_analytics_host_runs_total").get(
        "lcc|one_way") == 1
    assert node.metrics.keyed("dgraph_analytics_device_runs_total").get(
        "lcc") == 0
    assert "lcc" not in an.layouts(csr)


def test_a_write_answers_on_the_host_then_on_the_device():
    """A write closes a triangle: the overlay answers on the host, the
    compacted tablet on the device, the same counts."""
    src, dst = ref.kronecker(8, 3)
    node = _load(src, dst)
    first = node.analytics("pr", "follows")
    csr = node.snapshot().preds["follows"].csr
    assert first["device"] is True and "lcc" not in an.layouts(csr)
    assert node.analytics("lcc", "follows")["device"] is True
    assert an.layouts(csr).get("lcc") is not None
    new = [(0x7001, 0x7002), (0x7002, 0x7003), (0x7003, 0x7001)]
    s2, t2 = _both_ways(new)
    node.mutate(set_nquads="\n".join(f"<0x{a:x}> <follows> <0x{b:x}> ."
                                     for a, b in zip(s2, t2)),
                commit_now=True)
    s2, t2 = np.concatenate([src, s2]), np.concatenate([dst, t2])
    nodes, tri, want = ref.lcc(s2, t2)
    answers = []
    for _ in ("host", "device"):
        out = node.analytics("lcc", "follows", uids=_hexes(nodes))
        _check(out, nodes, tri, want)
        answers.append(out)
        node._assembler.compact(node._lock, force=True)
    assert [a["device"] for a in answers] == [False, True]
    assert answers[0]["triangles"] == answers[1]["triangles"]
    assert answers[1]["triangles"]["0x7001"] == 1
    assert node.metrics.keyed("dgraph_analytics_host_runs_total").get(
        "lcc|overlay") == 1


def test_bfloat16_counts_fail_the_check():
    """The served t through a bfloat16 rounding misses the 1e-4 epsilon on
    a Kronecker graph's vertices with many triangles; float32 does not."""
    src, dst = ref.kronecker(10, 5)
    nodes, tri, want = ref.lcc(src, dst)
    deg = np.asarray([len(set(dst[src == u].tolist()) - {u})
                      for u in nodes], dtype=np.float64)
    nz = want > 0
    w = deg[nz] * (deg[nz] - 1) / 2
    for dt, fails in ((jnp.bfloat16, True), (jnp.float32, False)):
        t = np.asarray(jnp.asarray(tri[nz], jnp.float32).astype(dt)
                       .astype(jnp.float32), np.float64)
        assert (ref.rel_error(t / w, want[nz]) > TOL) is fails


def test_http_kind_counters_and_span(monkeypatch):
    """POST /analytics `lcc` through a served node: the answer, the
    request's stages, the device_kernel span's attributes and the
    counters. The core holds half the ranks, so both the product and the
    compares run and are counted."""
    from dgraph_tpu.api.http import serve_forever
    from dgraph_tpu.obs import prom

    monkeypatch.setattr(lcc_ops, "core_size", lambda pairs: len(pairs) // 2)
    src, dst = ref.kronecker(9, 8)
    node = _load(src, dst, span_sample=1.0)
    srv = serve_forever(node, port=0)
    port = srv.server_address[1]
    nodes, tri, want = ref.lcc(src, dst)
    probes = _hexes(nodes[::5])
    try:
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/analytics",
            data=json.dumps({"kind": "lcc", "pred": "follows",
                             "uids": probes}).encode())
        with urllib.request.urlopen(req) as r:
            out = json.loads(r.read())["data"]["analytics"]
        closed = node.metrics.counter("dgraph_stage_requests_total")
        deadline = time.monotonic() + 5
        while closed.value < 1 and time.monotonic() < deadline:
            time.sleep(0.002)
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics") as r:
            parsed = prom.parse(r.read().decode())
    finally:
        srv.shutdown()
    assert out["device"] is True and out["pred"] == "follows"
    assert [out["triangles"][h] for h in probes] == tri[::5].tolist()
    assert out["total"] == tri.sum() // 3

    def val(name, **labels):
        return next(v for lab, v in parsed[name]
                    if all(lab.get(k) == x for k, x in labels.items()))

    lay = an.layouts(node.snapshot().preds["follows"].csr)["lcc"]
    assert val("dgraph_analytics_device_runs_total", kind="lcc") == 1
    assert val("dgraph_analytics_steps_total", kind="lcc") == 1
    assert val("dgraph_analytics_edges_read_total", kind="lcc") == len(src)
    assert val("dgraph_analytics_lcc_compares_total") == lay.compares > 0
    assert val("dgraph_analytics_lcc_merge_total") == lay.merge > 0
    assert lay.merge < lay.compares
    assert 0 < lay.core == len(nodes) // 2
    assert val("dgraph_analytics_lcc_core_edges_total") == \
        lay.core_edges > 0
    assert val("dgraph_analytics_lcc_oriented_edges_total") == \
        lay.oriented_edges
    assert val("dgraph_analytics_kernel_steps_total", kind="pr") == 0
    assert "dgraph_analytics_host_runs_total" not in parsed
    for stage in ("http.read", "plan", "exec.prep", "dev.dispatch",
                  "dev.wait", "dev.post", "encode", "http.write"):
        assert val("dgraph_stage_us_total", stage=stage) >= 0, stage
    spans = []
    for row in node.tracer.sink.index(8):
        if row["root"] == "analytics":
            rec = node.tracer.sink.get(row["trace_id"])
            spans += [sp["attrs"] for sp in rec["spans"]
                      if sp["name"] == "device_kernel"]
    assert len(spans) == 1
    attrs = spans[0]
    assert attrs["kernel"] == "pb.analytics_lcc"
    assert (attrs["nodes"], attrs["edges"]) == (len(nodes), len(src))
    assert attrs["oriented_edges"] == lay.oriented_edges == len(src) // 2
    assert attrs["max_out"] == lay.max_out
    assert (attrs["core"], attrs["core_edges"]) == (lay.core,
                                                    lay.core_edges)
    assert attrs["total"] == tri.sum() // 3
    assert pb.JIT_PROGRAMS and "pb.analytics_lcc" in lcc_ops.JIT_PROGRAMS
