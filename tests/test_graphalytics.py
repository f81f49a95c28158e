"""LDBC Graphalytics' PR and WCC on a one-chip node (query/analytics.py
kinds `pr` / `wcc`, ops/pallas_bfs.analytics_pr / analytics_wcc over the
resident PullGraph), held to the plain reference (tests/graphalytics_ref.py,
float64, nothing of dgraph_tpu) for EVERY vertex of seeded Graph500 graphs
at scales 8-12, through Node.analytics and POST /analytics.

PR tolerance: relative error 1e-4 a vertex, Graphalytics' epsilon-match.
Why float32 stays far inside it: a step is one float32 sum per vertex over
its in-edges (at most a few hundred at these scales, rounding error grows
as sqrt(degree) x 2^-24, ~1e-6 at worst) and a float32 sum of the dangling
mass; ten steps of a contraction by d = 0.85 do not compound it past
~1e-5. bfloat16 (8 bits of mantissa, 4e-3 a rounding) cannot pass it, and
neither can a run that drops the dangling mass: both are checked below."""

import json
import time
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import graphalytics_ref as ref
from dgraph_tpu.api.server import Node
from dgraph_tpu.ops import pallas_bfs as pb
from dgraph_tpu.query import analytics as an

TOL = 1e-4


def _load(src, dst, schema="follows: [uid] .", pred="follows", **kw):
    node = Node(**kw)
    node.alter(schema_text=schema)
    node.mutate(set_nquads="\n".join(
        f"<0x{a:x}> <{pred}> <0x{b:x}> ." for a, b in zip(src, dst)),
        commit_now=True)
    node._assembler.compact(node._lock, force=True)
    return node


GRAPHS = {8: 11, 10: 2147484901, 12: 77}


@pytest.fixture(scope="module", params=sorted(GRAPHS))
def kron(request):
    scale = request.param
    src, dst = ref.kronecker(scale, GRAPHS[scale])
    return scale, src, dst, _load(src, dst)


def _hexes(uids):
    return [hex(int(u)) for u in uids]


def test_pr_matches_the_reference_at_every_vertex(kron):
    _, src, dst, node = kron
    nodes, want = ref.pagerank(src, dst, 10, 0.85)
    out = node.analytics("pr", "follows", iterations=10, damping=0.85,
                         uids=_hexes(nodes), top=20)
    assert out["device"] is True and out["iterations"] == 10
    assert out["nodes"] == len(nodes) and out["edges"] == len(src)
    got = np.asarray([out["values"][h] for h in _hexes(nodes)])
    assert ref.rel_error(got, want) <= TOL
    assert abs(out["sum"] - 1.0) <= TOL
    order = np.argsort(-want, kind="stable")[:20]
    assert [r["score"] for r in out["top"]] == pytest.approx(
        want[order].tolist(), rel=TOL)


def test_wcc_is_the_reference_partition_labelled_by_least_member(kron):
    _, src, dst, node = kron
    nodes, want = ref.wcc(src, dst)
    out = node.analytics("wcc", "follows", uids=_hexes(nodes))
    assert out["device"] is True and out["rounds"] >= 2
    assert [out["labels"][h] for h in _hexes(nodes)] == _hexes(want)
    sizes = np.unique(want, return_counts=True)[1]
    assert out["components"] == len(sizes)
    assert out["largest"] == sizes.max()


def test_vmem_gather_leaves_every_rank_and_label_bit_identical(kron):
    """The served programs, handed the tablet's GatherLayout, answer
    every vertex's PR rank and WCC label to the bit as the same programs
    over XLA's gather: the kernel moves the values' bits and row_reduce
    sums each row in the same order."""
    _, _, _, node = kron
    csr = node.snapshot().preds["follows"].csr
    g = an.layout(csr, "pull")[0]
    lay, windows = an.layout(csr, "gather")
    assert lay is not None and windows > 0
    probes = np.arange(len(g.host_in_subjects), dtype=np.int32)
    for gather in (None, lay):
        pr = pb.analytics_pr(g.in_src_pad_d, g.in_iptr_rank, g.row_ends,
                             g.out_degree_d, probes, np.int32(10),
                             np.float32(0.85), np.float32(-1), gather,
                             top=20)
        wcc = pb.analytics_wcc(g.in_src_pad_d, g.in_iptr_rank, g.row_ends,
                               probes, gather, push=False)
        got = [np.asarray(a).tobytes() for a in (*pr, *wcc)]
        if gather is None:
            want = got
    assert got == want


def test_lower_precision_and_lost_dangling_mass_fail_the_check(kron):
    """The same program with its ranks in bfloat16 fails the 1e-4 check;
    so does the reference without the dangling mass, on a graph that has
    dangling vertices (a stored-both-ways graph has none)."""
    _, src, dst, node = kron
    nodes, want = ref.pagerank(src, dst, 10, 0.85)
    g = pb.pull_graph_for(node.snapshot().preds["follows"].csr)
    assert np.array_equal(g.host_in_subjects, nodes)
    probes = np.arange(len(nodes), dtype=np.int32)
    vals = pb.analytics_pr(g.in_src_pad_d, g.in_iptr_rank, g.row_ends,
                           g.out_degree_d, probes, np.int32(10),
                           jnp.bfloat16(0.85), jnp.bfloat16(-1), top=1)[0]
    assert ref.rel_error(np.asarray(vals, np.float64), want) > TOL
    s, t = _dangling_digraph(7)
    _, full = ref.pagerank(s, t)
    _, lossy = ref.pagerank(s, t, dangling=False)
    assert ref.rel_error(lossy, full) > TOL


def _dangling_digraph(seed, n=300, m=1500):
    """A directed graph whose every source is also a destination (a cycle
    over the sources) with sinks that have no out-edge: the rank spaces
    coincide and dangling mass exists."""
    rng = np.random.default_rng(seed)
    heads = np.arange(1, n + 1)
    cyc = np.stack([heads, np.roll(heads, -1)], 1)
    rnd = np.stack([rng.integers(1, n + 1, m),
                    rng.integers(1, 2 * n + 1, m)], 1)
    e = np.unique(np.concatenate([cyc, rnd]), axis=0)
    e = e[e[:, 0] != e[:, 1]]
    return e[:, 0], e[:, 1]


@pytest.fixture(scope="module")
def directed():
    s, t = _dangling_digraph(5)
    return s, t, _load(s, t)


def test_directed_graph_with_dangling_vertices(directed):
    """Sinks lose their rank to everybody (dangling mass); not stored both
    ways, a WCC round also pushes along out-edges."""
    s, t, node = directed
    csr = node.snapshot().preds["follows"].csr
    assert an.layout(csr, "pull")[1:] == (None, False)
    nodes, want = ref.pagerank(s, t)
    out = node.analytics("pr", "follows", uids=_hexes(nodes))
    assert out["device"] is True
    got = np.asarray([out["values"][h] for h in _hexes(nodes)])
    assert ref.rel_error(got, want) <= TOL
    nodes, labels = ref.wcc(s, t)
    out = node.analytics("wcc", "follows", uids=_hexes(nodes))
    assert out["device"] is True
    assert [out["labels"][h] for h in _hexes(nodes)] == _hexes(labels)


@pytest.mark.parametrize("seed", [1, 2, 3, 4, 5, 6])
def test_wcc_on_sparse_random_graphs_with_many_components(seed):
    """Many small components and one long path, stored both ways (even
    seeds) or one way with every source also a destination (odd, the
    `push` rounds): the device labels equal the reference's least members
    at every vertex."""
    rng = np.random.default_rng(seed)
    n = 400
    s = rng.integers(1, n + 1, n // 2)
    t = rng.integers(1, n + 1, n // 2)
    chain = np.arange(n + 1, n + 40)                 # one long path
    s = np.concatenate([s, chain[:-1]])
    t = np.concatenate([t, chain[1:]])
    keep = s != t
    s, t = s[keep], t[keep]
    if seed % 2 == 0:
        s, t = np.concatenate([s, t]), np.concatenate([t, s])
    else:
        # one way; a source with no in-edge gets one back from its target,
        # so that the rank spaces coincide
        lone = ~np.isin(s, t)
        s, t = np.concatenate([s, t[lone]]), np.concatenate([t, s[lone]])
    e = np.unique(np.stack([s, t], 1), axis=0)
    s, t = e[:, 0], e[:, 1]
    node = _load(s, t)
    csr = node.snapshot().preds["follows"].csr
    assert an.layout(csr, "pull")[1] is None
    nodes, labels = ref.wcc(s, t)
    out = node.analytics("wcc", "follows", uids=_hexes(nodes))
    assert out["device"] is True
    assert [out["labels"][h] for h in _hexes(nodes)] == _hexes(labels)
    sizes = np.unique(labels, return_counts=True)[1]
    assert (out["components"], out["largest"]) == (len(sizes), sizes.max())


def test_unequal_rank_spaces_are_declined_to_the_host():
    """A source that is no destination has no DST rank: the device path
    would answer over a partial vertex set, so the host answers, and the
    reason is counted."""
    s = np.asarray([1, 2, 3, 3, 4, 9])
    t = np.asarray([2, 3, 1, 5, 5, 1])          # 4 and 9: sources only
    node = _load(s, t)
    runs = node.metrics.keyed("dgraph_analytics_host_runs_total")
    nodes, want = ref.pagerank(s, t)
    out = node.analytics("pr", "follows", uids=_hexes(nodes))
    assert out["device"] is False and out["nodes"] == len(nodes) == 6
    got = np.asarray([out["values"][h] for h in _hexes(nodes)])
    assert ref.rel_error(got, want) <= 1e-9
    nodes, labels = ref.wcc(s, t)
    out = node.analytics("wcc", "follows", uids=_hexes(nodes))
    assert [out["labels"][h] for h in _hexes(nodes)] == _hexes(labels)
    assert runs.get("pr|rank_spaces") == 1 and runs.get("wcc|rank_spaces") == 1
    assert node.metrics.keyed("dgraph_analytics_device_runs_total").get(
        "pr") == 0


def test_a_write_is_answered_over_the_new_snapshot_host_and_device_alike():
    """A `follows` write joins two components: the next request answers
    over it on the host (the tablet is an overlay), and after compaction
    on the device, with the same answer."""
    src, dst = ref.kronecker(8, 3)
    node = _load(src, dst)
    # the tablet's first read gives a later write a base to stamp onto
    assert node.analytics("wcc", "follows")["device"] is True
    far = [0x7001, 0x7002]
    node.mutate(set_nquads="<0x7001> <follows> <0x7002> .\n"
                           "<0x7002> <follows> <0x7001> .", commit_now=True)
    before = node.analytics("wcc", "follows", uids=_hexes(far))
    assert before["device"] is False
    assert before["labels"] == {"0x7001": "0x7001", "0x7002": "0x7001"}
    node.mutate(set_nquads=f"<0x7001> <follows> <0x{int(src[0]):x}> .\n"
                           f"<0x{int(src[0]):x}> <follows> <0x7001> .",
                commit_now=True)
    s2 = np.concatenate([src, far, [0x7001, src[0]]])
    t2 = np.concatenate([dst, far[::-1], [src[0], 0x7001]])
    nodes, labels = ref.wcc(s2, t2)
    _, ranks = ref.pagerank(s2, t2)
    answers = []
    for _ in ("host", "device"):
        w = node.analytics("wcc", "follows", uids=_hexes(nodes))
        p = node.analytics("pr", "follows", uids=_hexes(nodes))
        assert [w["labels"][h] for h in _hexes(nodes)] == _hexes(labels)
        got = np.asarray([p["values"][h] for h in _hexes(nodes)])
        assert ref.rel_error(got, ranks) <= TOL
        answers.append((w, p))
        node._assembler.compact(node._lock, force=True)
    (wh, ph), (wd, pd) = answers
    assert (wh["device"], wd["device"]) == (False, True)
    for key in ("labels", "components", "largest", "nodes", "edges"):
        assert wh[key] == wd[key], key
    assert [r["uid"] for r in ph["top"]] == [r["uid"] for r in pd["top"]]


def test_probes_outside_the_vertex_set_and_bad_requests(directed):
    _, _, node = directed
    out = node.analytics("pr", "follows", uids=["0x1", "0xfffff", 2])
    assert out["values"]["0xfffff"] is None and out["values"]["0x2"] > 0
    out = node.analytics("wcc", "follows", uids=["0xfffff"])
    assert out["labels"] == {"0xfffff": None}
    with pytest.raises(ValueError):
        node.analytics("pr", "follows", iterations=-1)
    with pytest.raises(ValueError):
        node.analytics("pagerankish", "follows")


@pytest.mark.parametrize("kind", ["pr", "wcc"])
def test_gather_layout_is_built_by_the_first_pr_or_wcc_only(kind):
    """A /query and an `lcc` job build no gather layout and hold none; the
    first `pr` or `wcc` job of the snapshot builds it, and the next job
    of either kind reuses it."""
    src, dst = ref.kronecker(8, 5)
    node = _load(src, dst)
    csr = node.snapshot().preds["follows"].csr
    node.query(f"{{ q(func: uid(0x{int(src[0]):x})) {{ follows {{ uid }} }} }}")
    assert node.analytics("lcc", "follows")["device"] is True
    assert "gather" not in an.layouts(csr)
    assert node.analytics(kind, "follows")["device"] is True
    built = an.layouts(csr)["gather"]
    assert built[0] is not None and built[1] > 0
    other = "wcc" if kind == "pr" else "pr"
    assert node.analytics(other, "follows")["device"] is True
    assert an.layouts(csr)["gather"] is built


def test_http_kinds_stages_span_and_counters():
    """POST /analytics `pr` / `wcc` through a served node: the answer, the
    request's stages on /metrics, the device_kernel span in its trace
    (where the reduction ran) and the five counters."""
    from dgraph_tpu.api.http import serve_forever
    from dgraph_tpu.obs import prom

    src, dst = ref.kronecker(9, 8)
    node = _load(src, dst, span_sample=1.0)
    csr = node.snapshot().preds["follows"].csr
    srv = serve_forever(node, port=0)
    port = srv.server_address[1]

    def post(body):
        req = urllib.request.Request(f"http://127.0.0.1:{port}/analytics",
                                     data=json.dumps(body).encode())
        with urllib.request.urlopen(req) as r:
            return json.loads(r.read())["data"]["analytics"]

    try:
        nodes, want = ref.pagerank(src, dst)
        probes = _hexes(nodes[::7])
        pr = post({"kind": "pr", "pred": "follows", "iterations": 10,
                   "damping": 0.85, "uids": probes, "top": 20})
        assert pr["device"] is True and len(pr["top"]) == 20
        got = np.asarray([pr["values"][h] for h in probes])
        assert ref.rel_error(got, want[::7]) <= TOL
        wcc = post({"kind": "wcc", "pred": "follows", "uids": probes})
        assert set(wcc["labels"]) == set(probes)
        # the client has its answer before the handler closes the clock
        closed = node.metrics.counter("dgraph_stage_requests_total")
        deadline = time.monotonic() + 5
        while closed.value < 2 and time.monotonic() < deadline:
            time.sleep(0.002)
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics") as r:
            parsed = prom.parse(r.read().decode())
    finally:
        srv.shutdown()

    def val(name, **labels):
        return next(v for lab, v in parsed[name]
                    if all(lab.get(k) == x for k, x in labels.items()))

    # a step's reduction ran in the row_reduce kernel compiled for the chip,
    # or (off the chip) in Pallas' interpreter, which the counter leaves out
    reduce = "interpret" if pb.interpret_mode() else "pallas"
    gather = "interpret" if pb.interpret_mode() else "vmem"
    assert an.layouts(csr)["gather"][0] is not None
    for kind, steps in (("pr", 10), ("wcc", wcc["rounds"])):
        assert val("dgraph_analytics_device_runs_total", kind=kind) == 1
        # every step's gather by source rank, by where it ran
        assert val("dgraph_analytics_gather_steps_total", kind=kind,
                   path=gather) == steps
        assert val("dgraph_analytics_gather_steps_total", kind=kind,
                   path="xla") == 0
        assert val("dgraph_analytics_steps_total", kind=kind) == steps
        assert val("dgraph_analytics_kernel_steps_total", kind=kind) == \
            (steps if reduce == "pallas" else 0)
        assert val("dgraph_analytics_edges_read_total", kind=kind) == \
            steps * len(src)
    assert "dgraph_analytics_host_runs_total" not in parsed
    assert val("dgraph_stage_requests_total") == 2
    for stage in ("http.read", "plan", "exec.prep", "dev.dispatch",
                  "dev.wait", "dev.post", "encode", "http.write"):
        assert val("dgraph_stage_us_total", stage=stage) >= 0, stage
    kernels = {}
    roots = [row for row in node.tracer.sink.index(8)
             if row["root"] == "analytics"]
    assert len(roots) == 2
    for row in roots:
        rec = node.tracer.sink.get(row["trace_id"])
        for sp in rec["spans"]:
            if sp["name"] == "device_kernel":
                kernels[sp["attrs"]["kernel"]] = sp["attrs"]
    assert kernels["pb.analytics_pr"]["iterations"] == 10
    assert kernels["pb.analytics_wcc"]["rounds"] == wcc["rounds"]
    assert kernels["pb.analytics_wcc"]["edges"] == len(src)
    assert {k["reduce"] for k in kernels.values()} == {reduce}
    assert {k["gather"] for k in kernels.values()} == {gather}
    assert {k["windows"] for k in kernels.values()} == {
        an.layouts(csr)["gather"][1]}


@pytest.mark.parametrize("program", ["analytics_pr", "analytics_wcc"])
def test_analytics_programs_reduce_rows_in_the_kernel(one_v5e, monkeypatch,
                                                      program):
    """A whole-graph step's per-destination sum / min runs in row_reduce
    compiled for the chip: the program holds a Pallas custom call and no
    scatter into Nd + 1 slots (the segment_sum / segment_min by
    destination rank it replaced). What scatters are left build the
    destination ids once a call (into E_pad + 1) or are FastSV's
    vertex-sized hooking and the component sizes (into Nd)."""
    monkeypatch.setattr(pb, "interpret_mode", lambda: False)
    e_pad, n_items, nd = 16 * pb.EDGE_BLOCK, 32, 3_001

    def arg(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_v5e)

    graph = (arg((e_pad,)), arg((nd + 1,)),
             pb.RowEnds(arg((n_items,)), arg((n_items,))))
    if program == "analytics_pr":
        lowered = pb.analytics_pr.lower(
            *graph, arg((nd,)), arg((64,)), arg(()), arg((), jnp.float32),
            arg((), jnp.float32), top=20)
    else:
        lowered = pb.analytics_wcc.lower(*graph, arg((64,)), push=False)
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text
    scatters = [ln for ln in text.splitlines() if " scatter(" in ln]
    assert scatters and not any(f"[{nd + 1}]" in ln for ln in scatters)


@pytest.mark.parametrize("program", ["analytics_pr", "analytics_wcc"])
def test_analytics_programs_gather_by_source_in_the_kernel(one_v5e,
                                                           monkeypatch,
                                                           program):
    """Handed a GatherLayout, a whole-graph step gathers by source rank
    in gather_sorted compiled for the chip, with the largest table the
    VMEM budget admits (GATHER_TABLE_MAX, 1M values): the program holds
    no XLA gather with an element an edge, only vertex-sized ones
    (FastSV's parents, the probes)."""
    monkeypatch.setattr(pb, "interpret_mode", lambda: False)
    e_pad, n_items = 16 * pb.EDGE_BLOCK, 32
    nd = pb.GATHER_TABLE_MAX // 4 - 1

    def arg(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_v5e)

    graph = (arg((e_pad,)), arg((nd + 1,)),
             pb.RowEnds(arg((n_items,)), arg((n_items,))))
    lay = pb.GatherLayout(arg((e_pad,)), arg((e_pad,)),
                          arg((e_pad // pb.EDGE_BLOCK, 1, 128)))
    if program == "analytics_pr":
        lowered = pb.analytics_pr.lower(
            *graph, arg((nd,)), arg((64,)), arg(()), arg((), jnp.float32),
            arg((), jnp.float32), lay, top=20)
    else:
        lowered = pb.analytics_wcc.lower(*graph, arg((64,)), lay,
                                         push=False)
    text = lowered.compile().as_text()
    assert "gather_sorted" in text
    gathers = [ln for ln in text.splitlines() if " gather(" in ln]
    assert not any(f"[{e_pad}]" in ln for ln in gathers)
