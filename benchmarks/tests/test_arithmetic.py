"""The yardstick's arithmetic on hand-made inputs: statistics, trace
reduction, the generator, the op's reference against brute force."""

import importlib.util
import itertools
import os

import numpy as np
import pytest

from harness import check, loop, stats, trace_reduce
from harness.graph import Graph, bfs_dist, bfs_path, kronecker_edges
from harness.roofline import needed_bytes, peaks

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def op(name):
    spec = importlib.util.spec_from_file_location(
        "op_" + name.replace("-", "_"),
        os.path.join(HERE, "ops", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# -- statistics ---------------------------------------------------------------

LOG = [
    {"op": "a", "t_send": 0.0, "t_done": 0.10, "ok": True,
     "edges": 10},
    {"op": "a", "t_send": 0.1, "t_done": 0.30, "ok": True,
     "edges": 20},
    {"op": "b", "t_send": 0.3, "t_done": 0.35, "ok": True,
     "edges": 1},
    {"op": "a", "t_send": 0.4, "t_done": 0.45, "ok": False,
     "edges": 0},                                   # failed: HTTP error
    {"op": "a", "t_send": 0.5, "t_done": 0.55, "ok": True,
     "wrong": True, "edges": 5},                    # answered wrongly
    {"op": "a", "t_send": 0.9, "t_done": 1.20, "ok": True,
     "edges": 40},                                  # done after the window
]


def test_rate_counts_only_correct_ops_done_inside_the_window():
    assert stats.rate(LOG, 0.0, 1.0) == 3.0
    assert stats.rate(LOG, 0.0, 2.0) == 2.0          # 4 ops over 2 s


def test_failed_and_wrong_count_as_attempted_and_failed():
    assert stats.counts(LOG) == (6, 2)


def test_failed_request_is_never_a_fast_one():
    lat = stats.latencies_ms(LOG)
    assert sorted(lat)[-2:] == [stats.FAILED_MS, stats.FAILED_MS]
    assert stats.percentile(lat, 95) == stats.FAILED_MS
    assert stats.percentile(lat, 50) == pytest.approx(200.0)
    assert stats.latencies_ms(LOG, op="b") == [pytest.approx(50.0)]
    assert len(stats.latencies_ms(LOG, op="a")) == 5


@pytest.mark.parametrize("q,want", [(50, 3), (95, 5), (100, 5), (1, 1),
                                    (20, 1), (21, 2)])
def test_percentile_is_nearest_rank(q, want):
    assert stats.percentile([5, 1, 4, 2, 3], q) == want


def test_percentile_of_nothing_raises():
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_mean_of_compared_takes_the_judged_correct_ones():
    log = [dict(r, judged=i != 1) for i, r in enumerate(LOG)]
    # judged and good: edges 10, 1 and 40 (20 is outside the sample, 0
    # failed, 5 was answered wrongly)
    assert stats.mean_of_compared(log, "edges") == pytest.approx(17.0)
    assert stats.mean_of_compared(LOG, "edges") is None


def test_the_compared_sample_is_seeded_and_bounded():
    assert check.draw_sample(7, seed=1) == list(range(7))
    a, b = check.draw_sample(5000, 3000000011), check.draw_sample(5000, 5)
    assert a == check.draw_sample(5000, 3000000011) and a != b
    assert len(a) == len(set(a)) == check.SAMPLE and a == sorted(a)
    assert 0 <= a[0] and a[-1] < 5000


def test_requests_outside_the_sample_still_have_to_be_answered():
    reqs = [{"ok": True, "body": b'{"data": {}}', "error": None},
            {"ok": True, "body": b"<html>", "error": None},
            {"ok": False, "body": None, "error": "timeout"}]
    check.envelopes_only(reqs)
    assert [r["ok"] for r in reqs] == [True, False, False]
    assert not any(r["wrong"] or r["judged"] for r in reqs)
    assert stats.counts(reqs) == (3, 2)


# -- the deck ------------------------------------------------------------------

def test_deck_holds_each_op_in_exactly_its_share():
    assert sorted(loop.deck({"shortest": 0.8, "recurse3": 0.2})) == \
        ["recurse3"] + ["shortest"] * 4
    d = loop.deck({"point": 0.65, "neighbors": 0.18, "write-edge": 0.15,
                   "shortest": 0.02})
    assert len(d) == 100 and d.count("shortest") == 2 and d.count("point") == 65
    assert loop.deck({"shortest": 1.0}) == ["shortest"]
    with pytest.raises(ValueError):
        loop.deck({"a": 0.5, "b": 0.4})
    with pytest.raises(ValueError):
        loop.deck({"a": 0.999, "b": 0.001})


def test_every_client_deals_whole_decks():
    class Op:
        @staticmethod
        def draw(ctx, rng):
            return {}
    ops = {"a": Op, "b": Op}
    st = loop.op_stream({"ops": {"a": 0.75, "b": 0.25}}, ops, None, 7, 0)
    names = [next(st)[0] for _ in range(40)]
    for lo in range(0, 40, 4):
        assert sorted(names[lo: lo + 4]) == ["a", "a", "a", "b"]
    other = loop.op_stream({"ops": {"a": 0.75, "b": 0.25}}, ops, None, 8, 0)
    assert [next(other)[0] for _ in range(40)] != names


class _Srv:
    def __init__(self, fail):
        self.fail = fail

    def raw(self, method, path, body, timeout):
        if self.fail:
            raise RuntimeError("HTTP 500")
        return b'{"data": {}}'


@pytest.mark.parametrize("fail", [False, True])
def test_send_times_a_request_and_keeps_a_failure(fail):
    class Op:
        @staticmethod
        def request(p, ctx):
            return "POST", "/query", "{}"
    r = loop.send(_Srv(fail), {"q": Op}, None, "q", {})
    assert r["ok"] is (not fail) and r["t_done"] >= r["t_send"]
    assert (r["error"] is not None) is fail


# -- trace reduction ----------------------------------------------------------

IV = [(1.0, 2.0), (1.5, 2.5), (4.0, 5.0), (4.2, 4.4), (7.0, 7.0)]


def test_union_merges_overlaps_and_drops_empty():
    assert trace_reduce.union(IV) == [(1.0, 2.5), (4.0, 5.0)]


def test_busy_seconds_clips_to_the_window():
    assert trace_reduce.busy_seconds(IV, 0.0, 10.0) == pytest.approx(2.5)
    assert trace_reduce.busy_seconds(IV, 2.0, 4.5) == pytest.approx(1.0)


def test_gaps_are_the_complement():
    assert trace_reduce.gaps(IV, 0.0, 6.0) == [(0.0, 1.0), (2.5, 4.0),
                                               (5.0, 6.0)]
    busy = trace_reduce.busy_seconds(IV, 0.0, 6.0)
    idle = sum(e - s for s, e in trace_reduce.gaps(IV, 0.0, 6.0))
    assert busy + idle == pytest.approx(6.0)


def test_reduce_names_ops_and_gaps():
    dev = {"/device:TPU:0": [("fusion.1", 1.0, 2.0), ("copy", 1.5, 2.5),
                             ("fusion.1", 4.0, 5.0)]}
    host = [("ExecuteOnLocalDevices", 2.4, 3.9), ("gc", 0.0, 0.2)]
    r = trace_reduce.reduce(dev, host, 0.0, 6.0)
    assert r["busy_s"] == pytest.approx(2.5)
    assert r["device_ops"][0] == ("op fusion.1", pytest.approx(2.0))
    name, secs = r["idle_gaps"][0]
    assert secs == pytest.approx(1.5)
    assert "ExecuteOnLocalDevices" in name and "after copy" in name


def test_short_op_names_and_containers():
    f = trace_reduce.short_op
    assert f("%fusion.47 = s32[148757]{0:T(1024)S(1)} fusion(s32[3940352]{0} "
             "%x), kind=kLoop") == "fusion.47 fusion"
    assert f("%while.21 = (s32[]{:T(128)}, pred[148756]{0}) while((s32[]) "
             "%t), condition=%c") is None
    assert f("%cond.6.clone = (s32[3940352]{0:T(1024)}) conditional(s32[] "
             "%c), branch_computations={%a}") is None
    assert f('%k.2 = s32[30784,128]{1,0} custom-call(s32[33,128]{1,0} %p), '
             'custom_call_target="tpu_custom_call"') == "k.2 tpu_custom_call"
    assert f("jit_bfs_dist(6531728195678789724)") == "jit_bfs_dist"


def test_device_ops_lists_programs_then_leaf_ops():
    mods = [("jit_bfs_dist(1)", 0.0, 2.0), ("jit_bfs_dist(1)", 3.0, 4.0),
            ("jit_small(2)", 5.0, 5.5)]
    ops = [("%while.1 = (s32[]) while((s32[]) %t)", 0.0, 2.0),
           ("%fusion.2 = s32[8]{0} fusion(s32[8]{0} %a)", 0.0, 1.5)]
    got = trace_reduce.device_ops(mods, ops, n=4)
    assert got == [("program jit_bfs_dist", pytest.approx(3.0)),
                   ("program jit_small", pytest.approx(0.5)),
                   ("op fusion.2 fusion", pytest.approx(1.5))]


def test_reduce_without_a_device_plane_reports_no_busy_time():
    r = trace_reduce.reduce({}, [("x", 0.0, 1.0)], 0.0, 1.0)
    assert "busy_s" not in r and r["device_planes"] == 0


def test_busy_is_averaged_over_device_planes():
    dev = {"/device:TPU:0": [("a", 0.0, 1.0)],
           "/device:TPU:1": [("a", 0.0, 3.0)]}
    assert trace_reduce.reduce(dev, [], 0.0, 4.0)["busy_s"] == \
        pytest.approx(2.0)


# -- peaks and bytes ----------------------------------------------------------

def test_peaks_table_knows_the_v5e_and_refuses_the_unknown():
    assert peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks("TPU v9")


def test_needed_bytes():
    assert needed_bytes({"edges": 10, "nodes": 3}) == 64


# -- the ops' references against brute force on a 20-node graph ---------------

@pytest.fixture(scope="module")
def small():
    rng = np.random.default_rng(5)
    pairs = {(int(a), int(b)) for a, b in rng.integers(1, 21, (60, 2))
             if a != b}
    edges = np.asarray(sorted(pairs), dtype=np.int64)
    return Graph(edges, seed=5, score_mod=24, grp_mod=4), sorted(pairs)


def brute_dist(pairs, src, dst):
    adj = {}
    for a, b in pairs:
        adj.setdefault(a, []).append(b)
    dist, frontier = {src: 0}, [src]
    while frontier:
        nxt = []
        for u in frontier:
            for v in adj.get(u, ()):
                if v not in dist:
                    dist[v] = dist[u] + 1
                    nxt.append(v)
        frontier = nxt
    return dist.get(dst)


def test_bfs_matches_brute_force(small):
    g, pairs = small
    for src, dst in itertools.product(range(1, 21), repeat=2):
        want = brute_dist(pairs, src, dst)
        assert bfs_dist(g, src, dst)[0] == want
        path = bfs_path(g, src, dst)
        assert (path is None) == (want is None)
        if path is not None:
            assert len(path) - 1 == want
            assert all((a, b) in set(pairs) for a, b in zip(path, path[1:]))


def test_shortest_verify(small):
    g, pairs = small
    sh = op("shortest")
    src, dst = next((s, d) for s, d in itertools.product(range(1, 21), repeat=2)
                    if (brute_dist(pairs, s, d) or 0) >= 2)
    p = {"src": src, "dst": dst}
    good = sh.answer(g, p)
    problem, read = sh.verify(g, p, good)
    assert problem is None and read["edges"] > 0 and read["nodes"] >= 2
    longer = dict(good, path=good["path"][:1] + good["path"])
    assert sh.verify(g, p, longer)[0] is not None
    assert sh.verify(g, p, {"path": None})[0] is not None
    off = dict(good, weight=good["weight"] + 1)
    assert sh.verify(g, p, off)[0] is not None
    apart = next(({"src": s, "dst": d}
                  for s, d in itertools.product(range(1, 21), repeat=2)
                  if brute_dist(pairs, s, d) is None), None)
    if apart is not None:
        assert sh.verify(g, apart, {"path": None})[0] is None
        assert sh.verify(g, apart, good)[0] is not None


def test_shortest_draws_roots_with_an_edge(small):
    g, _ = small

    class Ctx:
        pass
    Ctx.g = g
    rng = np.random.default_rng(1)
    for _ in range(50):
        p = op("shortest").draw(Ctx, rng)
        assert g.degree[p["src"]] > 0 and g.degree[p["dst"]] > 0


def test_shortest_parse_reads_the_servers_shape():
    sh = op("shortest")
    data = {"_path_": [{"uid": "0x1", "follows": [{"uid": "0x2"}],
                        "_weight_": 1.0}],
            "sp_out": [{"uid": "0x1"}, {"uid": "0x2"}]}
    assert sh.parse(data) == {"path": [1, 2], "weight": 1.0, "uids": [1, 2]}
    assert sh.parse({}) == {"path": None}


DATA = {"generator": "graph500-kronecker", "scale": 8, "edge_factor": 16,
        "a": 0.57, "b": 0.19, "c": 0.19, "directed": False,
        "permute_labels": True, "dedup": True, "self_loops": False,
        "schema": "follows: [uid] .\n", "score_mod": 24, "grp_mod": 64}


def gen(seed, scale=8):
    return kronecker_edges(scale, 16, 0.57, 0.19, 0.19, seed)


def test_generator_is_seeded_and_clean():
    a, b, c = gen(3000000011), gen(3000000011), gen(7)
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert (a[:, 0] != a[:, 1]).all()
    assert len(np.unique(a, axis=0)) == len(a)
    assert a.min() >= 0 and a.max() < 256


def test_graph_is_undirected():
    fwd = {(int(u), int(v)) for u, v in gen(11)}
    assert all((v, u) in fwd for u, v in fwd)


def test_labels_are_scrambled_from_the_seed():
    """Raw R-MAT labels put the heavy vertices at the low numbers (A is
    the largest quadrant); scrambled, the heaviest one is somewhere else
    with every seed, and the low half holds no more than its share."""
    heaviest = set()
    for seed in range(6):
        deg = np.bincount(gen(seed, scale=10)[:, 0], minlength=1024)
        heaviest.add(int(np.argmax(deg)))
        assert 0.5 < deg[:512].sum() / deg[512:].sum() < 2.0
    assert len(heaviest) >= 5 and 0 not in heaviest


def test_from_config_honours_or_refuses_every_data_key():
    g = Graph.from_config(DATA, 5)
    assert all(g.has_edge(int(t), u) for u in g.subjects[:40].tolist()
               for t in g.targets(u))
    assert np.array_equal(g.indices, Graph.from_config(DATA, 5).indices)
    for bad in ({"weights": "uniform"}, {"generator": "rmat"},
                {"directed": True}, {"permute_labels": False},
                {"dedup": False}, {"self_loops": True}):
        with pytest.raises(ValueError):
            Graph.from_config({**DATA, **bad}, 5)
    with pytest.raises(KeyError):
        Graph.from_config({k: v for k, v in DATA.items()
                           if k != "permute_labels"}, 5)


def test_without_edges_keeps_rows_and_values(small):
    g, _ = small
    h = g.without_edges(0.3, np.random.default_rng(2))
    assert len(h.indices) < len(g.indices)
    assert np.array_equal(h.subjects, g.subjects)
    assert np.array_equal(h.grp, g.grp)
    for u in g.subjects.tolist():
        assert set(h.targets(u).tolist()) <= set(g.targets(u).tolist())
        assert h.degree[u] == len(h.targets(u))
