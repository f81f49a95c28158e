"""What crosses the host-device boundary for one `shortest` (ISSUE 27), and
how its first level is read (ISSUE 31).

In: four int32 scalars beside the resident graph, as one int32[4] argument
of the one jitted program (`ops/pallas_bfs.bfs_dist`). Out: one uint8[Nd]
array of distance labels, walked on the host as it arrives. Nothing else may run on
the device per request — no eager `jnp` program builds an argument — and
the labels and paths have to equal a plain host BFS, whether level 1 reads
the root's forward row (out-degree <= FIRST_HOP_CAP: "push") or streams
every in-edge ("stream").
"""

import numpy as np
import pytest

from dgraph_tpu.ops import pallas_bfs as pb


def _pull_graph(edges):
    """PullGraph of a list of (src uid, dst uid) pairs."""
    edges = sorted(set(edges))
    src = np.asarray([e[0] for e in edges], dtype=np.int64)
    dst = np.asarray([e[1] for e in edges], dtype=np.int64)
    subjects, counts = np.unique(src, return_counts=True)
    indptr = np.zeros(len(subjects) + 1, dtype=np.int64)
    np.cumsum(counts, out=indptr[1:])
    return pb.prep_pull(subjects, indptr, dst,
                        int(max(src.max(), dst.max())) + 1)


def _host_levels(edges, src):
    """{uid: BFS distance from src} by plain level-synchronous expansion."""
    out = {}
    for a, b in edges:
        out.setdefault(a, []).append(b)
    dist = {src: 0}
    frontier = [src]
    while frontier:
        nxt = []
        for u in frontier:
            for v in out.get(u, ()):
                if v not in dist:
                    dist[v] = dist[u] + 1
                    nxt.append(v)
        frontier = nxt
    return dist


def _labels(g, src, dst, max_hops):
    dr = int(np.searchsorted(g.host_in_subjects, dst))
    assert g.host_in_subjects[dr] == dst
    # a source with no out-edge goes in as the rank Ns
    out = np.flatnonzero(g.host_subjects == src)
    sr = int(out[0]) if len(out) else len(g.host_subjects)
    return np.asarray(pb.bfs_dist(
        g.in_src_pad, g.in_src_pad_d, g.in_iptr_rank, g.row_ends,
        g.subjects, g.in_subjects, g.fwd_indptr, g.fwd_dst_rank,
        np.asarray([src, sr, dr, max_hops], dtype=np.int32),
        chunks=g.chunks, chunks_d=g.chunks_d,
        first_hop_cap=pb.FIRST_HOP_CAP))


def _two_islands(seed):
    """Random digraph on uids 1..90 and 101..190 with no edge between the
    two ranges; uid 95 has out-edges only, uid 96 in-edges only."""
    rng = np.random.default_rng(seed)
    edges = []
    for lo in (1, 101):
        for _ in range(150):
            a, b = (int(x) for x in rng.integers(lo, lo + 90, size=2))
            if a != b:
                edges.append((a, b))
    edges += [(95, int(rng.integers(1, 91))), (int(rng.integers(1, 91)), 96)]
    return sorted(set(edges))


def _pick(edges, want):
    """First (src, dst, distance) in uid order whose host distance
    satisfies `want(d)`; src and dst both inside the first island."""
    for src in range(1, 91):
        lv = _host_levels(edges, src)
        for dst in range(1, 91):
            if dst != src and want(lv.get(dst)):
                return src, dst, lv.get(dst)
    raise AssertionError("no such pair in this graph")


def _check_path(edges, path, src, dst, want_len):
    assert path[0] == src and path[-1] == dst
    assert len(path) - 1 == want_len
    es = set(edges)
    assert all((a, b) in es for a, b in zip(path, path[1:]))


CASES = ["reachable", "unreachable", "src_without_in_edge",
         "dst_without_in_edge", "src_beyond_num_nodes", "max_hops_too_short",
         "src_without_out_edge", "max_hops_0", "max_hops_1"]

# the cap itself (every root of these graphs is under it: fewer edges in
# the graph than lanes in the slice), and one that splits the roots of one
# graph between the two branches
CAPS = [pb.FIRST_HOP_CAP, 2]


def _want_labels(g, edges, src, stop):
    lv = _host_levels(edges, src)
    return np.asarray([lv[u] if lv.get(u, 1 << 30) <= stop
                       else pb.DIST_UNREACHED
                       for u in g.host_in_subjects.tolist()])


@pytest.mark.parametrize("cap", CAPS)
@pytest.mark.parametrize("seed", [3, 11])
@pytest.mark.parametrize("case", CASES)
def test_labels_and_paths_equal_a_host_bfs(case, seed, cap, monkeypatch):
    monkeypatch.setattr(pb, "FIRST_HOP_CAP", cap)
    edges = _two_islands(seed)
    g = _pull_graph(edges)
    max_hops = 64
    if case == "reachable":
        src, dst, d = _pick(edges, lambda d: d is not None and d >= 3)
    elif case == "unreachable":
        src, d = 5, None
        dst = int(next(u for u in g.host_in_subjects if u > 100))
        assert dst not in _host_levels(edges, src)
    elif case == "src_without_in_edge":
        src = 95
        assert src not in set(g.host_in_subjects.tolist())
        lv = _host_levels(edges, src)
        dst, d = max(((v, k) for v, k in lv.items() if v != src),
                     key=lambda t: (t[1], -t[0]))
    elif case == "dst_without_in_edge":
        src, dst = 5, 95
        assert pb.shortest_bfs(g, src, dst, max_hops) is None
        return
    elif case == "src_beyond_num_nodes":
        src, dst = g.num_nodes + 7, 5
        assert pb.shortest_bfs(g, src, dst, max_hops) is None
        return
    elif case == "src_without_out_edge":
        src, dst, d = 96, 5, None
        assert src not in set(g.host_subjects.tolist())
        assert pb.first_hop_mode(g, src) == "push"      # the empty row
    elif case == "max_hops_0":
        src, dst, d = _pick(edges, lambda d: d == 1)
        max_hops = 0
    elif case == "max_hops_1":
        src, dst, d = _pick(edges, lambda d: d == 1)
        max_hops = 1
    else:
        src, dst, d = _pick(edges, lambda d: d is not None and d >= 3)
        max_hops = d - 1

    path = pb.shortest_bfs(g, src, dst, max_hops)
    # the loop stops after the level that reaches dst, or at max_hops
    stop = d if d is not None and d <= max_hops else max_hops
    got = _labels(g, src, dst, max_hops)
    assert got.dtype == np.uint8 and got.shape == (len(g.host_in_subjects),)
    np.testing.assert_array_equal(got, _want_labels(g, edges, src, stop))
    if d is None or d > max_hops:
        assert path is None
    else:
        _check_path(edges, path, src, dst, d)


def _hub_and_leaf():
    """Uids 1..40 in a ring; hub 50 -> every ring uid and itself (41
    out-edges, a self-loop in its row); leaf 60 -> 7 only; 41 -> 50 and
    3 -> 60, so both have an in-edge. Uid 70, the LAST subject, -> 1, 2, 3:
    its row ends where the edge array ends."""
    edges = [(u, u % 40 + 1) for u in range(1, 41)]
    edges += [(50, u) for u in range(1, 41)] + [(50, 50), (40, 50)]
    edges += [(60, 7), (3, 60), (70, 1), (70, 2), (70, 3)]
    return sorted(set(edges))


# (root, target, cap, the branch level 1 takes)
FIRST_HOPS = {
    "hub_over_the_cap": (50, 20, 8, "stream"),
    "hub_under_the_cap": (50, 20, 64, "push"),
    "hub_at_the_cap": (50, 20, 41, "push"),
    "hub_one_over_the_cap": (50, 20, 40, "stream"),
    "leaf": (60, 20, 8, "push"),
    "self_loop_in_the_row": (50, 50, 64, "push"),
    # E = 87 > cap and the row starts 3 edges before the end: a
    # dynamic_slice of 8 lanes from there is clamped back by 5
    "row_at_the_end_of_the_edges": (70, 20, 8, "push"),
    "row_at_the_end_wider_cap": (70, 20, 64, "push"),
    "more_lanes_than_edges": (70, 20, pb.FIRST_HOP_CAP, "push"),
}


@pytest.mark.parametrize("name", sorted(FIRST_HOPS))
def test_both_first_hops_give_the_host_bfs(name, monkeypatch):
    src, dst, cap, mode = FIRST_HOPS[name]
    monkeypatch.setattr(pb, "FIRST_HOP_CAP", cap)
    edges = _hub_and_leaf()
    g = _pull_graph(edges)
    assert g.num_edges == 87
    assert pb.first_hop_mode(g, src) == mode
    lv = _host_levels(edges, src)
    for max_hops in (0, 1, 2, 64):
        stop = min(lv[dst], max_hops)
        np.testing.assert_array_equal(
            _labels(g, src, dst, max_hops),
            _want_labels(g, edges, src, stop), err_msg=f"{max_hops=}")
        path = pb.shortest_bfs(g, src, dst, max_hops)
        if lv[dst] > max_hops:
            assert path is None
        elif src == dst:
            assert path == [src]
        else:
            _check_path(edges, path, src, dst, lv[dst])


def test_a_row_in_any_order_is_level_twos_frontier():
    """prep_pull takes a row's targets as they come: the program sorts the
    row it read before the sparse kernel searches it."""
    # 1 -> 9, 3, 7, 5 (descending and mixed); each of them -> 20 + itself
    subjects = np.asarray([1, 3, 5, 7, 9], dtype=np.int64)
    indptr = np.asarray([0, 4, 5, 6, 7, 8], dtype=np.int64)
    indices = np.asarray([9, 3, 7, 5, 23, 25, 27, 29], dtype=np.int64)
    g = pb.prep_pull(subjects, indptr, indices, 30)
    assert np.asarray(g.fwd_dst_rank)[:4].tolist() == [3, 0, 2, 1]
    for dst in (23, 25, 27, 29):
        assert pb.shortest_bfs(g, 1, dst, 8) == [1, dst - 20, dst]
        # in_subjects = 3, 5, 7, 9, 23, 25, 27, 29: level 2 reaches all four
        np.testing.assert_array_equal(_labels(g, 1, dst, 8),
                                      [1, 1, 1, 1, 2, 2, 2, 2])


def test_a_search_is_its_own_source_and_target():
    edges = _two_islands(5)
    g = _pull_graph(edges)
    dst = int(g.host_in_subjects[0])
    assert pb.shortest_bfs(g, dst, dst, 8) == [dst]


def test_labels_stay_unsigned_over_a_140_hop_chain():
    """A distance over 127 would read negative through an int8 anywhere
    between the program's output, the fetch and the walk."""
    n = 141
    edges = [(u, u + 1) for u in range(1, n)]
    g = _pull_graph(edges)
    path = pb.shortest_bfs(g, 1, n, 200)
    assert path == list(range(1, n + 1))
    labels = _labels(g, 1, n, 200)
    # in_subjects = uids 2..141, label = uid - 1
    np.testing.assert_array_equal(labels, np.arange(1, n, dtype=np.uint8))
    # one short of the distance: not found, labels stop at max_hops
    assert pb.shortest_bfs(g, 1, n, 139) is None


@pytest.fixture(scope="module")
def backend_compiles():
    """Every backend_compile_duration event of jax.monitoring from here on:
    one per XLA program compiled — or fetched from the persistent cache —
    as obs/devprof.py reads it. Module-scoped: a listener cannot be taken
    off again."""
    from jax import monitoring

    seen = []

    def on_duration(event, _secs, **_kw):
        if event.endswith("backend_compile_duration"):
            seen.append(event)

    monitoring.register_event_duration_secs_listener(on_duration)
    return seen


def test_one_program_a_search(backend_compiles):
    """The first search on a new graph shape compiles bfs_dist and nothing
    else; the next one, from another source to another target, compiles
    nothing. An eager jnp call building an argument would add programs
    here, and run on the device at every request."""
    # Ns = 211 subjects, Nd = 223 destinations: a shape no other test has
    edges = [(u, u + 1) for u in range(1, 212)]
    edges += [(7, 1000 + k) for k in range(12)]
    g = _pull_graph(edges)
    assert len(g.host_subjects) == 211 and len(g.host_in_subjects) == 223

    before = len(backend_compiles)
    assert pb.shortest_bfs(g, 3, 9, 16) == [3, 4, 5, 6, 7, 8, 9]
    assert len(backend_compiles) - before == 1

    before = len(backend_compiles)
    assert pb.shortest_bfs(g, 5, 1003, 16) == [5, 6, 7, 1003]
    assert pb.shortest_bfs(g, 9, 3, 16) is None
    assert len(backend_compiles) - before == 0
