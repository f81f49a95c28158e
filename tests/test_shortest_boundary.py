"""What crosses the host-device boundary for one `shortest` (ISSUE 27).

In: three int32 scalars beside the resident graph, as one int32[3] argument
of the one jitted program (`ops/pallas_bfs.bfs_dist`). Out: one uint8[Nd]
array of distance labels, walked on the host as it arrives. Nothing else may run on
the device per request — no eager `jnp` program builds an argument — and
the labels and paths have to equal a plain host BFS.
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
                        int(max(src.max(), dst.max())) + 1,
                        with_host_arrays=True)


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
    return np.asarray(pb.bfs_dist(
        g.in_src_pad, g.in_src_pad_d, g.in_iptr_rank, g.subjects,
        g.in_subjects, np.asarray([src, dr, max_hops], dtype=np.int32),
        chunks=g.chunks, chunks_d=g.chunks_d))


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
         "dst_without_in_edge", "src_beyond_num_nodes", "max_hops_too_short"]


@pytest.mark.parametrize("seed", [3, 11])
@pytest.mark.parametrize("case", CASES)
def test_labels_and_paths_equal_a_host_bfs(case, seed):
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
    else:
        src, dst, d = _pick(edges, lambda d: d is not None and d >= 3)
        max_hops = d - 1

    path = pb.shortest_bfs(g, src, dst, max_hops)
    lv = _host_levels(edges, src)
    # the loop stops after the level that reaches dst, or at max_hops
    stop = d if d is not None and d <= max_hops else max_hops
    want = np.asarray([lv[u] if lv.get(u, 1 << 30) <= stop
                       else pb.DIST_UNREACHED
                       for u in g.host_in_subjects.tolist()])
    got = _labels(g, src, dst, max_hops)
    assert got.dtype == np.uint8 and got.shape == (len(g.host_in_subjects),)
    np.testing.assert_array_equal(got, want)
    if d is None or d > max_hops:
        assert path is None
    else:
        _check_path(edges, path, src, dst, d)


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
