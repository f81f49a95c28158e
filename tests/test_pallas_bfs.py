"""Equality tests for the Pallas pull-BFS kernel (interpret mode on CPU).

The kernel (ops/pallas_bfs.py) is the TPU-native replacement for the
reference's bp128-unpack + per-uid posting iteration hot loop
(worker/task.go:476-602). These tests pin its semantics to a plain host
BFS across the shape edge cases the kernel's blocking scheme creates:
sparse<->dense frontier switch at FRONTIER_CAP, bitmap chunk boundaries
(num_nodes = 32768 +/- 1), edge streams not divisible by EDGE_BLOCK,
multi-chunk bitmaps, and empty frontiers.
"""

import numpy as np
import pytest
import jax.numpy as jnp

from dgraph_tpu.models.rmat import rmat_csr
from dgraph_tpu.ops import pallas_bfs as pb


def host_k_hop(subjects, indptr, indices, seed_uids, num_nodes, hops):
    """Reference host BFS: visited mask + traversed out-edge count per hop."""
    adj = {int(s): indices[indptr[i]:indptr[i + 1]]
           for i, s in enumerate(subjects)}
    visited = np.zeros(num_nodes, dtype=bool)
    visited[seed_uids] = True
    frontier = np.unique(np.asarray(seed_uids, dtype=np.int64))
    traversed = 0
    for _ in range(hops):
        dests = [adj[int(u)] for u in frontier if int(u) in adj]
        total = sum(len(d) for d in dests)
        traversed += total
        if total == 0:
            frontier = np.zeros(0, dtype=np.int64)
            continue
        dest = np.unique(np.concatenate(dests))
        fresh = dest[~visited[dest]]
        visited[fresh] = True
        frontier = fresh
    return visited, traversed


def run_both(subjects, indptr, indices, seed_uids, num_nodes, hops):
    g = pb.prep_pull(subjects, indptr, indices, num_nodes)
    seeds_mask = jnp.zeros(num_nodes, dtype=bool)
    if len(seed_uids):
        seeds_mask = seeds_mask.at[jnp.asarray(np.asarray(seed_uids))].set(True)
    res = pb.k_hop_pull_pallas(g, seeds_mask, hops=hops)
    h_visited, h_traversed = host_k_hop(
        subjects, indptr, indices, seed_uids, num_nodes, hops)
    np.testing.assert_array_equal(np.asarray(res.visited), h_visited)
    assert int(res.traversed) == h_traversed
    # push fast path (explicit seed list) must agree with the mask-only run
    res_p = pb.k_hop_pull_pallas(
        g, seeds_mask, hops=hops,
        seed_uids=np.asarray(seed_uids, dtype=np.int64))
    np.testing.assert_array_equal(np.asarray(res_p.visited), h_visited)
    assert int(res_p.traversed) == h_traversed
    return res


def random_csr(rng, num_nodes, num_edges):
    src = rng.integers(0, num_nodes, size=num_edges)
    dst = rng.integers(0, num_nodes, size=num_edges)
    keep = np.unique(np.stack([src, dst], axis=1), axis=0)
    src, dst = keep[:, 0], keep[:, 1]
    order = np.lexsort((dst, src))
    src, dst = src[order], dst[order]
    subjects, counts = np.unique(src, return_counts=True)
    indptr = np.zeros(len(subjects) + 1, dtype=np.int64)
    np.cumsum(counts, out=indptr[1:])
    return subjects.astype(np.int64), indptr, dst.astype(np.int64)


def test_rmat_multi_hop_matches_host(rng):
    subjects, indptr, indices = rmat_csr(12, 8, seed=5)
    num_nodes = int(max(subjects.max(), indices.max())) + 2
    seeds = np.unique(rng.choice(subjects, size=16, replace=False))
    run_both(subjects, indptr, indices, seeds, num_nodes, hops=3)


def test_empty_frontier():
    subjects, indptr, indices = rmat_csr(8, 4, seed=1)
    num_nodes = int(max(subjects.max(), indices.max())) + 2
    res = run_both(subjects, indptr, indices, np.zeros(0, np.int64),
                   num_nodes, hops=2)
    assert int(res.traversed) == 0
    assert not np.asarray(res.visited).any()


def test_frontier_with_no_out_edges():
    # seed uid exists but has no row in the CSR
    subjects = np.array([1, 2], dtype=np.int64)
    indptr = np.array([0, 1, 2], dtype=np.int64)
    indices = np.array([5, 6], dtype=np.int64)
    run_both(subjects, indptr, indices, np.array([40]), 64, hops=2)


@pytest.mark.parametrize("delta", [-1, 0, 1])
def test_chunk_boundary_num_nodes(rng, delta):
    """num_nodes at 32768 +/- 1: the single/multi-chunk switch and the
    pad-node-outside-uid-space rule (prep_pull adds a chunk when the uid
    space exactly fills the bitmap)."""
    num_nodes = pb.NODES_PER_CHUNK + delta
    subjects, indptr, indices = random_csr(rng, num_nodes, 6000)
    # force edges touching the top of the uid space
    hi = num_nodes - 1
    subjects_l = list(subjects)
    if hi not in subjects_l:
        subjects = np.append(subjects, hi)
        indptr = np.append(indptr, indptr[-1] + 1)
        indices = np.append(indices, 0)
    seeds = np.array([int(subjects[0]), hi], dtype=np.int64)
    run_both(subjects, indptr, indices, seeds, num_nodes, hops=3)


def test_multi_chunk_bitmap(rng):
    """3+ bitmap chunks with edges crossing chunk boundaries. The chunk
    space is SOURCE-RANK-compressed, so >= 2*NODES_PER_CHUNK distinct
    sources are needed to exercise the multi-chunk path."""
    num_nodes = pb.NODES_PER_CHUNK * 2 + 123
    n_edges = pb.NODES_PER_CHUNK * 2 + 40000
    # every node appears as a source at least once -> Ns == num_nodes
    src = np.concatenate([np.arange(num_nodes),
                          rng.integers(0, num_nodes,
                                       size=n_edges - num_nodes)])
    # half the edges deliberately cross into a different chunk
    dst = (src + pb.NODES_PER_CHUNK + rng.integers(0, 100, size=n_edges)) % num_nodes
    order = np.lexsort((dst, src))
    src, dst = src[order], dst[order]
    subjects, counts = np.unique(src, return_counts=True)
    indptr = np.zeros(len(subjects) + 1, dtype=np.int64)
    np.cumsum(counts, out=indptr[1:])
    seeds = np.unique(rng.choice(subjects, size=8))
    res = run_both(subjects, indptr, dst, seeds, num_nodes, hops=3)
    g = pb.prep_pull(subjects, indptr, dst, num_nodes)
    assert g.chunks >= 3
    assert int(res.traversed) > 0


@pytest.mark.parametrize("extra", [0, 1, 7])
def test_edge_count_not_block_aligned(rng, extra):
    """E % EDGE_BLOCK != 0 (and E < EDGE_BLOCK): padding edges must never
    count as active or mark nodes."""
    num_nodes = 2048
    num_edges = pb.EDGE_BLOCK + extra if extra else 300
    subjects, indptr, indices = random_csr(rng, num_nodes, num_edges)
    seeds = np.unique(rng.choice(subjects, size=4))
    run_both(subjects, indptr, indices, seeds, num_nodes, hops=2)


def _star_graph(n_spokes, num_nodes):
    """uid 0 -> spokes 1..n_spokes; each spoke -> uid num_nodes-1."""
    subjects = np.arange(0, n_spokes + 1, dtype=np.int64)
    counts = np.ones(n_spokes + 1, dtype=np.int64)
    counts[0] = n_spokes
    indptr = np.zeros(n_spokes + 2, dtype=np.int64)
    np.cumsum(counts, out=indptr[1:])
    indices = np.concatenate([
        np.arange(1, n_spokes + 1, dtype=np.int64),          # hub fan-out
        np.full(n_spokes, num_nodes - 1, dtype=np.int64),    # spokes converge
    ])
    return subjects, indptr, indices


@pytest.mark.parametrize("n_spokes", [pb.FRONTIER_CAP - 1,
                                      pb.FRONTIER_CAP,
                                      pb.FRONTIER_CAP + 1])
def test_sparse_dense_crossover(n_spokes):
    """Hop 2's frontier is exactly at/under/over FRONTIER_CAP, driving the
    sparse (2-level bucket search) vs dense (chunked bitmap) kernel choice.
    Both must agree with the host BFS."""
    num_nodes = pb.FRONTIER_CAP + 1000
    subjects, indptr, indices = _star_graph(n_spokes, num_nodes)
    res = run_both(subjects, indptr, indices, np.array([0]), num_nodes, hops=2)
    # hop1 traverses n_spokes hub edges; hop2 traverses n_spokes spoke edges
    assert int(res.traversed) == 2 * n_spokes


def test_dense_seed_frontier(rng):
    """Seed frontier itself above FRONTIER_CAP: first hop takes the dense
    path immediately."""
    num_nodes = 40000  # spans 2 chunks
    subjects, indptr, indices = random_csr(rng, num_nodes, 30000)
    seeds = np.unique(rng.choice(subjects, size=pb.FRONTIER_CAP + 500))
    run_both(subjects, indptr, indices, seeds, num_nodes, hops=2)


def test_prep_pull_rejects_out_of_range_uids():
    subjects = np.array([0], dtype=np.int64)
    indptr = np.array([0, 1], dtype=np.int64)
    indices = np.array([100], dtype=np.int64)
    with pytest.raises(ValueError, match="num_nodes"):
        pb.prep_pull(subjects, indptr, indices, num_nodes=50)
    with pytest.raises(ValueError, match="num_nodes"):
        pb.prep_pull(np.array([100], np.int64), indptr,
                     np.array([0], np.int64), num_nodes=50)


def test_matches_xla_pull_path(rng):
    """Cross-check against ops.traversal.k_hop_pull (the XLA formulation the
    kernel replaces) on a mid-size R-MAT graph."""
    from dgraph_tpu.ops import traversal

    subjects, indptr, indices = rmat_csr(11, 8, seed=9)
    num_nodes = int(max(subjects.max(), indices.max())) + 2
    seeds = np.unique(rng.choice(subjects, size=32, replace=False))

    g = pb.prep_pull(subjects, indptr, indices, num_nodes)
    seeds_mask = jnp.zeros(num_nodes, dtype=bool).at[jnp.asarray(seeds)].set(True)
    res = pb.k_hop_pull_pallas(g, seeds_mask, hops=3)

    in_sub, in_ptr, in_src = traversal.reverse_csr(subjects, indptr, indices)
    ref = traversal.k_hop_pull(
        jnp.asarray(subjects), jnp.asarray(indptr), jnp.asarray(in_sub),
        jnp.asarray(in_ptr), jnp.asarray(in_src), seeds_mask, hops=3,
        num_nodes=num_nodes)
    np.testing.assert_array_equal(np.asarray(res.visited),
                                  np.asarray(ref.visited))
    assert int(res.traversed) == int(ref.traversed)


def test_duplicate_seed_uids_not_overcounted(rng):
    """A repeated seed must not be expanded once per occurrence (review r4)."""
    subjects = np.array([0, 1])
    indptr = np.array([0, 1, 2])
    indices = np.array([1, 2])
    g = pb.prep_pull(subjects, indptr, indices, 4)
    mask = jnp.zeros(4, dtype=bool).at[0].set(True)
    res = pb.k_hop_pull_pallas(g, mask, hops=1, seed_uids=np.array([0, 0, 0]))
    assert int(res.traversed) == 1


def test_hops_zero_returns_seeds_as_frontier(rng):
    subjects = np.array([0])
    indptr = np.array([0, 1])
    indices = np.array([1])
    g = pb.prep_pull(subjects, indptr, indices, 4)
    mask = jnp.zeros(4, dtype=bool).at[0].set(True)
    res = pb.k_hop_pull_pallas(g, mask, hops=0)
    np.testing.assert_array_equal(np.asarray(res.frontier), np.asarray(mask))
    assert int(res.traversed) == 0


@pytest.mark.parametrize("n_set", [0, 1, 33, pb.FRONTIER_CAP])
def test_table_of_a_sorted_list_is_the_table_of_its_mask(rng, n_set):
    """The sparse kernel's search table has two makers: _frontier_table
    from a mask (a nonzero over it), and its second half, _table_of_list,
    from a list that a caller already holds (bfs_dist: the row that level
    1 read). Same members, same table, same active prefix."""
    n = 20_000
    members = np.sort(rng.choice(n, size=n_set, replace=False))
    mask = np.zeros(n, dtype=bool)
    mask[members] = True
    flist = np.full(pb.FRONTIER_CAP, np.iinfo(np.int32).max, dtype=np.int32)
    flist[:n_set] = members
    by_mask = np.asarray(pb._frontier_table(jnp.asarray(mask)))
    by_list = np.asarray(pb._table_of_list(jnp.asarray(flist)))
    assert by_mask.shape == (33, 128) and by_mask.dtype == np.int32
    np.testing.assert_array_equal(by_mask, by_list)
    # a member listed twice (where a pad leaves room for it) changes the
    # table and not what it answers
    twice = flist.copy()
    if 0 < n_set < pb.FRONTIER_CAP:
        twice[n_set] = members[0]
    twice.sort()
    src = np.full(pb.EDGE_BLOCK, n, dtype=np.int32)
    src[: 2 * len(members): 2] = members
    want = np.cumsum(np.isin(src, members)).astype(np.int32)
    for table in (by_list, np.asarray(pb._table_of_list(jnp.asarray(twice)))):
        got = pb.active_prefix_sparse(jnp.asarray(table), jnp.asarray(src))
        np.testing.assert_array_equal(np.asarray(got), want)
