"""Equality tests for the Pallas pull-BFS kernel (interpret mode on CPU).

The kernel (ops/pallas_bfs.py) is the TPU-native replacement for the
reference's bp128-unpack + per-uid posting iteration hot loop
(worker/task.go:476-602). These tests pin the programs a request reaches
to a plain host BFS across the shape edge cases the kernel's blocking
scheme creates: sparse<->dense frontier switch at FRONTIER_CAP, bitmap
chunk boundaries (num_nodes = 32768 +/- 1), edge streams not divisible by
EDGE_BLOCK, multi-chunk bitmaps, empty frontiers, and where destination rows
end relative to the edge blocks and the rank tiles (a search's kernels hand
back one value a row, picked inside the block the row ends in).

Every case runs under each program that can express it:
  push / stream  bfs_dist (`shortest`) from single roots, its first level
                 over the root's forward row (first_hop_cap at its default)
                 or over the in-edge stream (first_hop_cap=1: every root
                 with more than one out-edge streams);
  recurse        recurse_fused (`@recurse`) from the whole seed set.

The recurse programs dedup edges on VERTICES (a vertex's out-edges are all
first traversed in the level the vertex is first in a frontier);
test_recurse_dedups_edges holds them, recurse_step and the host's lazy
matrices to a plain loop over a per-edge `seen`, on graphs built to
re-enter vertices.
"""

import hashlib
from types import SimpleNamespace

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from dgraph_tpu.models.rmat import rmat_csr
from dgraph_tpu.ops import pallas_bfs as pb
from dgraph_tpu.query import recurse as recmod

FIRST_HOP_CAPS = {"push": pb.FIRST_HOP_CAP, "stream": 1}
programs = pytest.mark.parametrize("program", ["push", "stream", "recurse"])


def host_k_hop(subjects, indptr, indices, seed_uids, num_nodes, hops):
    """Reference host BFS: visited mask + traversed out-edge count per hop,
    and the level each uid was first reached at (DIST_UNREACHED = never)."""
    adj = {int(s): indices[indptr[i]:indptr[i + 1]]
           for i, s in enumerate(subjects)}
    visited = np.zeros(num_nodes, dtype=bool)
    visited[seed_uids] = True
    dist = np.where(visited, 0, pb.DIST_UNREACHED).astype(np.uint8)
    frontier = np.unique(np.asarray(seed_uids, dtype=np.int64))
    traversed = 0
    for h in range(hops):
        dests = [adj[int(u)] for u in frontier if int(u) in adj]
        total = sum(len(d) for d in dests)
        traversed += total
        if total == 0:
            frontier = np.zeros(0, dtype=np.int64)
            continue
        dest = np.unique(np.concatenate(dests))
        fresh = dest[~visited[dest]]
        visited[fresh] = True
        dist[fresh] = h + 1
        frontier = fresh
    return visited, traversed, dist


def host_recurse(subjects, indptr, indices, seed_uids, depth,
                 allow_loop=False):
    """Reference host @recurse (edge dedup, recurse.go expandRecurse) over
    a per-EDGE `seen`: per level, the uids reached over a never-traversed
    edge (any edge under `allow_loop`), the count of every out-edge of the
    level's frontier, and the level's uid matrix: for each vertex of its
    sorted frontier, the targets of the fresh edges of its row."""
    row_of = {int(s): i for i, s in enumerate(subjects)}
    seen = np.zeros(len(indices), dtype=bool)
    frontier = np.unique(np.asarray(seed_uids, dtype=np.int64))
    levels = []
    for _ in range(depth):
        traversed, fresh, matrix = 0, [], []
        for u in frontier:
            r = row_of.get(int(u))
            edges = (np.arange(indptr[r], indptr[r + 1]) if r is not None
                     else np.zeros(0, dtype=np.int64))
            traversed += len(edges)
            if not allow_loop:
                edges = edges[~seen[edges]]
                seen[edges] = True
            fresh.append(edges)
            matrix.append(indices[edges])
        frontier = (np.unique(indices[np.concatenate(fresh)]) if fresh
                    else np.zeros(0, dtype=np.int64))
        levels.append((frontier, traversed, matrix))
    return levels


def fused(g, seed_uids, depth, allow_loop=False,
          first_hop_cap=pb.FIRST_HOP_CAP):
    """One recurse_fused call as the executor makes it: the seeds in as
    one host array of ranks. Returns host (masks_p, traversed)."""
    seeds = pb.stack_seeds(g, [pb.seed_ranks(g, seed_uids)], 1)[0]
    return jax.device_get(pb.recurse_fused(
        *pb.fused_graph_args(g), seeds, depth=depth, chunks=g.chunks,
        chunks_d=g.chunks_d, allow_loop=allow_loop,
        first_hop_cap=first_hop_cap))


def check_search(g, csr, root, hops, first_hop_cap):
    """bfs_dist's uint8[Nd] labels from `root` against the host BFS levels.
    The destination is a vertex the search never finds within `hops`, or
    else one of the farthest: the early exit then cuts nothing off."""
    visited, _traversed, dist = host_k_hop(*csr, [root], g.num_nodes, hops)
    want = dist[g.host_in_subjects]
    labels = np.asarray(pb.bfs_dist(
        g.in_src_pad, g.in_src_pad_d, g.in_iptr_rank, g.row_ends,
        g.subjects, g.in_subjects, g.fwd_indptr, g.fwd_dst_rank,
        np.asarray([root, pb._source_row(g, root)[0], int(np.argmax(want)),
                    hops], dtype=np.int32),
        chunks=g.chunks, chunks_d=g.chunks_d, first_hop_cap=first_hop_cap))
    assert labels.dtype == np.uint8
    np.testing.assert_array_equal(labels, want)
    # visited within k hops = label <= k, scattered through in_subjects
    got = np.zeros(g.num_nodes, dtype=bool)
    got[root] = True
    got[g.host_in_subjects[labels <= hops]] = True
    np.testing.assert_array_equal(got, visited)


def check_recurse(g, csr, seed_uids, hops):
    """recurse_fused's per-level reached sets and traversed counts against
    the host recurse; their union with the seeds is the BFS's visited set.
    Returns the per-level traversed counts."""
    masks_h, trav = fused(g, seed_uids, hops)
    nd = len(g.host_in_subjects)
    union = np.zeros(g.num_nodes, dtype=bool)
    union[seed_uids] = True
    levels = host_recurse(*csr, seed_uids, hops)
    for lvl, (want_reached, want_traversed, _matrix) in enumerate(levels):
        reached = g.host_in_subjects[pb.unpack_words(masks_h[lvl], nd)]
        np.testing.assert_array_equal(reached, want_reached)
        assert int(trav[lvl]) == want_traversed
        union[reached] = True
    visited, _traversed, _dist = host_k_hop(
        *csr, seed_uids, g.num_nodes, hops)
    np.testing.assert_array_equal(union, visited)
    return trav


def run_both(program, subjects, indptr, indices, seed_uids, num_nodes, hops):
    """The host BFS against `program` (module docstring) from `seed_uids`:
    a search from each one, or one recurse from all of them."""
    csr = (subjects, indptr, indices)
    g = pb.prep_pull(subjects, indptr, indices, num_nodes)
    if program == "recurse":
        return check_recurse(g, csr, seed_uids, hops)
    cap = FIRST_HOP_CAPS[program]
    for root in seed_uids:
        check_search(g, csr, int(root), hops, cap)
    return [pb.first_hop_pushes(pb._source_row(g, int(root))[1], cap)
            for root in seed_uids]


def fan_out_root(subjects, indptr):
    """A uid with the most out-edges: first_hop_cap=1 makes it stream."""
    return int(subjects[np.argmax(np.diff(indptr))])


def csr_of(src, dst):
    """CSR (subjects, indptr, indices) of the distinct (src, dst) pairs."""
    keep = np.unique(np.stack([src, dst], axis=1), axis=0)   # sorts by row
    src, dst = keep[:, 0], keep[:, 1]
    subjects, counts = np.unique(src, return_counts=True)
    indptr = np.zeros(len(subjects) + 1, dtype=np.int64)
    np.cumsum(counts, out=indptr[1:])
    return subjects.astype(np.int64), indptr, dst.astype(np.int64)


def random_csr(rng, num_nodes, num_edges):
    """num_edges distinct edges, uniform over the pairs."""
    pairs = rng.choice(num_nodes * num_nodes, size=num_edges, replace=False)
    return csr_of(pairs // num_nodes, pairs % num_nodes)


@programs
def test_rmat_multi_hop_matches_host(rng, program):
    subjects, indptr, indices = rmat_csr(12, 8, seed=5)
    num_nodes = int(max(subjects.max(), indices.max())) + 2
    seeds = np.unique(rng.choice(subjects, size=16, replace=False))
    if program != "recurse":
        seeds = np.append(seeds[:3], fan_out_root(subjects, indptr))
    out = run_both(program, subjects, indptr, indices, seeds, num_nodes,
                   hops=3)
    if program != "recurse":
        assert out[-1] == (program == "push")


@pytest.mark.parametrize("program", ["push", "recurse"])
def test_empty_frontier(program):
    """Nothing to expand: a recurse from no seed, a search of zero hops."""
    subjects, indptr, indices = rmat_csr(8, 4, seed=1)
    num_nodes = int(max(subjects.max(), indices.max())) + 2
    if program == "recurse":
        trav = run_both(program, subjects, indptr, indices,
                        np.zeros(0, np.int64), num_nodes, hops=2)
        assert not trav.any()
    else:
        run_both(program, subjects, indptr, indices, subjects[:1],
                 num_nodes, hops=0)


@programs
def test_frontier_with_no_out_edges(program):
    # seed uid exists but has no row in the CSR
    subjects = np.array([1, 2], dtype=np.int64)
    indptr = np.array([0, 1, 2], dtype=np.int64)
    indices = np.array([5, 6], dtype=np.int64)
    run_both(program, subjects, indptr, indices, np.array([40]), 64, hops=2)


@programs
@pytest.mark.parametrize("delta", [-1, 0, 1])
def test_chunk_boundary_num_nodes(rng, delta, program):
    """num_nodes at 32768 +/- 1, every uid a source and a destination (the
    bitmaps are over the rank spaces): the single/multi-chunk switch and the
    pad-rank-outside-the-ranks rule (prep_pull adds a chunk when the ranks
    exactly fill the bitmap)."""
    num_nodes = pb.NODES_PER_CHUNK + delta
    ring = np.arange(num_nodes)
    subjects, indptr, indices = csr_of(
        np.concatenate([ring, rng.integers(0, num_nodes, size=6000)]),
        np.concatenate([(ring + 1) % num_nodes,
                        rng.integers(0, num_nodes, size=6000)]))
    g = pb.prep_pull(subjects, indptr, indices, num_nodes)
    assert g.chunks == g.chunks_d == (1 if delta < 0 else 2)
    # the top of the uid space, and a root that streams at first_hop_cap=1
    seeds = np.array([fan_out_root(subjects, indptr), num_nodes - 1])
    out = run_both(program, subjects, indptr, indices, seeds, num_nodes,
                   hops=3)
    if program != "recurse":
        assert out[0] == (program == "push")


@programs
def test_multi_chunk_bitmap(rng, program):
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
    subjects, indptr, dst = csr_of(src, dst)
    seeds = np.unique(rng.choice(subjects, size=8))
    if program != "recurse":
        seeds = np.append(seeds[:2], fan_out_root(subjects, indptr))
    out = run_both(program, subjects, indptr, dst, seeds, num_nodes, hops=3)
    g = pb.prep_pull(subjects, indptr, dst, num_nodes)
    assert g.chunks >= 3
    if program == "recurse":
        assert out.all()
    else:
        assert out[-1] == (program == "push")


@programs
@pytest.mark.parametrize("extra", [0, 1, 7])
def test_edge_count_not_block_aligned(rng, extra, program):
    """E % EDGE_BLOCK != 0 (and E < EDGE_BLOCK): padding edges must never
    count as active or mark nodes."""
    num_nodes = 2048
    num_edges = pb.EDGE_BLOCK + extra if extra else 300
    subjects, indptr, indices = random_csr(rng, num_nodes, num_edges)
    assert len(indices) == num_edges
    seeds = np.unique(rng.choice(subjects, size=4))
    if program != "recurse":
        seeds = np.append(seeds, fan_out_root(subjects, indptr))
    out = run_both(program, subjects, indptr, indices, seeds, num_nodes,
                   hops=2)
    if program != "recurse":
        assert out[-1] == (program == "push")


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


@programs
@pytest.mark.parametrize("n_spokes", [pb.FRONTIER_CAP - 1,
                                      pb.FRONTIER_CAP,
                                      pb.FRONTIER_CAP + 1])
def test_sparse_dense_crossover(n_spokes, program):
    """Hop 2's frontier is exactly at/under/over FRONTIER_CAP, driving the
    sparse (2-level bucket search) vs dense (chunked bitmap) kernel choice —
    and, for a search at the default first_hop_cap, whether the hub's row
    still fits the push. All must agree with the host BFS."""
    num_nodes = pb.FRONTIER_CAP + 1000
    subjects, indptr, indices = _star_graph(n_spokes, num_nodes)
    out = run_both(program, subjects, indptr, indices, np.array([0]),
                   num_nodes, hops=2)
    if program == "recurse":
        # level 1 traverses n_spokes hub edges, level 2 n_spokes spoke edges
        assert out.tolist() == [n_spokes, n_spokes]
    else:
        assert out == [program == "push" and n_spokes <= pb.FIRST_HOP_CAP]


def test_dense_seed_frontier(rng):
    """Seed frontier itself above FRONTIER_CAP: the first level takes the
    dense path immediately (a recurse: a search has one root)."""
    num_nodes = 40000  # spans 2 chunks
    subjects, indptr, indices = random_csr(rng, num_nodes, 30000)
    seeds = np.unique(rng.choice(subjects, size=pb.FRONTIER_CAP + 500))
    run_both("recurse", subjects, indptr, indices, seeds, num_nodes, hops=2)


def planted_csr(rng, in_degrees, num_nodes):
    """A graph whose destination rank v (uid v + 1) has exactly
    in_degrees[v] in-edges from distinct sources, so where each row ends
    in the dst-sorted stream is the caller's choice. Sources are uniform,
    but: the root (uid num_nodes - 1, never a destination) points at three
    destinations, and each of those at about 70% of all rows — level 2 of a
    search from the root reaches most of the graph, so level 3's frontier is
    dense wherever there are enough destinations. Returns the CSR and the
    root."""
    nd = len(in_degrees)
    root = num_nodes - 1
    mids = 1 + rng.choice(np.flatnonzero(np.asarray(in_degrees) >= 4),
                          size=3, replace=False)
    src, dst = [], []
    for v, d in enumerate(in_degrees):
        row = rng.choice(num_nodes - 1, size=d, replace=False)
        for j, m in enumerate(mids[: d]):
            if rng.random() < 0.7 and m not in row:
                row[j] = m
        if v + 1 in mids and root not in row:
            row[3] = root
        src.append(row)
        dst.append(np.full(d, v + 1))
    assert nd + 1 < num_nodes
    return csr_of(np.concatenate(src), np.concatenate(dst)), root


def _fill(rng, n_rows):
    return rng.integers(1, 6, size=n_rows).tolist()


# in-degrees by destination rank, by what the stream's first blocks hold
ROW_END_SHAPES = {
    # row 1 ends on block 0's last lane, row 2 starts on block 1's first
    "last_lane_first_lane": lambda rng: (
        [pb.EDGE_BLOCK - 192, 192, 300] + _fill(rng, 6000)),
    # row 1 runs from block 0 into block 2: no row ends in block 1
    "row_spans_three_blocks": lambda rng: (
        [100, 2 * pb.EDGE_BLOCK + 3000] + _fill(rng, 6000)),
    # block 1 holds EDGE_BLOCK rows of one edge: nine rank tiles' worth
    "every_edge_ends_a_row": lambda rng: (
        [pb.EDGE_BLOCK] + [1] * pb.EDGE_BLOCK + _fill(rng, 2000)),
}


@programs
@pytest.mark.parametrize("shape", sorted(ROW_END_SHAPES))
def test_row_ends_against_the_edge_blocks(rng, shape, program):
    """Where rows end relative to the kernel's edge blocks: a row-end kernel
    picks a row's value in the block the row ends in, whatever block it
    began in. Level 2 runs the sparse kernel, level 3 the dense one."""
    in_degrees = ROW_END_SHAPES[shape](rng)
    (subjects, indptr, indices), root = planted_csr(rng, in_degrees, 24_000)
    g = pb.prep_pull(subjects, indptr, indices, 24_000)
    np.testing.assert_array_equal(np.diff(g.host_in_iptr), in_degrees)
    ends_in = np.bincount((g.host_in_iptr[1:] - 1) // pb.EDGE_BLOCK)
    last = g.host_in_iptr[1:] - 1
    assert {"last_lane_first_lane": pb.EDGE_BLOCK - 1 in last
            and pb.EDGE_BLOCK in g.host_in_iptr,
            "row_spans_three_blocks": ends_in[1] == 0 and ends_in[2] > 0,
            "every_edge_ends_a_row": ends_in[1] == pb.EDGE_BLOCK}[shape]
    # level 3's frontier (level 2's fresh vertices) takes the dense kernel
    _visited, _trav, dist = host_k_hop(subjects, indptr, indices, [root],
                                       24_000, 3)
    assert np.count_nonzero(dist == 2) > pb.SPARSE_MAX
    assert np.count_nonzero(dist == 3) > 0
    out = run_both(program, subjects, indptr, indices, np.array([root]),
                   24_000, hops=3)
    if program != "recurse":
        assert out == [program == "push"]


@programs
@pytest.mark.parametrize("nd", [127, 128, 129, 1023, 1024, 1025,
                                5119, 5120, 5121])
def test_destination_count_against_the_rank_tiles(rng, nd, program):
    """Nd one under, at and over a lane row (128) and a rank tile
    (RANK_TILE = 1024) of the row-end kernels' output; from 5 * 1024 on,
    level 3's frontier is dense."""
    assert pb.RANK_TILE == 1024
    num_nodes = nd + 500
    (subjects, indptr, indices), root = planted_csr(
        rng, _fill(rng, nd), num_nodes)
    g = pb.prep_pull(subjects, indptr, indices, num_nodes)
    assert len(g.host_in_subjects) == nd
    _visited, _trav, dist = host_k_hop(subjects, indptr, indices, [root],
                                       num_nodes, 3)
    assert (np.count_nonzero(dist == 2) > pb.SPARSE_MAX) == (nd > 5000)
    run_both(program, subjects, indptr, indices, np.array([root]),
             num_nodes, hops=3)


@pytest.mark.parametrize("shape", sorted(ROW_END_SHAPES))
def test_row_end_items_hold_every_row_once(rng, shape):
    """prep_pull's RowEnds: every destination rank has exactly one item of
    its rank tile and of the edge block its last in-edge is in; every edge
    block has an item; items come in stream order, in a multiple of the
    class size, the padding repeating the last item."""
    in_degrees = ROW_END_SHAPES[shape](rng)
    (subjects, indptr, indices), _root = planted_csr(rng, in_degrees, 24_000)
    g = pb.prep_pull(subjects, indptr, indices, 24_000)
    block, tile = (np.asarray(a) for a in g.row_ends)
    n_blocks = g.in_src_pad.shape[0] // pb.EDGE_BLOCK
    assert len(block) % pb._ITEM_CLASS == 0 and len(block) == len(tile)
    items = list(zip(block.tolist(), tile.tolist()))
    real = sorted(set(items))
    assert items == real + [real[-1]] * (len(items) - len(real))
    assert (np.diff(tile) >= 0).all()
    last = g.host_in_iptr[1:] - 1
    want = set(zip((last // pb.EDGE_BLOCK).tolist(),
                   (np.arange(len(last)) // pb.RANK_TILE).tolist()))
    assert want <= set(real)
    # what is there beyond that: one item for each block no row ends in
    assert sorted(b for b, _t in set(real) - want) == sorted(
        set(range(n_blocks)) - set((last // pb.EDGE_BLOCK).tolist()))
    # the positions the kernels read, on the device: rank v at [v // 128,
    # v % 128], -1 past the last rank
    got = np.asarray(pb._last_edges(g.in_iptr_rank)).reshape(-1)
    np.testing.assert_array_equal(got[: len(last)], last)
    assert (got[len(last):] == -1).all() and len(got) % pb.RANK_TILE == 0


# sha256 of the lowered text (CPU: the kernels in interpret mode, inlined)
# of the programs a request reaches, for rmat_csr(12, 8, seed=5).
# recurse_fused and recurse_fused_multi as PR 37 left them: seeds in as
# ranks (int32[2, S]; a stacked int32[B, 2, S], a row of pads skipped),
# level 1 a push over the seeds' forward rows or a stream by their degree
# sum, level 2 from the pushed rows as a list, nothing uid-sized inside —
# recurse_fused pinned at S = 1 (the row as one dynamic_slice),
# recurse_fused_multi at S = 4 (the rows as one gather). recurse_step as PR
# 35 left it (one emit, row_end_prefix*; edge dedup on vertices: it shares
# _recurse_tail with the fused programs), bfs_dist as it was at commit
# ab2061a (PR 34), before that PR — the search shares its kernels, its
# membership tests and _hop_for with them. A PR that means to change a
# program replaces its line
PROGRAM_LOWERINGS = {
    "recurse_fused":
        "104fb9fae5dfc2fc48c9df66a396b649bf3f797ac40e2a38bbdf835129c646ae",
    "recurse_fused_multi":
        "f04ca67ed1d1c3facf17718451cf94664be06605cf62608dabcbac45e364c0e9",
    "recurse_step":
        "4466867cced45d75ff48c0a2ad6f03625b1e8310091440167409d66f2b607386",
    "bfs_dist":
        "2ba50790fd26e29c394e3818bb001924b2c3dd32e6bbb1c44b00e5efdbf44375",
}


def lower_program(program, subjects, indptr, indices):
    """`program` of PROGRAM_LOWERINGS, lowered for a CSR."""
    n = int(max(subjects.max(), indices.max())) + 1
    g = pb.prep_pull(subjects, indptr, indices, n)
    statics = dict(depth=3, chunks=g.chunks, chunks_d=g.chunks_d,
                   allow_loop=False, first_hop_cap=pb.FIRST_HOP_CAP)
    return {
        "recurse_fused": lambda: pb.recurse_fused.lower(
            *pb.fused_graph_args(g), np.zeros((2, 1), np.int32), **statics),
        "recurse_fused_multi": lambda: pb.recurse_fused_multi.lower(
            *pb.fused_graph_args(g), np.zeros((2, 2, 4), np.int32),
            **statics),
        "recurse_step": lambda: pb.recurse_step.lower(
            g.in_src_pad, g.in_iptr_rank, g.row_ends, g.subjects,
            g.in_subjects, g.fwd_indptr, jnp.zeros((n,), bool),
            jnp.zeros(g.subjects.shape, bool),
            chunks=g.chunks, num_nodes=n, allow_loop=False),
        "bfs_dist": lambda: pb.bfs_dist.lower(
            g.in_src_pad, g.in_src_pad_d, g.in_iptr_rank, g.row_ends,
            g.subjects, g.in_subjects, g.fwd_indptr, g.fwd_dst_rank,
            np.zeros(4, np.int32), chunks=g.chunks, chunks_d=g.chunks_d,
            first_hop_cap=pb.FIRST_HOP_CAP),
    }[program]()


@pytest.mark.parametrize("program", sorted(PROGRAM_LOWERINGS))
def test_programs_lower_to_the_text_they_had(program):
    """The programs share their kernels and their frontier plumbing: a
    change meant for one of them that reaches another shows here,
    character for character."""
    lowered = lower_program(program, *rmat_csr(12, 8, seed=5)).as_text()
    assert hashlib.sha256(lowered.encode()).hexdigest() == \
        PROGRAM_LOWERINGS[program]


def test_prep_pull_rejects_out_of_range_uids():
    subjects = np.array([0], dtype=np.int64)
    indptr = np.array([0, 1], dtype=np.int64)
    indices = np.array([100], dtype=np.int64)
    with pytest.raises(ValueError, match="num_nodes"):
        pb.prep_pull(subjects, indptr, indices, num_nodes=50)
    with pytest.raises(ValueError, match="num_nodes"):
        pb.prep_pull(np.array([100], np.int64), indptr,
                     np.array([0], np.int64), num_nodes=50)


@pytest.mark.parametrize("n_set", [0, 1, 33, pb.FRONTIER_CAP])
def test_table_of_a_sorted_list_is_the_table_of_its_mask(rng, n_set):
    """The sparse kernel's search table has two makers: _frontier_table
    from a mask (a nonzero over it), and its second half, _table_of_list,
    from a list that a caller already holds (bfs_dist: the row that level
    1 read). Same members, same table, same active prefix (read at the
    row ends of a stream in which every edge is a row)."""
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
    # every edge a row of its own: the row ends are the whole prefix
    iptr = np.arange(len(src) + 1, dtype=np.int32)
    ends = pb._row_ends(iptr, len(src))
    last = pb._last_edges(jnp.asarray(iptr))
    for table in (by_list, np.asarray(pb._table_of_list(jnp.asarray(twice)))):
        got = pb.row_end_prefix_sparse(jnp.asarray(table), jnp.asarray(src),
                                       ends, last)
        np.testing.assert_array_equal(np.asarray(got).reshape(-1)[:len(src)],
                                      want)


def _undirected_rmat(rng):
    """An R-MAT stored in both directions: every level re-enters the one
    before it over the reverse edges."""
    subjects, indptr, indices = rmat_csr(9, 6, seed=3)
    src = np.repeat(subjects, np.diff(indptr))
    csr = csr_of(np.concatenate([src, indices]),
                 np.concatenate([indices, src]))
    return csr, rng.choice(csr[0], size=3, replace=False), 5


def _directed_cycle(rng):
    """0 -> 1 -> ... -> 6 -> 0, walked once and a half around: the seed
    comes back as a destination and its one edge is seen by then."""
    n = 7
    at = np.arange(n)
    return csr_of(at, (at + 1) % n), np.array([2]), 10


def _self_loops(rng):
    """A random graph in which a third of the vertices, the seeds among
    them, point at themselves: a vertex re-enters the level after it
    entered."""
    n = 300
    subjects, indptr, indices = random_csr(rng, n, 1200)
    src = np.repeat(subjects, np.diff(indptr))
    loops = np.arange(0, n, 3)
    return (csr_of(np.concatenate([src, loops]),
                   np.concatenate([indices, loops])),
            np.array([0, 3, 7]), 5)


def _seed_with_no_in_edge(rng):
    """Seed 50 has out-edges and no in-edge (it exists in the src-rank
    space only) and feeds a cycle 0 -> 1 -> 2 -> 3 -> 0 with a chord."""
    src = np.array([50, 50, 0, 1, 2, 3, 1])
    dst = np.array([0, 2, 1, 2, 3, 0, 3])
    return csr_of(src, dst), np.array([50]), 6


def _seed_reaching_seed(rng):
    """Two seeds on one chain, the second two steps down from the first:
    it is expanded at level 1 and reached again at level 2."""
    at = np.arange(9)
    src = np.concatenate([at, [9, 4]])
    dst = np.concatenate([at + 1, [2, 0]])
    return csr_of(src, dst), np.array([0, 2]), 6


def _across_sparse_max(rng):
    """Frontiers of 1, 5000 (over SPARSE_MAX: the dense kernel), 51, and
    the 5000 again — all of them expanded by then, so an empty frontier
    for the kernel and 5000 rows to charge — level by level."""
    hubs = np.arange(1, 5001)
    leaves = 5001 + hubs % 50
    back = hubs[hubs % 7 == 0]
    assert len(hubs) > pb.SPARSE_MAX
    src = np.concatenate([np.zeros_like(hubs), hubs, back,
                          5001 + (hubs - 1) // 100])
    dst = np.concatenate([hubs, leaves, np.zeros_like(back), hubs])
    return csr_of(src, dst), np.array([0]), 5


REENTRY_GRAPHS = {
    "undirected_rmat": _undirected_rmat,
    "directed_cycle": _directed_cycle,
    "self_loops": _self_loops,
    "seed_with_no_in_edge": _seed_with_no_in_edge,
    "seed_reaching_seed": _seed_reaching_seed,
    "across_sparse_max": _across_sparse_max,
}


def fused_levels(g, seeds, depth, allow_loop,
                 first_hop_cap=pb.FIRST_HOP_CAP):
    """Per level (reached uids, traversed) of one recurse_fused call."""
    masks_h, trav = fused(g, seeds, depth, allow_loop, first_hop_cap)
    nd = len(g.host_in_subjects)
    return [(g.host_in_subjects[pb.unpack_words(masks_h[lvl], nd)],
             int(t)) for lvl, t in enumerate(trav)]


def stepped_levels(g, seeds, depth, allow_loop):
    """The same from a chain of recurse_step calls, each fed the one
    before's destinations and its `expanded`."""
    seeds_mask = np.zeros(g.num_nodes, dtype=bool)
    seeds_mask[seeds] = True
    frontier = jnp.asarray(seeds_mask)
    expanded = jnp.zeros(g.subjects.shape, dtype=bool)
    levels = []
    for _ in range(depth):
        dest_p, trav, expanded = pb.recurse_step(
            g.in_src_pad, g.in_iptr_rank, g.row_ends, g.subjects,
            g.in_subjects, g.fwd_indptr, frontier, expanded,
            chunks=g.chunks, num_nodes=g.num_nodes, allow_loop=allow_loop)
        dest = pb.unpack_words(np.asarray(dest_p), g.num_nodes)
        levels.append((np.flatnonzero(dest), int(trav)))
        frontier = jnp.asarray(dest)
    return levels


@pytest.mark.parametrize("allow_loop", [False, True], ids=["dedup", "loop"])
@pytest.mark.parametrize("levels_of", [fused_levels, stepped_levels],
                         ids=["fused", "stepped"])
@pytest.mark.parametrize("graph", sorted(REENTRY_GRAPHS))
def test_recurse_dedups_edges(rng, graph, levels_of, allow_loop):
    """Edge dedup kept on vertices is edge dedup: on graphs that re-enter
    vertices, each level's reached set, its traversed count (re-entered
    vertices charged again) and the uid matrix the executor would render
    (LazyRecurseMatrix over _first_visits, as query/recurse.py builds it)
    equal a plain loop over a per-edge `seen`."""
    csr, seeds, depth = REENTRY_GRAPHS[graph](rng)
    num_nodes = int(max(csr[0].max(), csr[2].max())) + 2
    g = pb.prep_pull(*csr, num_nodes)
    want = host_recurse(*csr, seeds, depth, allow_loop)
    got = levels_of(g, seeds, depth, allow_loop)
    store = SimpleNamespace(host_arrays=lambda: csr)
    expanded = np.zeros(num_nodes, dtype=bool)
    frontier = np.sort(seeds)
    for (reached, traversed), (want_reached, want_traversed, want_matrix) \
            in zip(got, want, strict=True):
        np.testing.assert_array_equal(reached, want_reached)
        assert traversed == want_traversed
        matrix = recmod.LazyRecurseMatrix(
            store, frontier,
            None if allow_loop else recmod._first_visits(expanded, frontier))
        assert len(matrix) == len(want_matrix)
        for row, want_row in zip(matrix, want_matrix):
            np.testing.assert_array_equal(row, want_row)
        frontier = reached
    # the graphs do re-enter: some level charges edges it does not follow
    if not allow_loop:
        assert any(sum(len(r) for r in m) < t for _f, t, m in want)


@pytest.mark.parametrize("program", ["recurse_fused", "recurse_fused_multi",
                                     "recurse_step"])
def test_recurse_programs_hold_nothing_edge_sized(one_v5e, monkeypatch,
                                                  program):
    """A recurse level dedups vertices: beside the edge arrays it is
    given (the two streams; the fused programs read the seeds' rows of
    fwd_dst_pad too), a recurse program compiled for the chip holds
    nothing with an element an edge — no output (the bool[depth, E_pad] fresh flags are
    gone) and no temporary (the per-edge prefix, `seen`, `fresh` and their
    cumsum were 14 bytes an edge and more): outputs and temporaries
    together stay under one BYTE an edge. 2.1M edges over 40k + 40k
    ranks, shapes no other test traces (the kernels are lowered for the
    chip here, not for the interpreter)."""
    monkeypatch.setattr(pb, "interpret_mode", lambda: False)
    e_pad, n_items = 256 * pb.EDGE_BLOCK, 320
    ns, nd, n = 40_000, 40_001, 65_536

    def arg(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_v5e)

    ends = pb.RowEnds(arg((n_items,)), arg((n_items,)))
    statics = dict(depth=3, chunks=2, chunks_d=2, allow_loop=False)
    # the graph arguments, the padded forward array among them
    layout = (arg((e_pad,)), arg((e_pad,)), arg((nd + 1,)), ends,
              arg((ns + 1,)), arg((e_pad,)), arg((nd,)))
    compiled = {
        "recurse_fused": lambda: pb.recurse_fused.lower(
            *layout, arg((2, 1)), **statics),
        "recurse_fused_multi": lambda: pb.recurse_fused_multi.lower(
            *layout, arg((2, 2, 4)), **statics),
        "recurse_step": lambda: pb.recurse_step.lower(
            arg((e_pad,)), arg((nd + 1,)), ends, arg((ns,)), arg((nd,)),
            arg((ns + 1,)), arg((n,), bool), arg((ns,), bool),
            chunks=2, num_nodes=n, allow_loop=False),
    }[program]().compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.output_size_in_bytes < e_pad
