"""Pallas pull-BFS: the locality-blocked frontier-bit gather kernel.

This is the native kernel the reference implements as 146k lines of
generated SSE2 (bp128/unpack_amd64.s + worker/task.go:476-602 per-uid
posting iteration). XLA's element-granularity gather runs orders of
magnitude below HBM bandwidth, and every BFS formulation pays one E-sized
random gather per hop (frontier[in_src[e]]). Here that gather runs inside
a Pallas kernel where it can't miss:

  - the frontier is a bit-packed bitmap: num_nodes bits = num_nodes/8
    bytes, VMEM-resident for the whole kernel (1M nodes = 128 KB). Zero
    HBM traffic for masks.
  - the bitmap is laid out as (CHUNKS, 1024) int32 words; 1024 words =
    one 8x128 int32 vreg, the unit Mosaic can gather from in one op. The
    kernel loops over chunks, gathering each edge's frontier word from
    the chunk that owns it (chunks = ceil(num_nodes / 32768); a scale-20
    graph needs 33 — ~5 VPU ops per edge per chunk).
  - the edge stream (in_src, sorted by destination) is the ONLY O(E) HBM
    read: 4 bytes per edge, at streaming rate.
  - the kernel fuses the inclusive prefix-sum of the per-edge active
    flags (two-level lane/sublane scan + a sequential-grid carry in
    SMEM) and keeps the block's prefix in VMEM: it picks it at the ends of
    the rows that end in the block (a graph-static table of in-block
    positions, RowEnds) and writes one int32 per destination rank.
    Nothing edge-sized is written and XLA gathers nothing: per-node
    reachability is a diff of neighbouring ranks — node-sized.
  - one emit (row_end_prefix*) for every program. A search wants the
    VERTICES a frontier reaches. A recurse dedups EDGES, but an edge is
    active by its source alone, so every out-edge of a vertex is first
    traversed in the one level that vertex is first in a frontier: "edge
    e was traversed before" is "src(e) was in an earlier frontier", a
    node-sized set (`expanded`), and a level's fresh edges are the
    out-edges of `frontier & ~expanded` — again a frontier to reach from.

Per hop:   active[e] = frontier_bit[in_src[e]]          (Pallas, streaming)
           prefix    = cumsum(active)                   (fused in kernel)
           bounds_v  = prefix[iptr[v+1]-1]       (picked in the kernel)
           reached_v = bounds_v - bounds_{v-1} > 0         (node-sized)
  search:  frontier' = reached & ~visited               (node-sized)
  recurse: frontier' = reached; the kernel's frontier is
           frontier & ~expanded; expanded |= frontier   (node-sized)

Reference semantics preserved: `traversed` counts every out-edge of every
frontier node per level (re-entered vertices included). Three programs
are built on the kernel, and they are what a request reaches: bfs_dist
(`shortest`, query/shortest.py), recurse_fused / recurse_fused_multi
(`@recurse` alone and batched, query/recurse.py and query/batch.py) and
recurse_step (a recurse that needs the host between levels).
tests/test_pallas_bfs.py holds them to a plain host BFS at every edge of
the blocking scheme.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dgraph_tpu.obs import costs

WORDS_PER_CHUNK = 1024          # one 8x128 int32 vreg
NODES_PER_CHUNK = WORDS_PER_CHUNK * 32
EDGE_BLOCK = 8192               # edges per grid step (64 x 128)
_LANES = 128


def _block_prefix(active: jax.Array) -> jax.Array:
    """Inclusive prefix sum of a (R, 128) int block in row-major order,
    computed as two triangular matmuls on the MXU (f32 is exact here:
    block totals are <= EDGE_BLOCK << 2^24). Mosaic lowers matmuls far
    better than narrow pad/concat scans."""
    R, L = active.shape
    af = active.astype(jnp.float32)
    kk = lax.broadcasted_iota(jnp.int32, (L, L), 0)
    jj = lax.broadcasted_iota(jnp.int32, (L, L), 1)
    upper = (kk <= jj).astype(jnp.float32)             # inclusive lane scan
    lane = lax.dot_general(af, upper, (((1,), (0,)), ((), ())),
                           preferred_element_type=jnp.float32)
    rr = lax.broadcasted_iota(jnp.int32, (R, R), 0)
    cc = lax.broadcasted_iota(jnp.int32, (R, R), 1)
    lower = (cc < rr).astype(jnp.float32)              # strictly-lower: rows before
    row_sums = jnp.sum(af, axis=1, keepdims=True)      # (R, 1)
    row_off = lax.dot_general(lower, row_sums, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    return (lane + row_off).astype(jnp.int32)


def _active_dense(words_ref, src, chunks: int):
    """0/1 per edge of a (R, 128) tile of source ranks: its bit in the
    VMEM-resident frontier bitmap. The lookup of every dense kernel.

    The frontier-word lookup runs as a chunk loop: each chunk is 1024 words
    laid out (8, 128); Mosaic's dynamic_gather handles the lane dimension
    (take_along_axis along axis=1, single-vreg form) and an 8-way masked
    select handles the sublane row (masks hoisted out of the chunk loop) —
    zero HBM traffic for the bitmap (VMEM-resident throughout)."""
    # bit-plane word layout (see pack_words): node n lives in chunk n>>15,
    # panel row (n>>12)&7, lane n&127, bit (n>>7)&31 — chosen so packing a
    # node mask into words is 32 lane-aligned shift-ors, not a 32-wide
    # cross-lane reduction
    bit = jnp.bitwise_and(lax.shift_right_logical(src, 7), 31)
    cidx = lax.shift_right_logical(src, 15)            # owning chunk
    col = jnp.bitwise_and(src, _LANES - 1)
    row = jnp.bitwise_and(lax.shift_right_logical(src, 12), 7)
    row_masks = [row == r for r in range(8)]           # hoisted: 8 ops total

    def body(c, acc):
        cw = words_ref[pl.ds(c * 8, 8), :]             # (8,128): 1024 words
        cmask = cidx == c
        for r in range(8):
            row_r = jnp.broadcast_to(cw[r : r + 1, :], src.shape)
            g = jnp.take_along_axis(row_r, col, axis=1)    # in-vreg gather
            acc = jnp.where(row_masks[r] & cmask, g, acc)
        return acc

    wordv = lax.fori_loop(0, chunks, body, jnp.zeros_like(src))
    return jnp.bitwise_and(lax.shift_right_logical(wordv, bit), 1)


FRONTIER_CAP = 4096    # sparse-path capacity: 128 buckets x 32 entries


def _active_sparse(ftab_ref, src):
    """0/1 per edge of a (R, 128) tile of source ranks: membership in a
    sorted frontier list (<= FRONTIER_CAP uids) in a 2-level 128-ary layout
    instead of the full-bitmap chunk loop — ~5x fewer VPU ops per edge, the
    win for the early BFS hops where the frontier is small. The lookup of
    every sparse kernel.

    ftab layout (33, 128): row 0 = per-bucket max (bucket g = sorted
    frontier[32g:32g+32]); rows 1+j = element j of every bucket. Padding
    slots hold INT32_MAX (never equal to a real uid)."""
    seps = jnp.broadcast_to(ftab_ref[0:1, :], src.shape)

    # branchless lower-bound over the 128 bucket separators:
    # first bucket g with max(bucket g) >= src
    b = jnp.zeros_like(src)
    for k in (64, 32, 16, 8, 4, 2, 1):
        cand = b + k
        sep = jnp.take_along_axis(seps, jnp.minimum(cand - 1, _LANES - 1),
                                  axis=1)
        b = jnp.where(sep < src, cand, b)
    b = jnp.minimum(b, _LANES - 1)

    # equality scan of the 32 entries of the selected bucket
    active = jnp.zeros_like(src)
    for j in range(32):
        lane = jnp.broadcast_to(ftab_ref[1 + j : 2 + j, :], src.shape)
        v = jnp.take_along_axis(lane, b, axis=1)
        active = jnp.bitwise_or(active, (v == src).astype(jnp.int32))
    return active


def interpret_mode() -> bool:
    """True when the Pallas kernels run in interpret mode (any backend not
    named "tpu"): equality only, never a served tier."""
    return jax.default_backend() != "tpu"


RANK_TILE = 1024       # destination ranks per output tile: one 8x128 vreg
_ITEM_CLASS = 32       # grid steps of a row-end kernel come in this multiple


def _rank_tiles(n_ranks: int) -> int:
    """Output tiles of a row-end kernel over n_ranks destinations."""
    return max(1, -(-n_ranks // RANK_TILE))


class RowEnds(NamedTuple):
    """The grid of a row-end kernel (graph-static, built once by prep_pull):
    a list of ITEMS, one per (edge block, tile of RANK_TILE destination
    ranks) pair such that a row of the tile ends in the block, in stream
    order; an edge block in which no row ends has one item too, so every
    block is streamed. A row ends in exactly one block, so exactly one
    item picks it. The list is padded to a multiple of _ITEM_CLASS with
    items that repeat the last block and tile."""

    block: jax.Array      # int32[n_items] edge block of an item
    tile: jax.Array       # int32[n_items] rank tile of an item


def _row_ends(iptr: np.ndarray, e_pad: int) -> RowEnds:
    """The RowEnds of a dst-sorted stream of e_pad edges whose row v is
    iptr[v]..iptr[v+1] (every row non-empty)."""
    nd = len(iptr) - 1
    n_blocks = e_pad // EDGE_BLOCK
    n_tiles = _rank_tiles(nd)
    blk_of = (iptr[1:].astype(np.int64) - 1) // EDGE_BLOCK   # [Nd] sorted
    count = np.bincount(blk_of, minlength=n_blocks)
    first = np.cumsum(count) - count                   # first rank per block
    # the tiles a block's ranks first..first+count-1 touch; a block with no
    # row end sits on the tile the next rank will land in
    t_lo = np.minimum(first // RANK_TILE, n_tiles - 1)
    t_hi = np.where(count > 0, (first + count - 1) // RANK_TILE, t_lo)
    per_block = t_hi - t_lo + 1
    start = np.cumsum(per_block) - per_block           # first item per block
    n_real = int(per_block.sum())
    n_items = -(-n_real // _ITEM_CLASS) * _ITEM_CLASS
    block = np.full(n_items, n_blocks - 1, dtype=np.int32)
    block[:n_real] = np.repeat(np.arange(n_blocks), per_block)
    tile = np.full(n_items, n_tiles - 1, dtype=np.int32)
    tile[:n_real] = (np.arange(n_real) - start[block[:n_real]]
                     + t_lo[block[:n_real]])
    return RowEnds(jnp.asarray(block), jnp.asarray(tile))


def _last_edges(in_iptr_rank: jax.Array) -> jax.Array:
    """int32[n_tiles*8, 128]: the stream position of each destination
    rank's last in-edge, rank v at [v // 128, v % 128]; -1 past Nd. What
    a row-end kernel reads a rank tile of."""
    nd = in_iptr_rank.shape[0] - 1
    n_tiles = _rank_tiles(nd)
    return jnp.pad(in_iptr_rank[1:] - 1, (0, n_tiles * RANK_TILE - nd),
                   constant_values=-1).reshape(n_tiles * 8, _LANES)


def _row_end_kernel(block_ref, tile_ref, table_ref, src_ref, last_ref,
                    out_ref, prefix_ref, carry_ref, *, active_of):
    """One grid step of a row-end kernel (every program's emit: a level
    wants the vertices reached, not the edges): item i -> the inclusive
    active-prefix at the last in-edge of each destination rank of the
    item's tile whose row ends in the item's edge block, written to the
    rank's slot of the output tile. Nothing edge-sized leaves the kernel.

    An item that opens an edge block runs the membership test
    (`active_of(table_ref, src tile)`: _active_dense over the frontier
    bitmap or _active_sparse over the search table) and the block scan,
    and keeps the block's own prefix in VMEM for the block's later items;
    carry_ref = [prefix before this block, prefix after it]. Consecutive
    items on one output tile find it resident (its block index has not
    changed) and fill disjoint slots; the item that opens a tile zeroes
    it."""
    i = pl.program_id(0)
    before = jnp.maximum(i - 1, 0)
    opens_block = (i == 0) | (block_ref[i] != block_ref[before])
    opens_tile = (i == 0) | (tile_ref[i] != tile_ref[before])

    @pl.when(i == 0)
    def _():
        carry_ref[1] = 0

    @pl.when(opens_block)
    def _():
        prefix = _block_prefix(active_of(table_ref, src_ref[:]))
        prefix_ref[:] = prefix
        carry_ref[0] = carry_ref[1]
        carry_ref[1] = carry_ref[1] + prefix[prefix.shape[0] - 1, _LANES - 1]

    @pl.when(opens_tile)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    # the pick: a rank whose last in-edge sits at `at` inside this block
    # wants prefix[at >> 7, at & 127]. The lane is a dynamic gather inside
    # one vreg, the row a masked select over the block's 64 prefix rows; a
    # rank whose row ends in another block matches no row
    at = last_ref[:] - block_ref[i] * EDGE_BLOCK       # (8, 128) int32
    row = lax.shift_right_arithmetic(at, 7)
    lane = jnp.bitwise_and(at, _LANES - 1)
    picked = jnp.zeros_like(at)
    for r in range(EDGE_BLOCK // _LANES):
        row_r = jnp.broadcast_to(prefix_ref[r : r + 1, :], at.shape)
        g = jnp.take_along_axis(row_r, lane, axis=1)       # in-vreg gather
        picked = jnp.where(row == r, g, picked)
    ends_here = (at >= 0) & (at < EDGE_BLOCK)
    out_ref[:] = jnp.where(ends_here, picked + carry_ref[0], out_ref[:])


def _row_end_call(active_of, table, src_pad, ends: RowEnds, last):
    """The pallas_call of both row-end kernels: `table` (the frontier
    bitmap or the search table) whole in VMEM, the edge stream by the
    item's block, `last` (_last_edges) and the output by the item's tile.
    Returns int32[n_tiles*8, 128], rank v at [v // 128, v % 128]."""
    rblk = EDGE_BLOCK // _LANES
    return pl.pallas_call(
        partial(_row_end_kernel, active_of=active_of),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(ends.block.shape[0],),
            in_specs=[
                pl.BlockSpec(table.shape, lambda i, blk, tile: (0, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((rblk, _LANES), lambda i, blk, tile: (blk[i], 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((8, _LANES), lambda i, blk, tile: (tile[i], 0),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec((8, _LANES),
                                   lambda i, blk, tile: (tile[i], 0),
                                   memory_space=pltpu.VMEM),
            scratch_shapes=[pltpu.VMEM((rblk, _LANES), jnp.int32),
                            pltpu.SMEM((2,), jnp.int32)],
        ),
        out_shape=jax.ShapeDtypeStruct(last.shape, jnp.int32),
        interpret=interpret_mode(),
    )(ends.block, ends.tile, table, src_pad.reshape(-1, _LANES), last)


@partial(jax.jit, static_argnames=("chunks",))
def row_end_prefix(words: jax.Array, src_pad: jax.Array, ends: RowEnds,
                   last: jax.Array, *, chunks: int) -> jax.Array:
    """The inclusive prefix-count of frontier-active edges, taken at each
    destination rank's last in-edge and only there, in `last`'s layout:
    non-decreasing over the ranks; rank v was reached iff its value is
    above rank v-1's (0 before rank 0). The edge-sized prefix never exists
    outside the kernel."""
    return _row_end_call(partial(_active_dense, chunks=chunks), words,
                         src_pad, ends, last)


@jax.jit
def row_end_prefix_sparse(ftab: jax.Array, src_pad: jax.Array,
                          ends: RowEnds, last: jax.Array) -> jax.Array:
    """Sparse-frontier row_end_prefix (ftab: (33,128) 2-level layout)."""
    return _row_end_call(_active_sparse, ftab, src_pad, ends, last)


_INT32_MAX = np.iinfo(np.int32).max    # pad of a frontier list: no real rank


def _table_of_list(flist: jax.Array) -> jax.Array:
    """int32[FRONTIER_CAP] sorted ascending, _INT32_MAX pads last ->
    (33,128) search table."""
    buckets = flist.reshape(_LANES, 32)
    seps = buckets[:, 31]                  # per-bucket max
    return jnp.concatenate([seps[None, :], buckets.T], axis=0)


def _frontier_table(frontier: jax.Array) -> jax.Array:
    """bool[num_nodes] (popcount <= FRONTIER_CAP) -> (33,128) search table."""
    flist = jnp.nonzero(frontier, size=FRONTIER_CAP,
                        fill_value=jnp.int32(_INT32_MAX))[0]
    return _table_of_list(flist.astype(jnp.int32))


class PullGraph(NamedTuple):
    """Device-resident pull-BFS layout of one predicate CSR.

    Both endpoint spaces are RANK-COMPRESSED: the kernel gathers frontier
    bits by source *rank* (position in the sorted out-degree>0 subject list)
    and reachability is computed per destination *rank* — power-law graphs
    leave ~half the uid space with no edges at all, so rank spaces halve the
    bitmap chunk loop (the kernel's per-edge cost), the frontier pack, and
    the node phase (the row-end slots).
    The device arrays are the programs' arguments; the host arrays are
    what the engine reads between launches (a traversal's seeds as ranks,
    its first level, a search's backtrack)."""

    in_src_pad: jax.Array       # int32[E_pad] source SRC-RANKS, dst-sorted
    in_src_pad_d: jax.Array     # int32[E_pad] source DST-RANKS, dst-sorted
    in_iptr_rank: jax.Array     # int32[Nd+1] edge offsets per dst rank: the
    # kernels pick their prefix at these, block by block
    row_ends: RowEnds           # the (edge block, rank tile) pairs those
    # kernels walk
    subjects: jax.Array         # int32[Ns] sorted uids with out-edges
    in_subjects: jax.Array      # int32[Nd] sorted uids with in-edges
    fwd_indptr: jax.Array       # int32[Ns+1] forward CSR: the rows level 1
    # of a search or of a fused recurse reads
    fwd_dst_rank: jax.Array     # int32[E] dst RANKS in forward edge order
    fwd_dst_pad: jax.Array      # int32[E_pad] the same, padded to the edge
    # stream's block class: what the fused recurse programs read their
    # seeds' rows from, so that an edge written inside the pad block moves
    # no shape of theirs (bfs_dist takes the exact array)
    out_degree_d: jax.Array     # int32[Nd] out-degree by DST rank (0: the
    # destination has no out-edge): what a recurse level >= 2 charges; by
    # src rank it is diff(fwd_indptr)
    num_nodes: int
    num_edges: int
    chunks: int                 # bitmap chunks over the SRC-RANK space
    chunks_d: int               # bitmap chunks over the DST-RANK space
    host_in_iptr: np.ndarray    # HOST int32[Nd+1]
    host_in_src: np.ndarray     # HOST int32[E] src ranks, dst-sorted — the
    # in-adjacency the shortest-path backtrack walks
    host_map_s2d: np.ndarray    # HOST int32[Ns] dst rank of subject j, or Nd
    host_in_subjects: np.ndarray  # HOST int64[Nd]
    host_subjects: np.ndarray   # HOST int64[Ns]
    host_fwd_indptr: np.ndarray  # HOST int[Ns+1]: a source's out-degree,
    # which picks the first level of a search and of a fused recurse


def prep_pull(subjects: np.ndarray, indptr: np.ndarray,
              indices: np.ndarray, num_nodes: int) -> PullGraph:
    """Host-side once-per-snapshot prep: transpose to dst-sorted in-edges,
    remap both endpoints to rank spaces, pad the edge stream to the kernel
    block size pointing at an always-zero bitmap word. The host arrays are
    what the engine reads between launches (the shortest-path
    backtrack)."""
    E = len(indices)
    if E and int(np.max(indices)) >= num_nodes:
        raise ValueError(
            f"prep_pull: destination uid {int(np.max(indices))} >= "
            f"num_nodes={num_nodes}; pass num_nodes > max uid")
    if len(subjects) and int(np.max(subjects)) >= num_nodes:
        raise ValueError(
            f"prep_pull: subject uid {int(np.max(subjects))} >= "
            f"num_nodes={num_nodes}; pass num_nodes > max uid")
    subjects = np.asarray(subjects)
    src = np.repeat(np.arange(len(subjects), dtype=np.int64),
                    np.diff(indptr))                 # source RANK per edge
    order = np.argsort(np.asarray(indices), kind="stable")
    dst_sorted = np.asarray(indices)[order]
    src_sorted = src[order].astype(np.int32)
    in_subjects, counts = np.unique(dst_sorted, return_counts=True)
    nd = len(in_subjects)
    iptr = np.zeros(nd + 1, dtype=np.int32)
    np.cumsum(counts, out=iptr[1:])
    from dgraph_tpu.ops.uidset import host_rank_of

    # subject rank -> dst rank (Nd = "not a destination" sentinel slot)
    map_s2d = host_rank_of(in_subjects, subjects, nd).astype(np.int32)

    def _chunks_for(n):
        c = max(1, (n + NODES_PER_CHUNK - 1) // NODES_PER_CHUNK)
        if c * NODES_PER_CHUNK <= n:
            c += 1                   # pad rank must be outside real ranks
        return c

    ns = len(subjects)
    chunks = _chunks_for(ns)
    pad_src = chunks * NODES_PER_CHUNK - 1     # beyond Ns: bit always 0
    e_pad = max(EDGE_BLOCK, -(-E // EDGE_BLOCK) * EDGE_BLOCK)
    src_pad = np.full(e_pad, pad_src, dtype=np.int32)
    src_pad[:E] = src_sorted

    # dst-rank-space edge stream: after hop 1 the frontier is always a
    # subset of the destinations, so the kernel can gather bits straight
    # from the fresh dst-rank mask — no src<->dst remap gather per hop.
    # Sources that are never destinations can't be in a hop>=2 frontier;
    # their edges point at the always-zero pad word.
    chunks_d = _chunks_for(nd)
    pad_src_d = chunks_d * NODES_PER_CHUNK - 1
    src_d = map_s2d[src_sorted]                # Nd = "not a destination"
    src_d = np.where(src_d == nd, pad_src_d, src_d).astype(np.int32)
    src_pad_d = np.full(e_pad, pad_src_d, dtype=np.int32)
    src_pad_d[:E] = src_d

    # forward layout: the row a search's first level reads
    fwd_dst_rank = np.searchsorted(in_subjects, np.asarray(indices)).astype(
        np.int32)                    # every dst IS in in_subjects
    fwd_dst_pad = np.zeros(e_pad, dtype=np.int32)
    fwd_dst_pad[:E] = fwd_dst_rank
    out_degree_d = np.zeros(nd + 1, dtype=np.int32)    # slot Nd: dropped
    out_degree_d[map_s2d] = np.diff(indptr)
    return PullGraph(jnp.asarray(src_pad), jnp.asarray(src_pad_d),
                     jnp.asarray(iptr), _row_ends(iptr, e_pad),
                     jnp.asarray(subjects.astype(np.int32)),
                     jnp.asarray(in_subjects.astype(np.int32)),
                     jnp.asarray(np.asarray(indptr).astype(np.int32)),
                     jnp.asarray(fwd_dst_rank), jnp.asarray(fwd_dst_pad),
                     jnp.asarray(out_degree_d[:nd]),
                     int(num_nodes), int(E), int(chunks), int(chunks_d),
                     iptr, src_sorted, map_s2d,
                     in_subjects.astype(np.int64), subjects.astype(np.int64),
                     np.asarray(indptr))


def pack_words(mask: jax.Array, chunks: int) -> jax.Array:
    """bool[num_nodes] -> (chunks*8, 128) int32 bitmap, BIT-PLANE layout:
    word at [p, l] holds bit b for node p*4096 + b*128 + l. Packing is then
    32 lane-aligned shift-ors over (rows, 128) slices — the natural VPU
    shape — instead of a 32-wide cross-lane weighted reduction (~8x faster
    measured). The kernel's (chunk, row, lane, bit) decode matches."""
    cap = chunks * NODES_PER_CHUNK
    m = jnp.zeros((cap,), jnp.int32).at[: mask.shape[0]].set(
        mask.astype(jnp.int32))
    m3 = m.reshape(chunks * 8, 32, _LANES)
    words = m3[:, 0, :]
    for b in range(1, 32):
        words = jnp.bitwise_or(words, jnp.left_shift(m3[:, b, :], b))
    return words


SPARSE_MAX = FRONTIER_CAP   # frontier popcount at/below which the sparse
                            # search-table kernel beats pack+dense (tunable)


# ---------------------------------------------------------------------------
# edge-dedup traversal: the production @recurse path (reference
# query/recurse.go:31-177 expandRecurse). Unlike BFS (node-visited), recurse
# dedups EDGES: a node reached again over a never-traversed edge re-appears
# at the deeper level. An edge is traversed when its source is in a
# frontier, so the reach-set of recurse.go:129 is kept as the set of
# vertices that were in an earlier frontier (`expanded`, node-sized,
# carried on device across levels): see _recurse_tail.
# ---------------------------------------------------------------------------


def pull_graph_for(csr) -> PullGraph:
    """Cached PullGraph for a storage PredCSR (one host prep per snapshot)."""
    g = getattr(csr, "_pull_graph", None)
    if g is None:
        subjects, indptr, indices = csr.host_arrays()
        hi = max(int(subjects[-1]) if len(subjects) else 0,
                 int(indices.max()) if len(indices) else 0)
        g = prep_pull(np.asarray(subjects), np.asarray(indptr),
                      np.asarray(indices), hi + 1)
        csr._pull_graph = g
    return g


def pack_chunks(n: int) -> int:
    """Minimal chunk count whose word capacity covers n bits (pure packing —
    no kernel pad-rank slot needed)."""
    return max(1, (n + NODES_PER_CHUNK - 1) // NODES_PER_CHUNK)


def unpack_words(words: np.ndarray, n: int) -> np.ndarray:
    """Host inverse of pack_words' bit-plane layout: word [p, l] bit b holds
    node p*4096 + b*128 + l. Device→host results travel bit-packed (~8x
    fewer bytes than bool)."""
    w = np.asarray(words)
    bits = (w[:, None, :] >> np.arange(32, dtype=np.int32)[None, :, None]) & 1
    return bits.reshape(-1)[:n].astype(bool)


def _hop_for(frontier_bits, n_chunks: int, sparse, dense):
    """One frontier through the kernel its size picks: `sparse(search
    table)` at or below SPARSE_MAX set bits, `dense(bitmap words)` above."""
    fcount = jnp.sum(frontier_bits, dtype=jnp.int32)

    def sparse_hop(f):
        return sparse(_frontier_table(f))

    def dense_hop(f):
        return dense(pack_words(f, n_chunks))

    return lax.cond(fcount <= SPARSE_MAX, sparse_hop, dense_hop,
                    frontier_bits)


def _reached_from(bounds, n_ranks: int):
    """bool[n_ranks]: the ranks with an active in-edge, from the
    active-prefix at each rank's last in-edge (row_end_prefix)."""
    with jax.named_scope("bounds"):
        bounds = bounds.reshape(-1)[:n_ranks]
        return bounds > jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                         bounds])[:-1]


def _reached_for(frontier_bits, stream, n_chunks: int, ends: RowEnds, last):
    """Active-edge inclusive prefix at the row ends for one frontier, in
    `last`'s layout (a level's emit): nothing edge-sized leaves either
    branch."""
    return _hop_for(
        frontier_bits, n_chunks,
        lambda ftab: row_end_prefix_sparse(ftab, stream, ends, last),
        lambda words: row_end_prefix(words, stream, ends, last,
                                     chunks=n_chunks))


def _recurse_tail(frontier, expanded, out_degree, stream, n_chunks: int,
                  ends: RowEnds, last, nd: int, allow_loop: bool,
                  flist=None):
    """One recurse level in the stream's rank space: (frontier bits,
    the vertices that were in an earlier frontier) → (reached [Nd],
    traversed, expanded'). Edge dedup, exactly, on vertices: an edge is
    active iff its source is in the frontier, so it was traversed before
    iff its source was in an earlier frontier, and the level's fresh edges
    are the out-edges of `frontier & ~expanded` — the frontier the kernel
    reaches from. traversed counts EVERY out-edge of every frontier node,
    re-entered ones included (the budget the reference charges,
    recurse.go:167); reached = dst ranks with >= 1 fresh in-edge. The
    exactness-critical piece, kept in ONE place for the fused and stepped
    paths alike. `flist`, where the caller holds one (the rows a pushed
    level 1 read), lists the frontier's ranks — at most FRONTIER_CAP, in
    any order, _INT32_MAX for an empty lane: the listed ranks that are
    live, sorted, ARE the sparse kernel's table, with no nonzero over the
    mask (a rank listed twice changes the table and not what it
    answers)."""
    with jax.named_scope("visit"):
        traversed = jnp.sum(jnp.where(frontier, out_degree, 0),
                            dtype=jnp.int32)
        if allow_loop:
            live, expanded2 = frontier, expanded
        else:
            live = frontier & ~expanded
            expanded2 = expanded | frontier
    with jax.named_scope("prefix"):
        if flist is None:
            bounds = _reached_for(live, stream, n_chunks, ends, last)
        else:
            is_live = jnp.take(live, flist, mode="fill", fill_value=False)
            flist = jnp.sort(jnp.where(is_live, flist, _INT32_MAX))
            flist = jnp.pad(flist, (0, FRONTIER_CAP - flist.shape[0]),
                            constant_values=_INT32_MAX)
            bounds = row_end_prefix_sparse(_table_of_list(flist), stream,
                                           ends, last)
    return _reached_from(bounds, nd), traversed, expanded2


@partial(jax.jit, static_argnames=("chunks", "num_nodes", "allow_loop"))
def recurse_step(in_src_pad, in_iptr_rank, row_ends, subjects, in_subjects,
                 fwd_indptr, frontier_mask, expanded, *, chunks: int,
                 num_nodes: int, allow_loop: bool):
    """Single stepped level (used when filters / multiple recurse children
    force host control between levels), over the full uid space:
    multi-predicate frontiers are not confined to this predicate's
    destinations. `expanded` is bool[Ns], the subjects that were in an
    earlier frontier of this traversal, carried between calls. Returns
    (dest mask BIT-PACKED — the host fetch, not the kernel, is the latency
    floor of a single query —, traversed, expanded')."""
    reached, trav, expanded2 = _recurse_tail(
        jnp.take(frontier_mask, subjects), expanded, jnp.diff(fwd_indptr),
        in_src_pad, chunks, row_ends, _last_edges(in_iptr_rank),
        in_subjects.shape[0], allow_loop)
    dest = jnp.zeros((num_nodes,), bool).at[in_subjects].set(
        reached, mode="drop")
    return pack_words(dest, pack_chunks(num_nodes)), trav, expanded2


DIST_UNREACHED = 255    # uint8 distance label of a vertex never reached
FIRST_HOP_CAP = FRONTIER_CAP   # out-degree of a root at/below which level 1
                               # of a search reads the root's forward row


def first_hop_pushes(degree, cap: int):
    """THE predicate of a search's first level, shared by the program (a
    traced degree) and the host's counter (a Python one): push over the
    root's own forward row, or stream every in-edge."""
    return degree <= cap


@partial(jax.jit, static_argnames=("chunks", "chunks_d", "first_hop_cap"))
def bfs_dist(in_src_pad, in_src_pad_d, in_iptr_rank, row_ends, subjects,
             in_subjects, fwd_indptr, fwd_dst_rank, query, *, chunks: int,
             chunks_d: int, first_hop_cap: int = FIRST_HOP_CAP):
    """Unweighted single-source BFS distances, early-exiting when dst is
    reached — the kernel behind `shortest` on large CSRs (replaces the
    Bellman-Ford E-gather of ops/traversal.sssp, an element-granularity
    gather; here each hop from the second on is one Pallas E-stream that
    reads 4 bytes an edge and writes 4 bytes a destination, row_end_prefix:
    the program holds no edge-sized intermediate and no node-sized gather
    or scatter in a level that streams).

    Level 1 has one vertex in its frontier, so it reads that vertex's row
    of the forward CSR (one contiguous slice, one scatter) and not the
    whole in-edge stream, and level 2 takes that row, sorted, as its
    frontier list (the sparse kernel's table without a nonzero over the
    mask). A root with more than `first_hop_cap` out-edges (the slice's
    static width) keeps the stream for level 1 and the mask for level 2.
    The program chooses by the degree it reads in `fwd_indptr`.

    What crosses the host–device boundary for one search: IN, beside the
    resident graph, `query` = int32[4] (source UID, source rank — Ns for a
    source with no out-edge —, destination rank, max_hops) — one transfer;
    scalar arguments are one each, 0.25 ms of dispatch apiece on a v5e;
    OUT, one array — the per-dst-rank distance labels as uint8[Nd]
    (DIST_UNREACHED = never reached; max_hops is clamped below it). The
    whole hop loop runs in ONE dispatch (lax.while_loop). The host walks
    the predecessor chain itself from the labels (each step scans one
    node's in-edge slice — microseconds)."""
    nd = in_subjects.shape[0]
    n_edges = fwd_dst_rank.shape[0]
    width = min(first_hop_cap, n_edges)    # static: the slice's lanes
    if not 0 < width <= FRONTIER_CAP:      # the row has to fit the table
        raise ValueError(f"bfs_dist: first_hop_cap={first_hop_cap} over "
                         f"{n_edges} edges; want 1..{FRONTIER_CAP} lanes")
    src, src_rank, dst_rank, max_hops = (query[i] for i in range(4))
    # the named scopes are op_name metadata only (same compiled program):
    # a profile's leaf instructions carry the stage of the search they
    # belong to — seed / push / prefix / bounds / visit
    with jax.named_scope("seed"):
        # both uid lists are sorted and unique: comparing with the source
        # uid IS the one-hot seed mask gathered into that rank space
        visited0 = in_subjects == src                      # [Nd]
        dist0 = jnp.where(visited0, 0, DIST_UNREACHED).astype(jnp.int32)
        found0 = jnp.take(visited0, dst_rank)
        last = _last_edges(in_iptr_rank)       # what every level's pick reads

    def reached_by(frontier_bits, stream, n_chunks):
        return _reached_from(
            _reached_for(frontier_bits, stream, n_chunks, row_ends, last), nd)

    def visit(h, reached, visited, dist):
        with jax.named_scope("visit"):
            fresh = reached & ~visited
            visited2 = visited | fresh
            return (h + 1, fresh, visited2, jnp.where(fresh, h + 1, dist),
                    jnp.take(visited2, dst_rank))

    with jax.named_scope("push"):
        # indptr[Ns] = indptr[Ns + 1 (clipped)] = E: the "no out-edge"
        # rank reads an empty row; so does a search that may not expand
        row_at = jnp.take(fwd_indptr, src_rank + jnp.arange(2), mode="clip")
        start = row_at[0]
        degree = jnp.where(found0 | (max_hops <= 0), 0, row_at[1] - start)

    def push_hop(_):
        with jax.named_scope("push"):
            # dynamic_slice clamps a window that would pass the end of
            # the array: clamp first, and mask by edge position
            at = jnp.clip(start, 0, n_edges - width)
            lanes = at + jnp.arange(width, dtype=jnp.int32)
            row = jnp.where((lanes >= start) & (lanes < start + degree),
                            lax.dynamic_slice(fwd_dst_rank, (at,), (width,)),
                            _INT32_MAX)
            return jnp.zeros((nd,), bool).at[row].set(True, mode="drop"), row

    def stream_hop(_):
        with jax.named_scope("prefix"):
            # src-rank space: a source with out-edges and no in-edge
            # exists only here
            reached = reached_by(subjects == src, in_src_pad, chunks)
        return reached, jnp.full((width,), _INT32_MAX, jnp.int32)

    pushes = first_hop_pushes(degree, first_hop_cap)
    reached, row = lax.cond(pushes, push_hop, stream_hop, None)

    def cond(c):
        h, fresh, _visited, _dist, found = c
        return (~found) & (h < max_hops) & fresh.any()

    def row_hop(c):
        # level 2 after a push: the row level 1 read IS the frontier, as a
        # list of dst ranks — the sparse kernel's table without a nonzero
        # over the mask. A vertex of the row that was visited before (the
        # root, by a self-loop) reaches only visited vertices.
        h, _fresh, visited, dist, _found = c
        with jax.named_scope("push"):
            flist = jnp.pad(jnp.sort(row), (0, FRONTIER_CAP - width),
                            constant_values=_INT32_MAX)
            reached = _reached_from(row_end_prefix_sparse(
                _table_of_list(flist), in_src_pad_d, row_ends, last), nd)
        return visit(h, reached, visited, dist)

    def body(c):
        h, fresh, visited, dist, _found = c
        with jax.named_scope("prefix"):
            # a hop>=2 frontier is a subset of destinations: gather bits
            # straight from the fresh dst-rank mask (no remap gather)
            reached = reached_by(fresh, in_src_pad_d, chunks_d)
        return visit(h, reached, visited, dist)

    c = visit(jnp.int32(0), reached, visited0, dist0)
    c = lax.cond(pushes & cond(c), row_hop, lambda c: c, c)
    _h, _f, _v, dist, _found = lax.while_loop(cond, body, c)
    return dist.astype(jnp.uint8)


def _source_row(g: PullGraph, src: int) -> tuple[int, int]:
    """(source rank, out-degree) of a uid, from the host arrays; a uid
    with no out-edge has the rank Ns, whose row bfs_dist reads as empty."""
    ns = len(g.host_subjects)
    sr = int(np.searchsorted(g.host_subjects, src))
    if sr >= ns or g.host_subjects[sr] != src:
        return ns, 0
    return sr, int(g.host_fwd_indptr[sr + 1] - g.host_fwd_indptr[sr])


def first_hop_mode(g: PullGraph, src: int) -> str:
    """"push" or "stream": the branch bfs_dist takes for level 1 of a
    search from `src` (the label of dgraph_bfs_first_hop_total)."""
    degree = _source_row(g, src)[1]
    return "push" if first_hop_pushes(degree, FIRST_HOP_CAP) else "stream"


def shortest_bfs(g: PullGraph, src: int, dst: int, max_hops: int):
    """Host orchestration: run bfs_dist, fetch its one output (the uint8
    distance labels) once, walk the predecessor chain on the host
    in-adjacency. Returns the uid path [src..dst] or None (unreachable
    within max_hops)."""
    nd = len(g.host_in_subjects)
    if nd == 0:
        return None
    dr = int(np.searchsorted(g.host_in_subjects, dst))
    if dr >= nd or g.host_in_subjects[dr] != dst:
        return None              # dst has no in-edges: unreachable
    if src >= g.num_nodes:
        return None
    sr = _source_row(g, src)[0]
    max_hops = min(int(max_hops), DIST_UNREACHED - 1)
    # a numpy array, built on the host: nothing runs on the device per
    # request but the jitted program itself
    dist = bfs_dist(
        g.in_src_pad, g.in_src_pad_d, g.in_iptr_rank, g.row_ends,
        g.subjects, g.in_subjects, g.fwd_indptr, g.fwd_dst_rank,
        np.asarray([src, sr, dr, max_hops], dtype=np.int32),
        chunks=g.chunks, chunks_d=g.chunks_d, first_hop_cap=FIRST_HOP_CAP)
    # stages of the request's clock (obs/costs.py; no-ops without one):
    # up to here the caller's kernel window ran as dev.dispatch; blocked
    # in the fetch is dev.wait; the chain walk is dev.post
    with costs.stage("dev.wait"):
        dist_h = jax.device_get(dist)                      # ONE round-trip
    with costs.stage("dev.post"):
        return _walk_back(g, dist_h, dr, src, dst)


def _walk_back(g: PullGraph, dist: np.ndarray, dr: int, src: int, dst: int):
    """The uid path [src..dst] from bfs_dist's fetched uint8 labels, or
    None when dst was not reached."""
    if dist[dr] == DIST_UNREACHED:
        return None
    nd = len(dist)
    iptr, in_src = g.host_in_iptr, g.host_in_src
    map_s2d = g.host_map_s2d
    sub_uids = g.host_subjects   # uid of a src rank

    path = [dst]
    v_rank = dr
    for d in range(int(dist[dr]), 0, -1):
        srcs = in_src[iptr[v_rank]: iptr[v_rank + 1]]     # src RANKS
        if d == 1:
            # predecessor must be the seed itself
            cand = srcs[sub_uids[srcs] == src]
            if len(cand) == 0:
                return None      # inconsistent labels (cannot happen)
            path.append(src)
            break
        m = map_s2d[srcs]
        ok = (m < nd)
        ok[ok] = dist[m[ok]] == d - 1
        cand = srcs[ok]
        if len(cand) == 0:
            return None          # inconsistent labels (cannot happen)
        u_rank = int(cand[0])
        path.append(int(sub_uids[u_rank]))
        v_rank = int(map_s2d[u_rank])
    return path[::-1]


def fused_graph_args(g: PullGraph) -> tuple:
    """The graph arguments of recurse_fused / recurse_fused_multi, in
    order; the seeds follow them."""
    return (g.in_src_pad, g.in_src_pad_d, g.in_iptr_rank, g.row_ends,
            g.fwd_indptr, g.fwd_dst_pad, g.out_degree_d)


def seed_ranks(g: PullGraph, uids) -> np.ndarray:
    """int32[2, n]: the distinct seed uids of a recurse as ranks, found on
    the host — row 0 in the source space (Ns: no out-edge), row 1 in the
    destination space (Nd: no in-edge). What a fused program takes in
    place of a uid-space mask (stack_seeds pads it)."""
    from dgraph_tpu.ops.uidset import host_rank_of

    uids = np.unique(np.asarray(uids, dtype=np.int64))
    return np.stack([
        host_rank_of(g.host_subjects, uids, len(g.host_subjects)),
        host_rank_of(g.host_in_subjects, uids, len(g.host_in_subjects)),
    ]).astype(np.int32)


def stack_seeds(g: PullGraph, members: list[np.ndarray],
                rows: int) -> np.ndarray:
    """int32[rows, 2, S]: seed_ranks of each member of one launch, a row a
    member, padded with (Ns, Nd) to a pow2 class S of the longest list;
    rows no member holds are pads only. ONE host array: the only thing
    that crosses to the device for the launch."""
    width = max(1, *(m.shape[1] for m in members))
    out = np.empty((rows, 2, 1 << (width - 1).bit_length()), dtype=np.int32)
    out[:, 0] = len(g.host_subjects)
    out[:, 1] = len(g.host_in_subjects)
    for i, m in enumerate(members):
        out[i, :, : m.shape[1]] = m
    return out


def recurse_first_hop_mode(g: PullGraph, ranks: np.ndarray) -> str:
    """"push" or "stream": the branch a fused recurse takes for level 1
    from seed_ranks `ranks` (the label of dgraph_recurse_first_hop_total),
    by the predicate and the degree sum the program uses."""
    at = g.host_fwd_indptr
    total = int((np.take(at, ranks[0] + 1, mode="clip")
                 - np.take(at, ranks[0], mode="clip")).sum())
    return "push" if first_hop_pushes(total, FIRST_HOP_CAP) else "stream"


def _seed_rows(fwd_dst_pad, starts, degrees, width: int):
    """The seeds' forward rows (seed j: `degrees[j]` edges from
    `starts[j]`, at most `width` in all) laid side by side in `width`
    lanes of dst ranks, _INT32_MAX past their sum — level 1 of a recurse
    as a push. One seed: its row is one contiguous slice (bfs_dist's
    push_hop); several: each lane finds its (seed, offset) in the
    cumulative degrees and the rows are one `width`-wide gather."""
    lanes = jnp.arange(width, dtype=jnp.int32)
    if starts.shape[0] == 1:
        # dynamic_slice clamps a window that would pass the end of the
        # array: clamp first, and mask by edge position
        at = jnp.clip(starts[0], 0, fwd_dst_pad.shape[0] - width) + lanes
        return jnp.where(
            (at >= starts[0]) & (at < starts[0] + degrees[0]),
            lax.dynamic_slice(fwd_dst_pad, (at[0],), (width,)), _INT32_MAX)
    ends = jnp.cumsum(degrees)
    # the seed a lane falls in: the first whose rows end past it
    k = jnp.minimum(jnp.searchsorted(ends, lanes, side="right"),
                    starts.shape[0] - 1)
    at = starts[k] + lanes - (ends[k] - degrees[k])
    return jnp.where(lanes < ends[-1],
                     jnp.take(fwd_dst_pad, at, mode="clip"), _INT32_MAX)


def _recurse_fused_levels(in_src_pad, in_src_pad_d, in_iptr_rank, row_ends,
                          fwd_indptr, fwd_dst_pad, out_degree_d, seeds, *,
                          depth: int, chunks: int, chunks_d: int,
                          allow_loop: bool, first_hop_cap: int):
    """Traced body shared by recurse_fused (one seed list) and
    recurse_fused_multi (a stacked batch of them): level 1 by the seeds'
    degree sum, the levels from the second on as one lax.scan over the
    SAME per-level tail."""
    ns, nd = fwd_indptr.shape[0] - 1, out_degree_d.shape[0]
    width = max(first_hop_cap, 1)          # static: the lanes of the rows
    if width > FRONTIER_CAP:               # which have to fit the table
        raise ValueError(f"recurse_fused: first_hop_cap={first_hop_cap}; "
                         f"want at most {FRONTIER_CAP} lanes")
    last = _last_edges(in_iptr_rank)           # what every level's pick reads
    with jax.named_scope("seed"):
        # `expanded` lives in dst-rank space and starts from the seeds that
        # are destinations: a seed with no in-edge can never come back
        expanded = jnp.zeros((nd,), bool).at[seeds[1]].set(True, mode="drop")
        # indptr[Ns] = indptr[Ns + 1 (clipped)] = E: the pad rank's row is
        # empty. Level 1 charges every out-edge of every seed, whichever
        # way it goes
        starts = jnp.take(fwd_indptr, seeds[0], mode="clip")
        degrees = jnp.take(fwd_indptr, seeds[0] + 1, mode="clip") - starts
        total = jnp.sum(degrees, dtype=jnp.int32)

    def push_hop(_):
        with jax.named_scope("push"):
            row = _seed_rows(fwd_dst_pad, starts, degrees, width)
            return jnp.zeros((nd,), bool).at[row].set(True, mode="drop"), row

    def stream_hop(_):
        with jax.named_scope("prefix"):
            # src-rank space: a seed with out-edges and no in-edge exists
            # only here
            seeds_s = jnp.zeros((ns,), bool).at[seeds[0]].set(
                True, mode="drop")
            bounds = _reached_for(seeds_s, in_src_pad, chunks, row_ends, last)
        return (_reached_from(bounds, nd),
                jnp.full((width,), _INT32_MAX, jnp.int32))

    pushes = first_hop_pushes(total, first_hop_cap)
    reached, row = lax.cond(pushes, push_hop, stream_hop, None)
    masks_p = pack_words(reached, pack_chunks(nd))[None]
    trav = total[None]
    if depth == 1:
        return masks_p, trav

    def body(carry, second):
        # a level >= 2 frontier is the previous level's destinations: bits
        # straight from the dst-rank mask (no remap gather); after a push
        # level 2 has them as a list too, the rows level 1 read
        frontier_d, expanded = carry

        def tail(flist):
            return _recurse_tail(frontier_d, expanded, out_degree_d,
                                 in_src_pad_d, chunks_d, row_ends, last, nd,
                                 allow_loop, flist)

        reached, traversed, expanded2 = lax.cond(
            second & pushes, lambda: tail(row), lambda: tail(None))
        return (reached, expanded2), (pack_words(reached, pack_chunks(nd)),
                                      traversed)

    _carry, (masks_rest, trav_rest) = lax.scan(
        body, (reached, expanded), jnp.arange(depth - 1) == 0)
    return (jnp.concatenate([masks_p, masks_rest]),
            jnp.concatenate([trav, trav_rest]))


@partial(jax.jit, static_argnames=("depth", "chunks", "chunks_d",
                                   "allow_loop", "first_hop_cap"))
def recurse_fused(in_src_pad, in_src_pad_d, in_iptr_rank, row_ends,
                  fwd_indptr, fwd_dst_pad, out_degree_d, seeds, *,
                  depth: int, chunks: int, chunks_d: int, allow_loop: bool,
                  first_hop_cap: int = FIRST_HOP_CAP):
    """All `depth` levels in ONE dispatch: no host round-trip between
    levels. Single-predicate shape, so levels >= 2 stay entirely in
    DST-RANK space (a recurse frontier is the previous level's fresh
    destinations): no full-uid scatter, no src-rank remap gather, and the
    bitmap pack runs over the compressed rank space (the same dual-space
    trick as bfs_dist's levels >= 2).

    `seeds` is int32[2, S], the seed set in rank space (seed_ranks /
    stack_seeds: row 0 source ranks, pad Ns; row 1 destination ranks, pad
    Nd) — one host array, the only transfer of a request; nothing
    uid-sized exists in the program. Level 1 reads the seeds' degrees in
    `fwd_indptr`: at or under `first_hop_cap` out-edges in all
    (first_hop_pushes, bfs_dist's predicate) it reads the seeds' forward
    rows and scatters them, and level 2 takes those rows, less the
    vertices already expanded, as the sparse kernel's list (no nonzero
    over a mask); above it level 1 streams every in-edge from the seeds'
    src-rank bits and level 2 reaches from the mask, as every later level
    does. Same outputs either way, bit for bit.

    Returns stacked per-level (dest_words [D,Cd*8,128] BIT-PACKED
    DST-RANK masks — the host fetches these every query, so
    packed-and-rank-compressed is the cheapest form to move; traversed
    [D]). Nothing edge-sized is held or returned: which rows of a level's
    uidMatrix are fresh the host tells from the level frontiers it
    fetched (query/recurse.py LazyRecurseMatrix). Only for the
    single-uid-child no-filter recurse shape (the common + benchmarked
    one); anything needing host logic between levels uses recurse_step."""
    return _recurse_fused_levels(
        in_src_pad, in_src_pad_d, in_iptr_rank, row_ends, fwd_indptr,
        fwd_dst_pad, out_degree_d, seeds, depth=depth, chunks=chunks,
        chunks_d=chunks_d, allow_loop=allow_loop, first_hop_cap=first_hop_cap)


@partial(jax.jit, static_argnames=("depth", "chunks", "chunks_d",
                                   "allow_loop", "first_hop_cap"))
def recurse_fused_multi(in_src_pad, in_src_pad_d, in_iptr_rank, row_ends,
                        fwd_indptr, fwd_dst_pad, out_degree_d, seeds, *,
                        depth: int, chunks: int, chunks_d: int,
                        allow_loop: bool,
                        first_hop_cap: int = FIRST_HOP_CAP):
    """Multi-source batched recurse: seeds int32[B, 2, S] holds the seed
    lists of B concurrent queries (stack_seeds), one row a query as
    recurse_fused takes it, and a row of pads only for a slot no query
    holds. The whole batch runs as ONE device dispatch — the
    one-extra-dimension extension of recurse_fused the batched-dispatch
    tier launches (query/batch.py), which hands it one host array, so no
    occupancy has eager programs of its own and B is the batcher's
    capacity whatever the occupancy. lax.map over the exact recurse_fused
    body, so slice b of the stacked outputs is bit-identical to a solo
    recurse_fused call with row b (the per-level ops are integer/boolean —
    no float reassociation), each member's level 1 pushing or streaming
    by its own degree sum; a row without a seed that has an out-edge
    skips the body and hands back zeros, which is what the body gives
    it. Each query keeps its own expanded set: batching never entangles
    traversals. Returns (masks_p [B, depth, ...], traversed [B, depth])."""
    ns, nd = fwd_indptr.shape[0] - 1, out_degree_d.shape[0]

    def levels(row):
        return _recurse_fused_levels(
            in_src_pad, in_src_pad_d, in_iptr_rank, row_ends, fwd_indptr,
            fwd_dst_pad, out_degree_d, row, depth=depth, chunks=chunks,
            chunks_d=chunks_d, allow_loop=allow_loop,
            first_hop_cap=first_hop_cap)

    def nothing(_row):
        return (jnp.zeros((depth, pack_chunks(nd) * 8, _LANES), jnp.int32),
                jnp.zeros((depth,), jnp.int32))

    return lax.map(
        lambda row: lax.cond(jnp.any(row[0] < ns), levels, nothing, row),
        seeds)


# ---------------------------------------------------------------------------
# whole-graph analytics over the same layout (LDBC Graphalytics PR and WCC,
# query/analytics.py): every step reads every in-edge of the dst-sorted
# stream once, a segmented reduction by destination rank. The vertex set
# is the DST-RANK space, which holds every vertex with an edge when every
# source is also a destination (the caller checks: analytics.layout).
# A step is a gather of a value by source rank (gather_sorted, a block
# stream over a VMEM-resident table; XLA's element gather for a table too
# large for VMEM) and row_reduce, a block stream over the row-end
# kernels' grid that combines each row's values — a float sum or an int
# min over the whole row, not a prefix pick — and writes one value a
# destination rank.
# ---------------------------------------------------------------------------


def _dst_segments(in_iptr_rank: jax.Array, e_pad: int) -> jax.Array:
    """int32[e_pad]: the destination rank of each edge of the stream, Nd
    for the pad edges — the count of row starts iptr[1..Nd] at or below
    the edge's position."""
    starts = jnp.zeros(e_pad + 1, jnp.int32).at[in_iptr_rank[1:]].add(1)
    return jnp.cumsum(starts[:e_pad])


_COMBINE = {"sum": jnp.add, "min": jnp.minimum}


def _segmented_scan(x: jax.Array, seg: jax.Array, combine) -> jax.Array:
    """Inclusive scan of a (R, 128) block in row-major order that restarts
    wherever `seg` (non-decreasing: the edge's destination rank) changes.
    Log steps along the lanes (pltpu.roll), then along the sublanes over
    the rows' last lanes, then each row's lanes of the segment that ended
    the row before take that row's total. A sum is added in a tree of
    depth 14, never taken as a difference of prefixes, and nothing runs
    on the MXU, which would round f32 to bf16."""
    rows, lanes = x.shape
    lane = lax.broadcasted_iota(jnp.int32, x.shape, 1)
    row = lax.broadcasted_iota(jnp.int32, x.shape, 0)
    k = 1
    while k < lanes:
        take = (lane >= k) & (pltpu.roll(seg, k, 1) == seg)
        x = jnp.where(take, combine(x, pltpu.roll(x, k, 1)), x)
        k *= 2
    # a row's total so far is its last lane's; it runs into the next row
    # where that row starts in the same segment
    tail = jnp.broadcast_to(x[:, lanes - 1:], x.shape)
    tseg = jnp.broadcast_to(seg[:, lanes - 1:], x.shape)
    k = 1
    while k < rows:
        take = (row >= k) & (pltpu.roll(tseg, k, 0) == tseg)
        tail = jnp.where(take, combine(tail, pltpu.roll(tail, k, 0)), tail)
        k *= 2
    take = (row >= 1) & (pltpu.roll(tseg, 1, 0) == seg)
    return jnp.where(take, combine(x, pltpu.roll(tail, 1, 0)), x)


def _row_reduce_kernel(block_ref, tile_ref, val_ref, seg_ref, last_ref,
                       out_ref, scan_ref, cval_ref, cseg_ref, *, combine):
    """One grid step of row_reduce, over the row-end kernels' items: item
    i -> each destination rank of the item's tile whose row ends in the
    item's edge block gets its row's values combined, written to the
    rank's slot of the output tile as the value's 32 bits.

    An item that opens an edge block scans it (_segmented_scan) and
    finishes the row the block opens with from the SMEM carry —
    (cval, cseg): the combined value and the destination of the row the
    last block ended in, so a row crosses any number of blocks — then
    keeps the scan in VMEM for the block's later items. The pick is the
    row-end kernels': rank v wants the scan at its last in-edge."""
    i = pl.program_id(0)
    before = jnp.maximum(i - 1, 0)
    opens_block = (i == 0) | (block_ref[i] != block_ref[before])
    opens_tile = (i == 0) | (tile_ref[i] != tile_ref[before])

    @pl.when(i == 0)
    def _():
        cval_ref[0] = jnp.zeros((), cval_ref.dtype)
        cseg_ref[0] = -1                       # no row runs into block 0

    @pl.when(opens_block)
    def _():
        seg = seg_ref[:]
        x = _segmented_scan(val_ref[:], seg, combine)
        x = jnp.where(seg == cseg_ref[0], combine(x, cval_ref[0]), x)
        scan_ref[:] = lax.bitcast_convert_type(x, jnp.int32)
        cval_ref[0] = x[x.shape[0] - 1, _LANES - 1]
        cseg_ref[0] = seg[seg.shape[0] - 1, _LANES - 1]

    @pl.when(opens_tile)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    at = last_ref[:] - block_ref[i] * EDGE_BLOCK       # (8, 128) int32
    row = lax.shift_right_arithmetic(at, 7)
    lane = jnp.bitwise_and(at, _LANES - 1)
    picked = jnp.zeros_like(at)
    for r in range(EDGE_BLOCK // _LANES):
        row_r = jnp.broadcast_to(scan_ref[r : r + 1, :], at.shape)
        g = jnp.take_along_axis(row_r, lane, axis=1)       # in-vreg gather
        picked = jnp.where(row == r, g, picked)
    ends_here = (at >= 0) & (at < EDGE_BLOCK)
    out_ref[:] = jnp.where(ends_here, picked, out_ref[:])


@partial(jax.jit, static_argnames=("combine",))
def row_reduce(values: jax.Array, seg: jax.Array, ends: RowEnds,
               last: jax.Array, *, combine: str) -> jax.Array:
    """Each destination rank's in-edge values combined — `combine` "sum"
    (float32) or "min" (int32) — over a dst-sorted stream of E_pad values
    whose destination ranks are `seg` (_dst_segments), in `last`'s layout
    (_last_edges): rank v at [v // 128, v % 128], 0 past Nd. One block
    stream over the row-end kernels' grid (`ends`, graph-static); nothing
    edge-sized leaves it, and a pad edge's value reaches no rank."""
    rblk = EDGE_BLOCK // _LANES
    by_block = pl.BlockSpec((rblk, _LANES), lambda i, blk, tile: (blk[i], 0),
                            memory_space=pltpu.VMEM)
    by_tile = pl.BlockSpec((8, _LANES), lambda i, blk, tile: (tile[i], 0),
                           memory_space=pltpu.VMEM)
    words = pl.pallas_call(
        partial(_row_reduce_kernel, combine=_COMBINE[combine]),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(ends.block.shape[0],),
            in_specs=[by_block, by_block, by_tile],
            out_specs=by_tile,
            scratch_shapes=[pltpu.VMEM((rblk, _LANES), jnp.int32),
                            pltpu.SMEM((1,), values.dtype),
                            pltpu.SMEM((1,), jnp.int32)],
        ),
        out_shape=jax.ShapeDtypeStruct(last.shape, jnp.int32),
        interpret=interpret_mode(),
    )(ends.block, ends.tile, values.reshape(-1, _LANES),
      seg.reshape(-1, _LANES), last)
    return lax.bitcast_convert_type(words, values.dtype)


# The gather by source rank of a whole-graph step (the value of every
# in-edge's source, in stream order) as a block stream over a table held
# whole in VMEM. In stream order a tile of 1,024 edges meets nearly every
# (8, 128) chunk of the table, so the lookup would cost a pass over the
# whole table per tile (_active_dense's loop). gather_layout sorts each
# 8,192-edge block by source once a snapshot: a sublane row of 128 sorted
# edges then spans a few table rows of 128 values (22 on average at scale
# 18), and a window step hands each sublane row its next table row — one
# in-vreg lane gather and one select for 1,024 edges. The block's values
# go back to stream order in VMEM before they are written.

GATHER_TABLE_MAX = 4 << 20     # bytes of the largest table held in VMEM
_GATHER_UNROLL = 16            # window steps a loop turn


class GatherLayout(NamedTuple):
    """gather_sorted's layout of one edge stream (gather_layout, once a
    snapshot)."""

    src: jax.Array     # int32[E_pad] each block's source ranks, ascending
    back: jax.Array    # int32[E_pad] each edge's position in its block's
    # sorted order, in stream order
    meta: jax.Array    # int32[n_blocks, 1, 128] per block: [:64] the first
    # table row of each sublane row's window, [64:72] each tile's window
    # steps, a multiple of _GATHER_UNROLL


def _table_rows(n: int) -> int:
    """Rows of 128 values of a gather table of n values, a multiple of
    _GATHER_UNROLL (itself of 8): a tile's window steps, rounded up to
    it, never outnumber the table's rows."""
    return -(-n // (_GATHER_UNROLL * _LANES)) * _GATHER_UNROLL


def gather_fits(n: int) -> bool:
    """Whether a table of n 32-bit values is held whole in VMEM: the
    static shape test between gather_sorted and XLA's element gather."""
    return _table_rows(n) * _LANES * 4 <= GATHER_TABLE_MAX


def gather_layout(src: np.ndarray, n: int) -> tuple[GatherLayout | None,
                                                      int]:
    """(the GatherLayout of a stream whose edges read table slots `src`
    (int32[E_pad], every value below n, pad edges on a slot of their own),
    the window steps of one pass) — or (None, 0) where a table of n values
    does not gather_fits."""
    if not gather_fits(n):
        return None, 0
    rblk = EDGE_BLOCK // _LANES
    blocks = np.asarray(src, np.int32).reshape(-1, EDGE_BLOCK)
    order = np.argsort(blocks, axis=1, kind="stable")
    ranks = np.take_along_axis(blocks, order, axis=1)
    back = np.empty_like(order)
    np.put_along_axis(back, order, np.arange(EDGE_BLOCK), axis=1)
    rows = ranks.reshape(len(blocks), rblk, _LANES) >> 7
    span = rows[:, :, -1] - rows[:, :, 0] + 1
    steps = span.reshape(len(blocks), 8, 8).max(axis=2)
    steps = -(-steps // _GATHER_UNROLL) * _GATHER_UNROLL
    # a window starts at its sublane row's least table row, or early
    # enough that its last step is still a row of the table
    first = np.minimum(rows[:, :, 0],
                       _table_rows(n) - np.repeat(steps, 8, axis=1))
    meta = np.zeros((len(blocks), 1, _LANES), np.int32)
    meta[:, 0, :rblk] = first
    meta[:, 0, rblk:rblk + 8] = steps
    return (GatherLayout(jnp.asarray(ranks.reshape(-1)),
                         jnp.asarray(back.reshape(-1).astype(np.int32)),
                         jnp.asarray(meta)), int(steps.sum()))


def _gather_kernel(meta_ref, table_ref, src_ref, back_ref, out_ref,
                   vals_ref):
    """One edge block of gather_sorted. Each tile of 8 sorted sublane rows
    takes `steps` window steps: step j loads table row first_k + j for
    sublane row k, and each edge whose source sits in that row picks its
    lane. Then each stream tile picks its values from the 64 sorted rows
    by its `back` positions (row_reduce's pick)."""
    rblk = EDGE_BLOCK // _LANES
    sub = lax.broadcasted_iota(jnp.int32, (8, _LANES), 0)

    def window(t, carry):
        at = pl.multiple_of(t * 8, 8)
        src = src_ref[pl.ds(at, 8), :]
        first = [meta_ref[0, t * 8 + k] for k in range(8)]
        base = jnp.zeros_like(src)
        for k in range(8):
            base = jnp.where(sub == k, first[k], base)
        rel = lax.shift_right_logical(src, 7) - base   # the step that has it
        lane = jnp.bitwise_and(src, _LANES - 1)

        def turn(i, acc):
            for u in range(_GATHER_UNROLL):
                j = i * _GATHER_UNROLL + u
                rows = jnp.concatenate(
                    [table_ref[pl.ds(first[k] + j, 1), :] for k in range(8)])
                g = jnp.take_along_axis(rows, lane, axis=1)  # in-vreg gather
                acc = jnp.where(rel == j, g, acc)
            return acc

        vals_ref[pl.ds(at, 8), :] = lax.fori_loop(
            0, meta_ref[0, rblk + t] // _GATHER_UNROLL, turn,
            jnp.zeros_like(src))
        return carry

    def restore(t, carry):
        at = pl.multiple_of(t * 8, 8)
        back = back_ref[pl.ds(at, 8), :]
        row = lax.shift_right_logical(back, 7)
        lane = jnp.bitwise_and(back, _LANES - 1)
        picked = jnp.zeros_like(back)
        for r in range(rblk):
            row_r = jnp.broadcast_to(vals_ref[r : r + 1, :], back.shape)
            g = jnp.take_along_axis(row_r, lane, axis=1)
            picked = jnp.where(row == r, g, picked)
        out_ref[pl.ds(at, 8), :] = picked
        return carry

    lax.fori_loop(0, 8, window, 0)
    lax.fori_loop(0, 8, restore, 0)


@jax.jit
def gather_sorted(table: jax.Array, lay: GatherLayout) -> jax.Array:
    """table[src] in stream order for the stream `lay` (gather_layout)
    sorts, `table` a vector of 32-bit values (float32 or int32; moved as
    their bits, so every value equals XLA's gather) that gather_fits: one
    block stream, the table whole in VMEM."""
    n_rows, rblk = _table_rows(table.shape[0]), EDGE_BLOCK // _LANES
    words = jnp.pad(lax.bitcast_convert_type(table, jnp.int32),
                    (0, n_rows * _LANES - table.shape[0]))
    by_block = pl.BlockSpec((rblk, _LANES), lambda b: (b, 0),
                            memory_space=pltpu.VMEM)
    out = pl.pallas_call(
        _gather_kernel,
        grid=(lay.meta.shape[0],),
        in_specs=[pl.BlockSpec((None, 1, _LANES), lambda b: (b, 0, 0),
                               memory_space=pltpu.SMEM),
                  pl.BlockSpec((n_rows, _LANES), lambda b: (0, 0),
                               memory_space=pltpu.VMEM),
                  by_block, by_block],
        out_specs=by_block,
        scratch_shapes=[pltpu.VMEM((rblk, _LANES), jnp.int32)],
        out_shape=jax.ShapeDtypeStruct((lay.src.shape[0] // _LANES, _LANES),
                                       jnp.int32),
        interpret=interpret_mode(),
        name="gather_sorted",
    )(lay.meta, words.reshape(n_rows, _LANES),
      lay.src.reshape(-1, _LANES), lay.back.reshape(-1, _LANES))
    return lax.bitcast_convert_type(out.reshape(-1), table.dtype)


def _by_source(table: jax.Array, src: jax.Array, gather) -> jax.Array:
    """Each edge's value table[src] (src clipped to the table's last slot
    at pad edges): gather_sorted over the layout `gather` where there is
    one and the table gather_fits, else XLA's element gather."""
    if gather is None or not gather_fits(table.shape[0]):
        return table[src]
    return gather_sorted(table, gather)


@partial(jax.jit, static_argnames=("top",))
def analytics_pr(in_src_pad_d, in_iptr_rank, row_ends, out_degree_d, probes,
                 iterations, damping, tol, gather=None, *, top: int):
    """PageRank from 1/N: `iterations` steps, or fewer where a step's L1
    delta is <= `tol` (-1: none is, and every step runs, as Graphalytics
    requires); a step gives (1 - d) / N + d * (the in-neighbours' rank
    over their out-degree + the dangling vertices' rank / N). Ranks and
    `tol` are in `damping`'s dtype (float32 served; a row is summed in
    float32 by row_reduce). `gather` is the stream's GatherLayout over
    Nd + 1 slots (None: XLA's gather). Returns (ranks at the probe ranks,
    the `top` highest ranks and their dst ranks, the sum of all ranks, the
    steps run): nothing vertex-sized leaves the device."""
    nd = out_degree_d.shape[0]
    dt = damping.dtype
    seg = _dst_segments(in_iptr_rank, in_src_pad_d.shape[0])
    last = _last_edges(in_iptr_rank)
    src = jnp.minimum(in_src_pad_d, nd)       # pad edges: the zero slot Nd
    dangling = out_degree_d == 0
    inv = jnp.where(dangling, 0, 1 / jnp.maximum(out_degree_d, 1)).astype(dt)
    n = jnp.asarray(nd, dt)

    def step(r):
        w = _by_source(jnp.concatenate([r * inv, jnp.zeros(1, dt)])
                       .astype(jnp.float32), src, gather)
        pulled = row_reduce(w, seg, row_ends, last,
                            combine="sum").reshape(-1)[:nd].astype(dt)
        lost = jnp.sum(jnp.where(dangling, r, 0))
        return (1 - damping) / n + damping * (pulled + lost / n)

    def body(carry):
        k, r, _ = carry
        new = step(r)
        return k + 1, new, jnp.sum(jnp.abs(new - r))

    steps, r, _ = lax.while_loop(
        lambda c: (c[0] < iterations) & ~(c[2] <= tol), body,
        (jnp.int32(0), jnp.full(nd, 1, dt) / n, jnp.asarray(jnp.inf, dt)))
    top_v, top_i = lax.top_k(r, top)
    return r[probes], top_v, top_i, jnp.sum(r), steps


@partial(jax.jit, static_argnames=("push",))
def analytics_wcc(in_src_pad_d, in_iptr_rank, row_ends, probes, gather=None,
                  *, push: bool):
    """Weakly connected components by FastSV (Zhang, Azad and Hu, 2020):
    every vertex has a parent, itself at first. A round finds for each
    vertex the least grandparent among its in-neighbours (and, `push`, its
    out-neighbours: a graph not stored in both directions), hooks the
    vertex's parent and the vertex itself to it, and shortcuts the vertex
    to its grandparent; rounds run until one changes no grandparent — 4 on
    every Graph500 scale-18 seed tried, where one min-label pass and one
    jump a round took 4 or 5. Parents are dst ranks and only ever fall, so
    jumping them to a fixpoint leaves each vertex the least rank of its
    component. `gather` as analytics_pr's. Returns (labels at the probe
    ranks, components, the largest's size, rounds)."""
    nd = in_iptr_rank.shape[0] - 1
    seg = _dst_segments(in_iptr_rank, in_src_pad_d.shape[0])
    last = _last_edges(in_iptr_rank)
    src = jnp.minimum(in_src_pad_d, nd)
    sentinel = jnp.full(1, nd, jnp.int32)     # above every rank

    def least_near(gf):
        ext = jnp.concatenate([gf, sentinel])
        m = row_reduce(_by_source(ext, src, gather), seg, row_ends, last,
                       combine="min").reshape(-1)[:nd]
        if push:
            m = jnp.minimum(m, jnp.full(nd + 1, nd, jnp.int32)
                            .at[src].min(ext[seg])[:nd])
        return m

    def body(carry):
        f, gf, _, rounds = carry
        m = least_near(gf)
        f = jnp.minimum(jnp.minimum(f.at[f].min(m), m), gf)
        new_gf = f[f]
        return f, new_gf, jnp.any(new_gf != gf), rounds + 1

    ranks = jnp.arange(nd, dtype=jnp.int32)
    f, _, _, rounds = lax.while_loop(
        lambda c: c[2], body, (ranks, ranks, jnp.bool_(True), jnp.int32(0)))
    lab = lax.while_loop(
        lambda c: c[1], lambda c: (c[0][c[0]], jnp.any(c[0][c[0]] != c[0])),
        (f, jnp.bool_(True)))[0]
    components = jnp.sum(lab == ranks)
    largest = jnp.max(jnp.zeros(nd, jnp.int32).at[lab].add(1))
    return lab[probes], components, largest, rounds


# device-runtime observatory (obs/devprof.py, ISSUE 19): jitted entry
# points by program family, probed for live jit-cache size on
# /debug/compiles (see ops/segments.py).
JIT_PROGRAMS = {
    "pb.row_end_prefix": row_end_prefix,
    "pb.row_end_prefix_sparse": row_end_prefix_sparse,
    "pb.recurse_step": recurse_step,
    "pb.bfs_dist": bfs_dist,
    "pb.recurse_fused": recurse_fused,
    "pb.recurse_fused_multi": recurse_fused_multi,
    "pb.row_reduce": row_reduce,
    "pb.gather_sorted": gather_sorted,
    "pb.analytics_pr": analytics_pr,
    "pb.analytics_wcc": analytics_wcc,
}
