"""pb.gather_sorted — the gather by source rank of a whole-graph step
(pb.analytics_pr, pb.analytics_wcc) as a block stream over a VMEM-resident
table, each 8,192-edge block sorted by source once a snapshot
(pb.gather_layout) — held bit for bit to XLA's `table[src]` in interpret
mode, with the layout's invariants and the shape test that keeps XLA's
gather for a table over the VMEM budget."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dgraph_tpu.ops import pallas_bfs as pb

B = pb.EDGE_BLOCK


def _uniform(rng):
    """Sources uniform over 3,000 ranks; the last block part-filled, its
    pad edges on the sentinel slot Nd."""
    return 3_000, rng.integers(0, 3_000, 3 * B - 700)


def _hub(rng):
    """One source on two and a half whole blocks among a sparse rest: a
    sublane row whose window is a single table row, blocks that are one
    source throughout."""
    src = rng.integers(0, 2_000, 3 * B)
    src[B // 3: B // 3 + 5 * B // 2] = 1_234
    return 2_000, src


def _degree_one(rng):
    """Every rank the source of one edge, in shuffled order: the widest
    windows a block of distinct sources can have."""
    return 2 * B, rng.permutation(2 * B)


def _ragged(rng):
    """Nd = 1,001: the table's last row of 128 part-filled, its last
    (8, 128) tile mostly padding; sources crowd the top ranks."""
    return 1_001, 1_000 - rng.integers(0, 40, B + 5)


LAYOUTS = {"uniform": _uniform, "hub": _hub, "degree_one": _degree_one,
           "ragged": _ragged}


def _stream(layout):
    """(Nd, int32[E_pad] table slots by stream position: pad edges Nd)."""
    nd, src = LAYOUTS[layout](np.random.default_rng(len(layout)))
    e_pad = -(-len(src) // B) * B
    out = np.full(e_pad, nd, dtype=np.int32)
    out[:len(src)] = src
    return nd, out


@pytest.mark.parametrize("dtype", ["float32", "int32"])
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_gather_sorted_is_xla_gather_bit_for_bit(layout, dtype):
    """Every edge's value is table[src] to the bit: float32 tables of
    arbitrary bit patterns (NaNs and denormals among them) and int32
    tables over the whole range, the sentinel slot Nd holding a value of
    its own."""
    nd, src = _stream(layout)
    rng = np.random.default_rng(7)
    bits = rng.integers(-2**31, 2**31 - 1, nd + 1, dtype=np.int64)
    table = bits.astype(np.int32).view(np.dtype(dtype))
    lay, windows = pb.gather_layout(src, nd + 1)
    assert windows > 0
    got = np.asarray(pb.gather_sorted(jnp.asarray(table), lay))
    assert got.dtype == np.dtype(dtype)
    assert np.array_equal(got.view(np.int32), table[src].view(np.int32))


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_layout_sorts_each_block_and_windows_cover_every_source(layout):
    """Each block's sorted sources are a permutation of its stream's,
    `back` takes them back to stream order, and each sublane row's
    window — its first table row and its tile's steps — holds every
    source of the row inside the table."""
    nd, src = _stream(layout)
    lay, windows = pb.gather_layout(src, nd + 1)
    ranks = np.asarray(lay.src).reshape(-1, B)
    back = np.asarray(lay.back).reshape(-1, B)
    meta = np.asarray(lay.meta)[:, 0, :]
    blocks = src.reshape(-1, B)
    assert np.array_equal(ranks, np.sort(blocks, axis=1))
    assert np.array_equal(np.sort(back, axis=1),
                          np.broadcast_to(np.arange(B), back.shape))
    assert np.array_equal(np.take_along_axis(ranks, back, axis=1), blocks)
    rows = ranks.reshape(len(blocks), B // 128, 128) >> 7
    first = meta[:, :B // 128]
    steps = np.repeat(meta[:, B // 128: B // 128 + 8], 8, axis=1)
    assert np.all(steps % pb._GATHER_UNROLL == 0)
    assert windows == int(meta[:, B // 128: B // 128 + 8].sum())
    assert np.all(first[:, :, None] <= rows)
    assert np.all(rows < (first + steps)[:, :, None])
    assert np.all(first >= 0)
    assert np.all(first + steps <= pb._table_rows(nd + 1))


def test_windows_follow_the_sort_not_the_table():
    """A block of 8,192 distinct sources over 16,384 ranks meets each
    table row once: its 64 sublane rows span about two table rows each,
    where the stream order's tiles would meet all 128."""
    _, src = _stream("degree_one")
    lay, windows = pb.gather_layout(src, 2 * B + 1)
    spans = np.asarray(lay.meta)[:, 0, B // 128: B // 128 + 8]
    assert spans.max() == pb._GATHER_UNROLL
    assert windows == 16 * pb._GATHER_UNROLL


@pytest.mark.parametrize("over", [False, True])
def test_a_table_over_the_vmem_budget_keeps_xla_gather(over):
    """The shape test at the size that crosses it: the largest table
    GATHER_TABLE_MAX holds gathers in the kernel, one more (8, 128) tile
    of values keeps XLA's element gather — no layout is built for it,
    and a program handed a layout for it still gathers in XLA."""
    n = pb.GATHER_TABLE_MAX // 4 + (8 * 128 if over else 0)
    assert pb.gather_fits(n) is not over
    src = np.zeros(B, dtype=np.int32)
    lay, windows = pb.gather_layout(src, n)
    assert (lay is None) is over and (windows == 0) is over

    def arg(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype)

    given = pb.GatherLayout(arg((B,)), arg((B,)), arg((1, 1, 128)))
    text = jax.jit(pb._by_source).lower(
        arg((n,), jnp.float32), arg((B,)), given).as_text()
    assert ("gather_sorted" in text) is not over


def test_served_programs_keep_their_names_with_the_kernel():
    """The programs as served — handed a GatherLayout — keep the module
    names gx.pr_roofline / gx.wcc_roofline read their device time by,
    and call the kernel."""
    nd, e_pad, n_items = 300, B, pb._ITEM_CLASS

    def arg(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype)

    graph = (arg((e_pad,)), arg((nd + 1,)),
             pb.RowEnds(arg((n_items,)), arg((n_items,))))
    lay = pb.GatherLayout(arg((e_pad,)), arg((e_pad,)), arg((1, 1, 128)))
    pr = pb.analytics_pr.lower(*graph, arg((nd,)), arg((64,)), arg(()),
                               arg((), jnp.float32), arg((), jnp.float32),
                               lay, top=20).as_text()
    wcc = pb.analytics_wcc.lower(*graph, arg((64,)), lay,
                                 push=False).as_text()
    assert "module @jit_analytics_pr " in pr and "gather_sorted" in pr
    assert "module @jit_analytics_wcc " in wcc and "gather_sorted" in wcc
