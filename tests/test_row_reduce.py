"""pb.row_reduce — the per-destination sum / min of a whole-graph step
(pb.analytics_pr, pb.analytics_wcc) as a Pallas block stream — held to a
plain reduction at every edge of the blocking scheme, in interpret mode,
and the programs' module names that the benchmark reads their device time
by (benchmarks/harness/graphalytics.py PROGRAMS)."""

import importlib.util
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dgraph_tpu.ops import pallas_bfs as pb

B = pb.EDGE_BLOCK


def _hub(rng):
    """700 one-edge rows; a row ending on block 0's last edge; a hub
    over two whole blocks in which no row ends; 1,500 short rows ending
    mid-block, then pad edges. Nd = 2,202: three rank tiles, the last
    part-filled."""
    return np.concatenate([np.ones(700, int), [B - 700], [2 * B + 500],
                           rng.integers(1, 6, 1500)])


def _many(rng):
    """3,000 rows of 1-40 edges: rows end everywhere, a block's ends span
    rank tiles."""
    return rng.integers(1, 41, 3000)


def _single(rng):
    """5,000 one-edge rows: 8,192 rank ends a block, over eight tiles."""
    del rng
    return np.ones(5000, int)


LAYOUTS = {"hub": _hub, "many": _many, "single": _single}


def _stream(degrees):
    """(iptr, E, E_pad) of a dst-sorted stream with these row degrees."""
    iptr = np.zeros(len(degrees) + 1, dtype=np.int32)
    np.cumsum(degrees, out=iptr[1:])
    e = int(iptr[-1])
    return iptr, e, max(B, -(-e // B) * B)


@pytest.mark.parametrize("combine", ["sum", "min"])
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_row_reduce_equals_a_plain_segment_reduction(layout, combine):
    """Each rank's row combined, across any number of blocks and tiles;
    pad edges hold a value that would show if it reached a rank. Sums:
    values spanning 1e-9..1, where an f32 prefix difference fails — the
    kernel is held to the float64 sum at 1e-6 relative and to XLA's
    segment_sum. Mins: exact, both."""
    rng = np.random.default_rng(len(layout))
    iptr, e, e_pad = _stream(LAYOUTS[layout](rng))
    nd = len(iptr) - 1
    if layout == "hub":
        ends = iptr[1:] - 1
        assert B - 1 in ends and e % B and nd % pb.RANK_TILE
        assert not np.any((ends >= B) & (ends < 3 * B))   # blocks 1, 2
    seg = pb._dst_segments(jnp.asarray(iptr), e_pad)
    if combine == "sum":
        vals = np.full(e_pad, 7.0, np.float32)
        vals[:e] = 10.0 ** rng.uniform(-9, 0, e)
        want = np.add.reduceat(vals[:e].astype(np.float64), iptr[:-1])
        xla = jax.ops.segment_sum(vals, seg, num_segments=nd + 1,
                                  indices_are_sorted=True)[:nd]
        # the data defeats a prefix difference: some row reads > 1e-4 off
        pre = np.concatenate([[0], np.cumsum(vals[:e])]).astype(np.float32)
        diff = pre[iptr[1:]] - pre[iptr[:-1]]
        assert np.max(np.abs(diff - want) / want) > 1e-4
    else:
        vals = np.full(e_pad, -5, np.int32)
        vals[:e] = rng.integers(0, np.iinfo(np.int32).max, e)
        want = np.minimum.reduceat(vals[:e], iptr[:-1])
        xla = jax.ops.segment_min(vals, seg, num_segments=nd + 1,
                                  indices_are_sorted=True)[:nd]
    got = pb.row_reduce(jnp.asarray(vals), seg, pb._row_ends(iptr, e_pad),
                        pb._last_edges(jnp.asarray(iptr)), combine=combine)
    assert got.dtype == vals.dtype
    got = np.asarray(got).reshape(-1)
    assert np.all(got[nd:] == 0)
    got = got[:nd]
    if combine == "sum":
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=0)
        # and no row further from it than XLA's f32 segment_sum, whose
        # sequential adds read 1.2e-5 off on the 16,884-edge hub
        xla_off = np.abs(np.asarray(xla, np.float64) - want)
        assert np.all(np.abs(got - want) <= xla_off + 1e-6 * want)
    else:
        np.testing.assert_array_equal(got, want)
        np.testing.assert_array_equal(got, np.asarray(xla))


def _harness_programs() -> dict:
    """benchmarks/harness/graphalytics.py's PROGRAMS: op -> module name."""
    path = (pathlib.Path(__file__).resolve().parents[1] / "benchmarks"
            / "harness" / "graphalytics.py")
    spec = importlib.util.spec_from_file_location("_gx_harness", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.PROGRAMS


@pytest.mark.parametrize("op", ["gx_pr", "gx_wcc"])
def test_programs_keep_the_names_the_benchmark_reads(op):
    """gx.pr_roofline / gx.wcc_roofline find each program's device time by
    its module name in the trace: lowering the served program still names
    it as the harness expects."""
    nd, e_pad, n_items = 300, B, pb._ITEM_CLASS

    def arg(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype)

    graph = (arg((e_pad,)), arg((nd + 1,)),
             pb.RowEnds(arg((n_items,)), arg((n_items,))))
    if op == "gx_pr":
        lowered = pb.analytics_pr.lower(
            *graph, arg((nd,)), arg((64,)), arg(()), arg((), jnp.float32),
            arg((), jnp.float32), top=20)
    else:
        lowered = pb.analytics_wcc.lower(*graph, arg((64,)), push=False)
    name = _harness_programs()[op]
    assert f"module @{name} " in lowered.as_text()
