"""LDBC Graphalytics LCC: per-vertex triangle counts as batched sorted-set
intersections over the resident graph.

For an undirected simple graph, lcc(v) = t(v) / (d(v) (d(v) - 1) / 2),
where t(v) is the number of edges among v's distinct neighbours and d(v)
the number of those neighbours (self-loops excluded); lcc(v) = 0 where
d(v) < 2.

The layout (`build`, host numpy, once a snapshot) orients every edge from
the lower to the higher end of the degree order — u -> v when (d(u), u) <
(d(v), v), ids renumbered in that order — so that a vertex's out-row R(u)
holds at most ~sqrt(2|E|) ids however large its degree (414 on a Graph500
scale-18 graph whose largest degree is 25,350). Each out-row is a sorted
set, padded with -1 to a power-of-two class (at least MIN_CLASS) and kept
in the table of its class.

A triangle a < b < c has the oriented edges a->b, a->c, b->c and is found
exactly once: at the edge a->b, as the element c of R(a) that R(b) holds
too. The program compares R(a) with R(b) for every oriented edge, all
pairs at once (edges batched by their rows' class pair), and keeps for
each element of R(a) whether R(b) holds it — a "hit". Then

    t_low(a) = hits of a's edges          (a is the triangle's lowest)
    t_mid(b) = hits of the edges into b   (b is its middle)
    t_top(c) = hits on the element c      (c is its highest)

and t = t_low + t_mid + t_top. The hits of one tail a land on the slots of
its own row, so they are summed per tail (segment sums over the edges of a
bucket, which are sorted by tail) before they are spread over the
vertices. Every count is int32 and exact; the ratio is float32.

The dense core. The top K ids of the degree order are the core: an edge
whose tail is in it has its head there too, so a triangle whose lowest
vertex is in the core lies wholly inside it. Those triangles are counted
by one matrix product over the core's symmetric 0/1 adjacency A (int8 on
the MXU, int32 accumulation): t_core(v) = 1/2 Σ_w A[v, w] (A A)[v, w].
The compares run only over the edges whose tail lies outside the core,
and find every other triangle; the two sets are disjoint and hold every
triangle. K is chosen by `core_size` from the compare counts the layout
observes, priced against the product's cost: 0 (no core) for a graph with
no dense part, and the whole graph for a small dense one.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

MIN_CLASS = 8          # the narrowest row a table holds
CHUNK = 8192           # edges a step of a bucket's loop compares
ROW_QUANTUM = 256      # a table's rows come in multiples of this
TILE = 512             # the core's size comes in multiples of this
CORE_MAX = 16384       # the int8 adjacency of the core is at most 256 MiB
ROW_BLOCK = 512        # core rows a step of the product multiplies
# Seconds an element pair of the compare path costs, and seconds a
# multiply-accumulate of the core's product, priced in-process on a TPU
# v5e over a Graph500 scale-18 graph (seed 2147494201) at K = 0, 6,144,
# 8,192, 10,240, 11,264, 12,288, 12,800, 13,312, 14,336 and 16,384, CHUNK
# 8,192: a job less its product (298.5 ms at K = 0, 82.9 ms at 16,384)
# fits 1.57e-12 s a padded pair plus 68.5 ms over all ten K. The margin
# rises with K, 1.4-2.0e-12 up to 10,240 and 3.0e-12 from 12,288 up,
# because narrow buckets cost by the edge and the step; on that graph the
# rule picks K = 12,800 for any price from 1.6e-12 to 3.2e-12. The
# product ran 5.8-6.1e-15 s a MAC at K = 12,288 to 16,384 (int8, 512-row
# blocks).
C_PAIR = 1.6e-12
C_MAC = 5.8e-15


def row_class(length: np.ndarray) -> np.ndarray:
    """The width of the table that holds a row of `length` ids: the least
    power of two >= length, at least MIN_CLASS."""
    n = np.maximum(np.asarray(length, dtype=np.int64), 1)
    return np.maximum(MIN_CLASS, 1 << np.ceil(np.log2(n)).astype(np.int64))


class Layout(NamedTuple):
    """What analytics_lcc reads, built by `build` once a snapshot. Ids are
    positions in the degree order (0..N-1); `order` maps a dst rank to
    its id. Device arrays:

      tables[c]   int32[rows_c, P_c]  out-rows of class c, -1 past a row's
                  end; rows past the class's vertices all -1
      members[c]  int32[rows_c]  the id of each table row's vertex, N past
      tails[b]    int32[n_b]  per bucket b (its edges sorted by tail): the
                  tail's row in its table, the table's first pad row past
                  the bucket's edges
      heads[b]    int32[n_b]  the head's row in its table, likewise
      head_ids[b] int32[n_b]  the head's id, N past the edges
      order       int32[N]    id of each dst rank
      degree      int32[N]    distinct neighbours of each dst rank
      adjacency   int8[Kp, Kp]  the core's symmetric 0/1 adjacency, the
                  core's id N - K + i at row i, Kp = K up to a TILE
                  multiple; [0, 0] without a core

    Host facts: `buckets` (static: the tail's and the head's class index
    of each bucket), `oriented_edges`, `max_out`, `compares` (element
    pairs the compare path compares, padding included), `merge` (Σ |R(a)|
    + |R(b)| over the edges it compares: the least a merge reads), `core`
    (K, static), `core_edges` (the oriented edges inside the core) and
    `spread` (static: the rows of each table, its first, that hold every
    vertex outside the core — the only rows a hit can land on — up to a
    ROW_QUANTUM multiple, so it moves no more often than the tables'
    shapes)."""

    tables: tuple
    members: tuple
    tails: tuple
    heads: tuple
    head_ids: tuple
    order: jax.Array
    degree: jax.Array
    adjacency: jax.Array
    buckets: tuple
    oriented_edges: int
    max_out: int
    compares: int
    merge: int
    core: int
    core_edges: int
    spread: tuple


def _pad_to(n: int, q: int) -> int:
    return max(q, -(-n // q) * q)


def core_size(pairs: np.ndarray) -> int:
    """K, the size of the core, for a graph whose tail id i carries
    pairs[i] padded compares: the K of 0, TILE, 2 TILE, ... and
    min(N, CORE_MAX) that least costs C_PAIR x (the compares of the tails
    below N - K) + C_MAC x Kp^3, the smallest K of a tie."""
    n = len(pairs)
    top = min(n, CORE_MAX)
    ks = np.unique(np.append(np.arange(0, top + 1, TILE), top))
    below = np.concatenate([[0.0], np.cumsum(pairs, dtype=np.float64)])
    kp = -(-ks // TILE) * TILE
    cost = C_PAIR * below[n - ks] + C_MAC * kp.astype(np.float64) ** 3
    return int(ks[np.argmin(cost)])


@partial(jax.jit, static_argnames=("kp",))
def _adjacency(slots, *, kp):
    """int8[kp, kp]: 1 at the sorted flat `slots`, 0 elsewhere."""
    return jnp.zeros(kp * kp, jnp.int8).at[slots].set(
        1, mode="promise_in_bounds", indices_are_sorted=True,
        unique_indices=True).reshape(kp, kp)


def build(iptr: np.ndarray, nbrs: np.ndarray, core: int | None = None
          ) -> Layout:
    """The layout of the undirected graph whose vertex v (a dst rank, 0..
    N-1) has the neighbours nbrs[iptr[v]:iptr[v+1]] (ranks, sorted; a
    self-loop or a repeated neighbour is dropped here). `core` sets K in
    place of core_size's choice (tests only)."""
    iptr = np.asarray(iptr, dtype=np.int64)
    n = len(iptr) - 1
    v = np.repeat(np.arange(n, dtype=np.int64), np.diff(iptr))
    u = np.asarray(nbrs, dtype=np.int64)
    keep = u != v
    if len(u):
        keep[1:] &= (u[1:] != u[:-1]) | (v[1:] != v[:-1])
    v, u = v[keep], u[keep]
    degree = np.bincount(v, minlength=n)
    by_degree = np.lexsort((np.arange(n), degree))
    order = np.empty(n, dtype=np.int64)
    order[by_degree] = np.arange(n)
    a, b = order[v], order[u]
    up = a < b
    a, b = a[up], b[up]
    s = np.lexsort((b, a))
    a, b = a[s], b[s]
    od = np.bincount(a, minlength=n)
    optr = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(od, out=optr[1:])
    cls = row_class(od)
    widths = np.unique(cls[od > 0])
    # an edge a->b can hold a triangle only if R(a) has more than b and
    # R(b) is not empty
    live = (od[a] > 1) & (od[b] > 0)
    if core is None:
        core = core_size(np.bincount(a[live], minlength=n,
                                     weights=cls[a[live]] * cls[b[live]]))
    inner = a >= n - core
    kp = -(-core // TILE) * TILE
    ca, cb = a[inner] - (n - core), b[inner] - (n - core)
    adjacency = _adjacency(np.sort(np.concatenate(
        [ca * kp + cb, cb * kp + ca])).astype(np.int32), kp=kp)
    live &= ~inner
    row = np.full(n, -1, dtype=np.int64)     # a vertex's row in its table
    tables, members, pad_row, spread = [], [], [], []
    for w in widths:
        mine = np.flatnonzero((cls == w) & (od > 0))
        row[mine] = np.arange(len(mine))
        rows = _pad_to(len(mine) + 1, ROW_QUANTUM)
        tab = np.full((rows, int(w)), -1, dtype=np.int32)
        lens = od[mine]
        r = np.repeat(np.arange(len(mine)), lens)
        col = np.arange(int(lens.sum())) - np.repeat(
            np.cumsum(lens) - lens, lens)
        tab[r, col] = b[np.repeat(optr[mine], lens) + col]
        tables.append(tab)
        mem = np.full(rows, n, dtype=np.int32)
        mem[:len(mine)] = mine
        members.append(mem)
        pad_row.append(len(mine))
        spread.append(min(rows, _pad_to(int(np.searchsorted(
            mine, n - core)), ROW_QUANTUM)))
    a, b = a[live], b[live]
    key = np.searchsorted(widths, cls[a]) * len(widths) \
        + np.searchsorted(widths, cls[b])
    s = np.argsort(key, kind="stable")          # stays sorted by tail
    a, b, key = a[s], b[s], key[s]
    buckets, tails, heads, head_ids = [], [], [], []
    compares = 0
    for k in np.unique(key):
        lo, hi = np.searchsorted(key, [k, k + 1])
        i, j = int(k) // len(widths), int(k) % len(widths)
        m = _pad_to(int(hi - lo), CHUNK)
        t = np.full(m, pad_row[i], dtype=np.int32)
        h = np.full(m, pad_row[j], dtype=np.int32)
        hid = np.full(m, n, dtype=np.int32)
        t[:hi - lo] = row[a[lo:hi]]
        h[:hi - lo] = row[b[lo:hi]]
        hid[:hi - lo] = b[lo:hi]
        buckets.append((i, j))
        tails.append(t)
        heads.append(h)
        head_ids.append(hid)
        compares += m * int(widths[i]) * int(widths[j])
    dev = lambda xs: tuple(jnp.asarray(x) for x in xs)  # noqa: E731
    return Layout(dev(tables), dev(members), dev(tails), dev(heads),
                  dev(head_ids), jnp.asarray(order.astype(np.int32)),
                  jnp.asarray(degree.astype(np.int32)), adjacency,
                  tuple(buckets), int(od.sum()), int(od.max(initial=0)),
                  int(compares), int((od[a] + od[b]).sum()), int(core),
                  int(inner.sum()), tuple(spread))


def _hits(u: jax.Array, v: jax.Array) -> jax.Array:
    """int32[m, Pa]: 1 where the element u[e, j] (>= 0) is one of v[e, :].
    Compared with the edges along the lanes: [Pa, Pb, m], reduced over
    Pb."""
    ut, vt = u.T, v.T
    eq = jnp.any(ut[:, None, :] == vt[None, :, :], axis=1)
    return (eq & (ut >= 0)).astype(jnp.int32).T


def _bucket(tab_a, tab_b, h_a, t, tails, heads, head_ids):
    """One bucket's edges, CHUNK at a time: each edge's hits summed onto
    its tail's row of h_a (a segment sum: the tails are sorted) and its
    triangle count added to its head."""

    def body(k, carry):
        h, t = carry
        ua = lax.dynamic_slice_in_dim(tails, k * CHUNK, CHUNK)
        vb = lax.dynamic_slice_in_dim(heads, k * CHUNK, CHUNK)
        hb = lax.dynamic_slice_in_dim(head_ids, k * CHUNK, CHUNK)
        hit = _hits(tab_a.at[ua].get(mode="promise_in_bounds",
                                     indices_are_sorted=True),
                    tab_b.at[vb].get(mode="promise_in_bounds"))
        h = h.at[ua].add(hit, indices_are_sorted=True,
                         mode="promise_in_bounds")
        t = t.at[hb].add(hit.sum(axis=1), mode="promise_in_bounds")
        return h, t

    return lax.fori_loop(0, tails.shape[0] // CHUNK, body, (h_a, t))


def _core_triangles(adjacency):
    """int32[Kp]: each core vertex's triangles inside the core, 1/2 Σ_w
    A[v, w] (A A)[v, w], ROW_BLOCK rows of A A at a time. Exact: the
    operands are 0/1 int8, an entry of A A is at most Kp, a row's sum
    2 t(v) <= d(v) (d(v) - 1) < 2^31."""
    kp = adjacency.shape[0]

    def block(rows):
        p = jnp.dot(rows, adjacency, preferred_element_type=jnp.int32)
        return jnp.sum(jnp.where(rows > 0, p, 0), axis=1)

    return lax.map(block, adjacency.reshape(-1, ROW_BLOCK, kp)).reshape(
        kp) // 2


@partial(jax.jit, static_argnames=("buckets", "core", "spread"))
def analytics_lcc(tables, members, tails, heads, head_ids, order, degree,
                  adjacency, probes, *, buckets, core, spread):
    """Graphalytics LCC over a Layout: (t at the probe ranks, lcc at the
    probe ranks, Σ t / 3, Σ lcc over every vertex). Counts int32, the
    ratio float32; nothing vertex-sized leaves the device."""
    n = order.shape[0]
    h = [jnp.zeros(tab.shape, jnp.int32) for tab in tables]
    t = jnp.zeros(n + 1, jnp.int32)
    for (i, j), ua, vb, hb in zip(buckets, tails, heads, head_ids):
        h[i], t = _bucket(tables[i], tables[j], h[i], t, ua, vb, hb)
    for tab, mem, hi, r in zip(tables, members, h, spread):
        tab, hi = tab[:r], hi[:r]
        t = t.at[mem[:r]].add(hi.sum(axis=1), mode="promise_in_bounds")
        t = t.at[jnp.where(tab < 0, n, tab)].add(hi,
                                                 mode="promise_in_bounds")
    if core:
        t = t.at[n - core:n].add(_core_triangles(adjacency)[:core])
    tri = t[order]
    d = degree.astype(jnp.float32)
    lcc = jnp.where(degree > 1,
                    tri.astype(jnp.float32) / (d * (d - 1) * 0.5), 0.0)
    return tri[probes], lcc[probes], jnp.sum(tri) // 3, jnp.sum(lcc)


JIT_PROGRAMS = {"pb.analytics_lcc": analytics_lcc}
