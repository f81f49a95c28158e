"""Pure-device SSSP: Bellman-Ford edge relaxation under jit.

Reference semantics: query/shortest.go (host Dijkstra over a hash-map
adjacency). On the device it becomes an iterative sparse op over the
HBM-resident CSR with NO host round-trips inside the loop: one segment-min
per iteration over all E edges, lax.while_loop until fixpoint. Replaces
pointer-chasing Dijkstra for the device path (the exact k-shortest-path
semantics stay in query/shortest.py, which takes this tier only where
ops/pallas_bfs.bfs_dist does not apply).
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax


class SSSPResult(NamedTuple):
    dist: jax.Array        # float32[num_nodes]; inf = unreachable
    parent: jax.Array      # int32[num_nodes]; -1 = none/root
    iterations: jax.Array


@partial(jax.jit, static_argnames=("num_nodes", "max_iters"))
def sssp(subjects: jax.Array, indptr: jax.Array, indices: jax.Array,
         weights: jax.Array | None, src: jax.Array, *, num_nodes: int,
         max_iters: int = 64) -> SSSPResult:
    """Single-source shortest paths by iterated edge relaxation.

    One iteration = relax ALL E edges: candidate[dst] = min(dist[src]+w) via
    a segment-min scatter; while_loop until no distance changes. O(E) work
    per iteration, fully vectorized — the VPU-shaped dual of Dijkstra.
    """
    E = indices.shape[0]
    # per-edge source row: row r owns edges [indptr[r], indptr[r+1])
    edge_src_row = jnp.searchsorted(indptr, jnp.arange(E, dtype=indptr.dtype),
                                    side="right").astype(jnp.int32) - 1
    edge_src = jnp.take(subjects, edge_src_row)
    edge_dst = indices
    w = weights if weights is not None else jnp.ones((E,), dtype=jnp.float32)

    inf = jnp.float32(jnp.inf)
    dist0 = jnp.full((num_nodes,), inf).at[src].set(0.0)
    parent0 = jnp.full((num_nodes,), -1, dtype=jnp.int32)

    def cond(carry):
        _d, _p, changed, it = carry
        return changed & (it < max_iters)

    def body(carry):
        dist, parent, _changed, it = carry
        cand = jnp.take(dist, edge_src) + w
        # segment-min into destinations
        new_dist = dist.at[edge_dst].min(cand, mode="drop")
        improved = new_dist < dist
        # parent recovery: an edge "wins" if its candidate equals the new
        # distance of an improved dst; any winner is a valid SSSP-tree parent
        # (max picks one deterministically)
        wins = (cand == jnp.take(new_dist, edge_dst)) & jnp.take(improved, edge_dst)
        cleared = jnp.where(improved, jnp.int32(-1), parent)  # stale parents out
        new_parent = cleared.at[jnp.where(wins, edge_dst, num_nodes)].max(
            edge_src, mode="drop")
        return new_dist, new_parent, jnp.any(improved), it + 1

    dist, parent, _c, it = lax.while_loop(
        cond, body, (dist0, parent0, jnp.bool_(True), jnp.int32(0)))
    return SSSPResult(dist, parent, it)


# device-runtime observatory (obs/devprof.py, ISSUE 19): jitted entry
# points by program family, probed for live jit-cache size on
# /debug/compiles (see ops/segments.py).
JIT_PROGRAMS = {
    "traversal.sssp": sssp,
}
