"""Plain reference of the k-hop neighbourhood a uid variable inside
`@recurse` holds. numpy only: nothing of query/ or ops/ is imported, so
the tests can hold every tier of query/recurse.py to it.

    { var(func: uid(<roots>)) @recurse(depth: k) { v as follows } }

Semantics (upstream query/recurse.go:129-141 and query/query.go
populateUidValVar, written from memory): with `loop: false` the traversal
dedups EDGES, not vertices — an edge is expanded the first time a level's
frontier reaches its source and never again — so a level's destinations
are those of its fresh edges, they are the next level's frontier, and the
variable is the union of the destinations of levels 1..k. With
`loop: true` every edge of the frontier is expanded at every level.

On an undirected graph stored in both directions that union is every
vertex within k hops of the root, plus the root itself from k = 2 on (it
comes back over the reverse edge of its first hop): `within_hops` is that
second definition, and tests tie the two.
"""

from __future__ import annotations

import numpy as np


def khop_levels(src, dst, roots, depth: int, loop: bool = False):
    """Level expansion over (src, dst) edge pairs from `roots`.

    Returns (levels, union): levels[i] is the sorted destination set of
    level i + 1 — a level whose frontier is empty ends the list, so
    len(levels) <= depth — and union the sorted union of all of them."""
    src = np.asarray(src, dtype=np.int64)
    dst = np.asarray(dst, dtype=np.int64)
    seen = np.zeros(len(src), dtype=bool)
    frontier = np.unique(np.asarray(roots, dtype=np.int64))
    levels: list[np.ndarray] = []
    for _ in range(depth):
        if not len(frontier):
            break
        live = np.isin(src, frontier)
        if not loop:
            live &= ~seen
            seen |= live
        frontier = np.unique(dst[live])
        levels.append(frontier)
    union = np.unique(np.concatenate(levels)) if levels \
        else np.zeros(0, np.int64)
    return levels, union


def within_hops(src, dst, root: int, depth: int) -> np.ndarray:
    """Sorted vertices u with 1 <= d(root, u) <= depth, by a plain
    vertex-visited breadth-first search over the directed pairs."""
    src = np.asarray(src, dtype=np.int64)
    dst = np.asarray(dst, dtype=np.int64)
    visited = {int(root)}
    frontier = np.asarray([root], dtype=np.int64)
    for _ in range(depth):
        nxt = np.unique(dst[np.isin(src, frontier)])
        frontier = np.asarray([u for u in nxt.tolist() if u not in visited],
                              dtype=np.int64)
        if not len(frontier):
            break
        visited.update(frontier.tolist())
    visited.discard(int(root))
    return np.asarray(sorted(visited), dtype=np.int64)
