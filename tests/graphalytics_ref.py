"""Plain reference for LDBC Graphalytics' PR, WCC and LCC (specification
v1.0),
independent of the code under test: numpy / scipy in float64, nothing
imported from dgraph_tpu. tests/test_graphalytics.py holds the served
kinds `pr` and `wcc` to it for every vertex, tests/test_lcc.py the kind
`lcc`.

The vertex set is every vertex with an edge. PR: `iterations` steps from
1/N, each PR(v) = (1 - d) / N + d * (sum over in-neighbours u of
PR(u) / outdeg(u) + sum over dangling w of PR(w) / N). WCC: the weakly
connected components, each vertex labelled by its component's least
member. LCC: over the symmetrised simple graph (both directions of every
edge, self-loops and repeats dropped), t(v) is the number of edges among
v's distinct neighbours and lcc(v) = t(v) / (d(v) (d(v) - 1) / 2), 0 where
the degree d(v) < 2."""

from __future__ import annotations

import numpy as np
from scipy.sparse import coo_matrix
from scipy.sparse.csgraph import connected_components


def kronecker(scale: int, seed: int, edge_factor: int = 16,
              a: float = 0.57, b: float = 0.19, c: float = 0.19):
    """Graph500's Kronecker graph stored in both directions, as uids from
    1: int64 (src, dst), self-loops and duplicates dropped."""
    n_edges = edge_factor << scale
    rng = np.random.default_rng([seed, 500])
    src = np.zeros(n_edges, dtype=np.int64)
    dst = np.zeros(n_edges, dtype=np.int64)
    for _ in range(scale):
        r = rng.random(n_edges)
        src = (src << 1) | (r >= a + b)
        dst = (dst << 1) | (((r >= a) & (r < a + b)) | (r >= a + b + c))
    perm = rng.permutation(1 << scale)
    src, dst = perm[src], perm[dst]
    keep = src != dst
    key = np.unique(np.concatenate([(src[keep] << 32) | dst[keep],
                                    (dst[keep] << 32) | src[keep]]))
    return (key >> 32) + 1, (key & 0xFFFFFFFF) + 1


def vertices(src, dst) -> np.ndarray:
    return np.unique(np.concatenate([np.asarray(src), np.asarray(dst)]))


def pagerank(src, dst, iterations: int = 10, damping: float = 0.85,
             dangling: bool = True) -> tuple[np.ndarray, np.ndarray]:
    """(sorted vertex uids, their ranks). `dangling=False` drops the
    dangling vertices' mass: the variant a test holds must NOT pass."""
    nodes = vertices(src, dst)
    n = len(nodes)
    s = np.searchsorted(nodes, src)
    t = np.searchsorted(nodes, dst)
    outdeg = np.bincount(s, minlength=n).astype(np.float64)
    sink = outdeg == 0
    r = np.full(n, 1.0 / n)
    for _ in range(iterations):
        pulled = np.bincount(t, weights=r[s] / outdeg[s], minlength=n)
        lost = r[sink].sum() if dangling else 0.0
        r = (1.0 - damping) / n + damping * (pulled + lost / n)
    return nodes, r


def wcc(src, dst) -> tuple[np.ndarray, np.ndarray]:
    """(sorted vertex uids, the uid of each one's component's least
    member)."""
    nodes = vertices(src, dst)
    n = len(nodes)
    s = np.searchsorted(nodes, src)
    t = np.searchsorted(nodes, dst)
    adj = coo_matrix((np.ones(len(s)), (s, t)), shape=(n, n)).tocsr()
    _, comp = connected_components(adj, directed=True, connection="weak")
    least = np.full(comp.max() + 1 if n else 0, n, dtype=np.int64)
    np.minimum.at(least, comp, np.arange(n))
    return nodes, nodes[least[comp]]


def lcc(src, dst) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(sorted vertex uids, t(v) as int64, lcc(v) as float64): one
    np.intersect1d of the two ends' neighbour sets an edge."""
    nodes = vertices(src, dst)
    n = len(nodes)
    s = np.searchsorted(nodes, src)
    t = np.searchsorted(nodes, dst)
    keep = s != t
    pairs = np.unique(np.stack([np.concatenate([s[keep], t[keep]]),
                                np.concatenate([t[keep], s[keep]])], 1),
                      axis=0)
    starts = np.searchsorted(pairs[:, 0], np.arange(n + 1))
    nbrs = [pairs[starts[v]:starts[v + 1], 1] for v in range(n)]
    tri = np.zeros(n, dtype=np.int64)
    for a, b in pairs[pairs[:, 0] < pairs[:, 1]].tolist():
        common = len(np.intersect1d(nbrs[a], nbrs[b], assume_unique=True))
        tri[a] += common
        tri[b] += common
    tri //= 2                     # each triangle at v is seen from two edges
    deg = np.diff(starts).astype(np.float64)
    with np.errstate(divide="ignore", invalid="ignore"):
        ratio = np.where(deg > 1, tri / (deg * (deg - 1) / 2), 0.0)
    return nodes, tri, ratio


def rel_error(got: np.ndarray, want: np.ndarray) -> float:
    """Largest relative error of `got` against `want` (Graphalytics'
    epsilon-match reads relative error per vertex)."""
    return float(np.max(np.abs(np.asarray(got, np.float64) - want)
                        / np.abs(want)))
