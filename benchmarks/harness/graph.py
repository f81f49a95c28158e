"""The benchmark's own graph: the Graph500 Kronecker generator, the CSR the
plain references read, and the N-Quads writer that feeds `bulk`.

The generator follows the Graph500 specification's kernel-1 input: R-MAT
quadrant sampling (A, B, C, D = 1 - A - B - C) of edge_factor * 2**scale
edges, vertex labels scrambled by a seeded permutation, the graph taken as
undirected (each edge stored in both directions), self-loops and duplicate
edges dropped. numpy and scipy only; copied from dgraph_tpu/models/rmat.py and
chip_smoke.py (PR 21) and brought to the specification, so that a later PR
can change the program and the smoke, and not the yardstick.
"""

from __future__ import annotations

import numpy as np
from scipy.sparse import csr_matrix
from scipy.sparse.csgraph import breadth_first_order

# every key a config's `data` section may hold; from_config refuses any
# other, and any value it does not honour
DATA_KEYS = {"generator", "scale", "edge_factor", "a", "b", "c", "directed",
             "permute_labels", "dedup", "self_loops", "schema", "score_mod",
             "grp_mod"}
# what the one generator makes: a config states these, and only so
FIXED = {"generator": "graph500-kronecker", "directed": False,
         "permute_labels": True, "dedup": True, "self_loops": False}


def kronecker_edges(scale: int, edge_factor: int, a: float, b: float,
                    c: float, seed: int) -> np.ndarray:
    """Stored edges over 2**scale vertices as int64 [E, 2] (src, dst),
    sorted by (src, dst): edge_factor * 2**scale R-MAT samples (bit-by-bit
    quadrant sampling), labels permuted from the seed, both directions of
    every edge, self-loops and duplicates removed."""
    n_edges = edge_factor << scale
    rng = np.random.default_rng([seed, 500])
    src = np.zeros(n_edges, dtype=np.int64)
    dst = np.zeros(n_edges, dtype=np.int64)
    for _ in range(scale):
        r = rng.random(n_edges)
        src_bit = (r >= a + b).astype(np.int64)
        dst_bit = ((r >= a) & (r < a + b) | (r >= a + b + c)).astype(np.int64)
        src = (src << 1) | src_bit
        dst = (dst << 1) | dst_bit
    perm = rng.permutation(1 << scale)
    src, dst = perm[src], perm[dst]
    keep = src != dst
    src, dst = src[keep], dst[keep]
    key = np.unique(np.concatenate([(src << 32) | dst, (dst << 32) | src]))
    return np.stack([key >> 32, key & 0xFFFFFFFF], axis=1)


class Graph:
    """Forward CSR in uid space (uids start at 1) plus the per-subject
    value columns `score` and `grp`."""

    def __init__(self, edges: np.ndarray, seed: int, score_mod: int,
                 grp_mod: int) -> None:
        edges = np.asarray(edges, dtype=np.int64)
        self.subjects, counts = np.unique(edges[:, 0], return_counts=True)
        self.indptr = np.zeros(len(self.subjects) + 1, dtype=np.int64)
        np.cumsum(counts, out=self.indptr[1:])
        self.indices = np.ascontiguousarray(edges[:, 1])
        self.n = int(max(self.subjects.max(), self.indices.max())) + 1
        self.row = np.full(self.n, -1, dtype=np.int64)
        self.row[self.subjects] = np.arange(len(self.subjects))
        self.degree = np.zeros(self.n, dtype=np.int64)
        self.degree[self.subjects] = counts
        rng = np.random.default_rng(seed)
        self.score = np.full(self.n, -1, dtype=np.int64)
        self.score[self.subjects] = rng.integers(
            0, score_mod, len(self.subjects))
        self.grp = np.full(self.n, -1, dtype=np.int64)
        self.grp[self.subjects] = rng.integers(0, grp_mod, len(self.subjects))

    @classmethod
    def from_config(cls, data: dict, seed: int) -> "Graph":
        """The deployment's graph as its config file's `data` section
        states it, from the run's seed. Every key is honoured or refused:
        a config cannot state a graph that the run does not hold."""
        unknown = set(data) - DATA_KEYS
        if unknown:
            raise ValueError(f"data keys {sorted(unknown)} are read by "
                             f"nothing")
        stated = {k: data[k] for k in FIXED}
        if stated != FIXED:
            raise ValueError(f"the generator makes {FIXED}; the config "
                             f"states {stated}")
        edges = kronecker_edges(data["scale"], data["edge_factor"],
                                data["a"], data["b"], data["c"], seed) + 1
        return cls(edges, seed, data["score_mod"], data["grp_mod"])

    @property
    def csr(self):
        """The directed `follows` edges as a scipy CSR over uid rows, built
        on first use (float64 data: csgraph copies anything else a call)."""
        if getattr(self, "_csr", None) is None:
            indptr = np.zeros(self.n + 1, dtype=np.int64)
            np.cumsum(self.degree, out=indptr[1:])
            self._csr = csr_matrix(
                (np.ones(len(self.indices)), self.indices.astype(np.int32),
                 indptr.astype(np.int32)), shape=(self.n, self.n))
        return self._csr

    def targets(self, u: int) -> np.ndarray:
        """Sorted out-neighbours of u (empty for a non-subject)."""
        r = self.row[u] if 0 <= u < self.n else -1
        if r < 0:
            return self.indices[:0]
        return self.indices[self.indptr[r]: self.indptr[r + 1]]

    def has_edge(self, u: int, t: int) -> bool:
        row = self.targets(u)
        j = int(np.searchsorted(row, t))
        return j < len(row) and row[j] == t

    def without_edges(self, frac: float, rng) -> "Graph":
        """A copy with about `frac` of the edges missing and the value
        columns kept — the lossy graph the `approx` control answers from.
        Every subject keeps its row."""
        keep = rng.random(len(self.indices)) >= frac
        g = object.__new__(Graph)
        g.__dict__.update(self.__dict__)
        g._csr = None
        src = np.repeat(np.arange(len(self.subjects)), np.diff(self.indptr))
        cnt = np.bincount(src[keep], minlength=len(self.subjects))
        g.indices = self.indices[keep]
        g.indptr = np.zeros(len(self.subjects) + 1, dtype=np.int64)
        np.cumsum(cnt, out=g.indptr[1:])
        g.degree = np.zeros(self.n, dtype=np.int64)
        g.degree[self.subjects] = cnt
        return g


def bfs_tree(g: Graph, src: int, max_depth: int = 64):
    """(hop distance from src of every uid, -1 where there is no path;
    BFS parent of every uid) by scipy's breadth-first search over the
    directed `follows` edges. A C loop with node-sized outputs: a numpy
    level-set BFS allocates several edge-sized arrays a level, and some
    hundreds of them a run kept tens of GB of the machine's memory."""
    order, parent = breadth_first_order(g.csr, src, directed=True,
                                        return_predecessors=True)
    depth = np.full(g.n, -1, dtype=np.int64)
    depth[src] = 0
    rest = order[1:]
    for d in range(1, max_depth + 1):     # a tree parent is one level up
        level = rest[depth[parent[rest]] == d - 1]
        if not len(level):
            break
        depth[level] = d
        rest = rest[depth[rest] < 0]
    return depth, parent


def bfs_path(g: Graph, src: int, dst: int):
    """One shortest path src -> dst as a uid list, or None."""
    depth, parent = bfs_tree(g, src)
    if depth[dst] < 0:
        return None
    path = [dst]
    while path[-1] != src:
        path.append(int(parent[path[-1]]))
    return path[::-1]


def bfs_dist(g: Graph, src: int, dst: int):
    """(hop distance src -> dst or None, edges read, nodes visited) of a
    level-by-level BFS that stops after the level that reaches dst: it
    reads the out-edges of every node nearer than dst, and visits those
    nodes and dst."""
    if src == dst:
        return 0, 0, 1
    depth, _ = bfs_tree(g, src)
    if depth[dst] < 0:
        inside = depth >= 0
        return None, int(g.degree[inside].sum()), int(inside.sum())
    nearer = (depth >= 0) & (depth < depth[dst])
    return (int(depth[dst]), int(g.degree[nearer].sum()),
            int(nearer.sum()) + 1)


_HEX = np.frombuffer(b"0123456789abcdef", dtype=np.uint8)


def _hex_cols(vals: np.ndarray, width: int) -> np.ndarray:
    shifts = np.arange(width - 1, -1, -1, dtype=np.int64) * 4
    return _HEX[(vals[:, None] >> shifts[None, :]) & 0xF]


def write_rdf(g: Graph, path: str) -> int:
    """N-Quads for the whole graph; the uid edges are rendered as one
    fixed-width byte matrix per chunk, no per-edge Python."""
    width = max(5, (int(g.n).bit_length() + 3) // 4)
    tmpl = np.frombuffer(
        (b"<0x" + b"0" * width + b"> <follows> <0x" + b"0" * width
         + b"> .\n"), dtype=np.uint8)
    s_at, o_at = 3, 3 + width + len(b"> <follows> <0x")
    src = np.repeat(g.subjects, np.diff(g.indptr))
    with open(path, "wb") as f:
        for lo in range(0, len(src), 1 << 20):
            hi = min(lo + (1 << 20), len(src))
            buf = np.tile(tmpl, (hi - lo, 1))
            buf[:, s_at: s_at + width] = _hex_cols(src[lo:hi], width)
            buf[:, o_at: o_at + width] = _hex_cols(g.indices[lo:hi], width)
            f.write(buf.tobytes())
        f.write("".join(
            f'<0x{u:x}> <score> "{sc}"^^<xs:int> .\n'
            f'<0x{u:x}> <grp> "{gr}"^^<xs:int> .\n'
            for u, sc, gr in zip(g.subjects.tolist(),
                                 g.score[g.subjects].tolist(),
                                 g.grp[g.subjects].tolist())).encode())
    return len(src) + 2 * len(g.subjects)
