"""Device SSSP vs networkx-free host ground truth."""

import numpy as np
import jax.numpy as jnp
import pytest

from dgraph_tpu.ops import traversal


def make_graph(rng, n_nodes, n_edges, weighted=False):
    edges = {(int(a), int(b)) for a, b in rng.integers(0, n_nodes, size=(n_edges, 2))
             if a != b}
    edges = sorted(edges)
    subjects = sorted({a for a, _ in edges})
    sub_idx = {s: i for i, s in enumerate(subjects)}
    indptr = np.zeros(len(subjects) + 1, dtype=np.int32)
    for a, _ in edges:
        indptr[sub_idx[a] + 1] += 1
    np.cumsum(indptr, out=indptr)
    indices = np.asarray([b for _, b in edges], dtype=np.int32)
    w = None
    if weighted:
        w = rng.uniform(0.1, 5.0, size=len(edges)).astype(np.float32)
    return (np.asarray(subjects, dtype=np.int32), indptr, indices, w,
            {(a, b): i for i, (a, b) in enumerate(edges)})


def host_dijkstra(edges_map, w, src, n):
    import heapq

    adj = {}
    for (a, b), i in edges_map.items():
        adj.setdefault(a, []).append((b, float(w[i]) if w is not None else 1.0))
    dist = {src: 0.0}
    pq = [(0.0, src)]
    while pq:
        d, u = heapq.heappop(pq)
        if d > dist.get(u, np.inf):
            continue
        for v, c in adj.get(u, ()):
            if d + c < dist.get(v, np.inf):
                dist[v] = d + c
                heapq.heappush(pq, (d + c, v))
    out = np.full(n, np.inf, dtype=np.float32)
    for u, d in dist.items():
        out[u] = d
    return out


@pytest.mark.parametrize("weighted", [False, True])
def test_sssp_vs_dijkstra(rng, weighted):
    subjects, indptr, indices, w, emap = make_graph(rng, 200, 1000, weighted)
    res = traversal.sssp(jnp.asarray(subjects), jnp.asarray(indptr),
                         jnp.asarray(indices),
                         jnp.asarray(w) if w is not None else None,
                         jnp.int32(0), num_nodes=200, max_iters=64)
    want = host_dijkstra(emap, w, 0, 200)
    np.testing.assert_allclose(np.asarray(res.dist), want, rtol=1e-5)
    # parent consistency: dist[u] == dist[parent[u]] + w(parent[u] -> u)
    dist = np.asarray(res.dist)
    parent = np.asarray(res.parent)
    for u in range(200):
        p = parent[u]
        if p < 0:
            continue
        cost = float(w[emap[(int(p), u)]]) if w is not None else 1.0
        assert dist[u] == pytest.approx(dist[p] + cost, rel=1e-5)
