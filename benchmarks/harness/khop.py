"""The k-hop neighbour count as the four `khop<k>` ops share it: from a
root, how many distinct vertices does a k-level traversal over `follows`
reach?

    { var(func: uid(<root>)) @recurse(depth: <k>) { v as follows }
      khop(func: uid(v)) { count(uid) } }

What DQL's `@recurse(depth: k)` reaches, with its edge dedup, on a graph
stored in both directions (the one generator makes no other): every
vertex within k hops of the root, plus the root itself from k = 2 on — it
comes back over the reverse edge of its first hop. The reference here is
the benchmark's own: scipy's breadth-first search over the benchmark's
own CSR (harness/graph.py bfs_tree) and that rule; it imports nothing of
the program. benchmarks/tests/test_khop.py holds it to the program's
level-by-level reference (dgraph_tpu/models/khop.py).

An op file binds its k: `draw = draw_for(k)`; the rest reads `p["k"]`."""

from harness.graph import bfs_tree


def draw_for(k: int):
    def draw(ctx, rng) -> dict:
        """Root uniform over the vertices with at least one edge, as
        `shortest` draws its search keys."""
        s = ctx.g.subjects
        return {"root": int(s[rng.integers(len(s))]), "k": k}
    return draw


def request(p: dict, ctx):
    q = (f"{{ var(func: uid({hex(p['root'])})) @recurse(depth: {p['k']}) "
         f"{{ v as follows }} khop(func: uid(v)) {{ count(uid) }} }}")
    return "POST", f"/query?edgeLimit={ctx.edge_limit}", q


def parse(data: dict):
    rows = data.get("khop", [])
    if len(rows) != 1 or not isinstance(rows[0].get("count"), int):
        return {"count": None}
    return {"count": rows[0]["count"]}


def reach(g, p: dict):
    """(the count, edges the plain BFS reads, nodes it visits): it reads
    the out-edges of every vertex nearer than k and visits every vertex
    within k."""
    root, k = p["root"], p["k"]
    depth, _ = bfs_tree(g, root, k)
    inside = depth >= 0
    count = int(inside.sum()) - 1 + int(k >= 2 and g.degree[root] > 0)
    return (count, int(g.degree[inside & (depth < k)].sum()),
            int(inside.sum()))


def answer(g, p: dict):
    return {"count": reach(g, p)[0]}


def verify(g, p: dict, got):
    """Exact equality. Returns (problem | None, {"edges", "nodes"})."""
    count, edges, nodes = reach(g, p)
    stats = {"edges": edges, "nodes": nodes}
    if got.get("count") != count:
        return f"count {got.get('count')}, the reference counts {count}", \
            stats
    return None, stats
