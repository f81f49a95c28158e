"""shortest(from, to) over `follows`: one breadth-first search from a root.

The root is a Graph500 search key: uniform over the vertices with at least
one edge (self-loops are not in the graph). DQL has no answer that holds a
whole BFS tree, so the search is asked for one target, drawn like the
root; a pair in two components stays in (the answer is "no path")."""

from harness.graph import bfs_dist, bfs_path


def draw(ctx, rng) -> dict:
    s = ctx.g.subjects
    return {"src": int(s[rng.integers(len(s))]),
            "dst": int(s[rng.integers(len(s))])}


def request(p: dict, ctx):
    q = (f"{{ sp as shortest(from: {hex(p['src'])}, to: {hex(p['dst'])}) "
         f"{{ follows }} sp_out(func: uid(sp)) {{ uid }} }}")
    return "POST", f"/query?edgeLimit={ctx.edge_limit}", q


def parse(data: dict):
    """{"path": [uids] | None, "weight", "uids": sorted uid(var) block}."""
    paths = data.get("_path_", [])
    if not paths:
        return {"path": None}
    if len(paths) != 1:
        return {"path": "many"}
    node, path = paths[0], []
    weight = node.get("_weight_")
    while True:
        path.append(int(node["uid"], 16))
        nxt = node.get("follows")
        if not nxt:
            break
        node = nxt[0]
    return {"path": path, "weight": weight,
            "uids": sorted(int(r["uid"], 16)
                           for r in data.get("sp_out", []))}


def answer(g, p: dict):
    path = bfs_path(g, p["src"], p["dst"])
    if path is None:
        return {"path": None}
    return {"path": path, "weight": float(len(path) - 1),
            "uids": sorted(set(path))}


def verify(g, p: dict, got):
    """A path is right when it is a path of the graph and as long as the
    BFS distance. Returns (problem | None, {"edges", "nodes"} the plain
    BFS read and visited)."""
    src, dst = p["src"], p["dst"]
    dist, edges, nodes = bfs_dist(g, src, dst)
    stats = {"edges": edges, "nodes": nodes}
    path = got.get("path")
    if path is None:
        return (None if dist is None else
                f"no path returned, BFS distance {dist}"), stats
    if path == "many":
        return "more than one path returned", stats
    if dist is None:
        return "path returned, none exists", stats
    if path[0] != src or path[-1] != dst:
        return f"endpoints {path[0]:#x}..{path[-1]:#x}", stats
    hops = len(path) - 1
    if got.get("weight") != float(hops):
        return f"_weight_ {got.get('weight')} on a {hops}-hop path", stats
    if hops != dist:
        return f"length {hops} vs BFS distance {dist}", stats
    for u, t in zip(path, path[1:]):
        if not g.has_edge(u, t):
            return f"{u:#x}->{t:#x} is not an edge", stats
    if got.get("uids") != sorted(set(path)):
        return "uid(var) block differs from the path", stats
    return None, stats
