"""The k-hop neighbourhood count: a uid variable on a child of `@recurse`.

    { var(func: uid(r)) @recurse(depth: k) { v as follows }
      khop(func: uid(v)) { count(uid) } }

Every tier of query/recurse.py — host mirror, `pb.recurse_fused` and
`pb.recurse_step` (KERNEL_MIN_EDGES = 0, interpret mode here), the mesh
program on four virtual devices, tablet-routed expands over the wire —
records the variable through one helper and is held to the plain
reference, dgraph_tpu/models/khop.py, on seeded random graphs."""

import json
import urllib.request

import numpy as np
import pytest

from dgraph_tpu.api.http import serve_forever
from dgraph_tpu.api.server import Node
from dgraph_tpu.models.khop import khop_levels, within_hops
from dgraph_tpu.parallel.mesh import make_mesh
from dgraph_tpu.parallel.worker import distribute_snapshot
from dgraph_tpu.query import dql
from dgraph_tpu.query import recurse as recmod
from dgraph_tpu.query.engine import Executor

N = 40                   # the random part's vertices: uids 1..N
SINK = 41                # reached by an edge, has no out-edge (directed)
PAIR = (42, 43)          # a component of two vertices
LOOPED = (44, 45)        # 44 -> 44 and 44 -> 45
UNKNOWN = 60             # a uid the store never saw


def edges_of(kind: str, seed: int) -> np.ndarray:
    """int64 [E, 2] (src, dst), duplicates dropped; `both` stores every
    edge of the random part and of the pair in both directions."""
    rng = np.random.default_rng([seed, 17])
    a = rng.integers(1, N + 1, size=3 * N)
    b = rng.integers(1, N + 1, size=3 * N)
    e = np.stack([a, b], axis=1)[a != b]
    e = np.concatenate([e, [[1, SINK], list(PAIR)]])
    if kind == "both":
        e = np.concatenate([e, e[:, ::-1]])
    e = np.concatenate([e, [[LOOPED[0], LOOPED[0]], list(LOOPED)]])
    return np.unique(e, axis=0).astype(np.int64)


def loaded(node: Node, edges: np.ndarray) -> Node:
    node.alter(schema_text="follows: [uid] .")
    node.mutate(set_nquads="\n".join(
        f"<0x{s:x}> <follows> <0x{d:x}> ." for s, d in edges.tolist()),
        commit_now=True)
    # a cached answer of one tier must not stand in for the next tier's
    node.plan_cache = node.task_cache = node.result_cache = None
    return node


@pytest.fixture(scope="module", params=["directed", "both"])
def world(request):
    edges = edges_of(request.param, 5)
    plain = loaded(Node(), edges)
    mesh = loaded(Node(mesh_devices=4, mesh_min_edges=1), edges)
    wire = distribute_snapshot(plain.snapshot(), make_mesh(4), plain.zero)
    return {"kind": request.param, "edges": edges, "plain": plain,
            "mesh": mesh, "wire": wire}


def query_text(roots, k: int, loop: bool = False) -> str:
    uids = ", ".join(hex(r) for r in roots)
    return (f"{{ var(func: uid({uids})) @recurse(depth: {k}, "
            f"loop: {'true' if loop else 'false'}) {{ v as follows }} "
            f"khop(func: uid(v)) {{ count(uid) }} "
            f"all(func: uid(v)) {{ uid }} "
            f"low(func: uid(0x1, 0x2, 0x3, 0x4, 0x5, 0x6, 0x7, 0x8)) "
            f"@filter(uid(v)) {{ uid }} }}")


def ask(world, tier: str, q: str) -> dict:
    if tier == "wire":
        return Executor(world["wire"], world["plain"].store.schema).execute(
            dql.parse(q))
    node = world["mesh" if tier == "mesh" else "plain"]
    recmod.KERNEL_MIN_EDGES = 0 if tier == "kernel" else None
    try:
        return node.query(q)[0]
    finally:
        recmod.KERNEL_MIN_EDGES = None


def uids_in(block) -> list[int]:
    return sorted(int(r["uid"], 16) for r in block)


# (roots, depth, loop): depths up to and past FUSED_MAX_DEPTH (past it the
# kernel tier steps level by level and the mesh tier falls back)
CASES = {
    "k1": ([3], 1, False), "k2": ([3], 2, False), "k3": ([3], 3, False),
    "k6": ([3], 6, False), "k8": ([3], 8, False), "k10": ([3], 10, False),
    "no-out-edge": ([SINK], 3, False), "unknown-root": ([UNKNOWN], 2, False),
    "two-vertex-component": ([PAIR[0]], 3, False),
    "self-loop": ([LOOPED[0]], 2, False),
    "loop-true": ([3], 3, True), "two-roots": ([3, 7], 2, False),
}
assert CASES["k10"][1] > recmod.FUSED_MAX_DEPTH >= CASES["k8"][1]


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("tier", ["host", "kernel", "mesh", "wire"])
def test_variable_is_the_union_of_the_levels(world, tier, case):
    roots, k, loop = CASES[case]
    edges = world["edges"]
    known = [r for r in roots if r != UNKNOWN]
    _, want = khop_levels(edges[:, 0], edges[:, 1], known, k, loop)
    out = ask(world, tier, query_text(roots, k, loop))
    assert out["khop"] == [{"count": len(want)}]
    assert uids_in(out.get("all", [])) == want.tolist()
    assert uids_in(out.get("low", [])) == [u for u in want.tolist() if u <= 8]


@pytest.mark.parametrize("k", [1, 2, 3, 6])
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_edge_dedup_union_is_the_k_hop_ball_plus_the_root(seed, k):
    """The two definitions, tied: on a graph stored in both directions the
    union of the fresh edges' destinations over k levels is every vertex
    within k hops, and the root itself from k = 2 on."""
    edges = edges_of("both", seed)
    src, dst = edges[:, 0], edges[:, 1]
    for root in (1, 5, PAIR[0], UNKNOWN):
        _, union = khop_levels(src, dst, [root], k)
        ball = within_hops(src, dst, root, k)
        back = int(k >= 2 and (src == root).any())
        assert len(union) == len(ball) + back
        assert sorted(set(union.tolist()) - {root}) == ball.tolist()


def test_reference_levels_dedup_edges_not_vertices():
    # 1 -> 2 -> 3 -> 1, and 3 -> 4: level 3 comes back to 1; level 4 finds
    # 1 -> 2 already expanded, so the traversal ends there
    src, dst = np.array([1, 2, 3, 3]), np.array([2, 3, 1, 4])
    levels, union = khop_levels(src, dst, [1], 6)
    assert [lv.tolist() for lv in levels] == [[2], [3], [1, 4], []]
    assert union.tolist() == [1, 2, 3, 4]
    levels, _ = khop_levels(src, dst, [1], 5, loop=True)
    assert [lv.tolist() for lv in levels] == [[2], [3], [1, 4], [2], [3]]


def _series(node: Node, name: str, key: str | None = None) -> int:
    if key is None:
        return node.metrics.counter(name).value
    return node.metrics.keyed(name).get(key)


def test_a_var_block_materialises_no_matrix_and_counts_its_levels(world):
    """pb.recurse_fused under a `var` block: the variable comes from one OR
    over the fetched level masks — no uid matrix, no fresh-flag fetch —
    and the scan's levels are counted by whether their frontier was
    empty. The same traversal rendered materialises one matrix a level."""
    node = world["plain"]
    mat = "dgraph_recurse_materialized_total"
    lv = "dgraph_recurse_levels_total"
    before = (_series(node, mat), _series(node, lv, "live"),
              _series(node, lv, "empty"))
    ask(world, "kernel", query_text([PAIR[0]], 6))
    # a two-vertex component: 42 -> 43 (-> 42 where both directions are
    # stored, and nothing after it)
    live = 3 if world["kind"] == "both" else 2
    assert _series(node, mat) == before[0]
    assert _series(node, lv, "live") == before[1] + live
    assert _series(node, lv, "empty") == before[2] + 6 - live
    ask(world, "kernel", f"{{ q(func: uid({hex(PAIR[0])})) "
                         f"@recurse(depth: 6) {{ follows }} }}")
    assert _series(node, mat) == before[0] + live
    # past the fused scan's depth every stepped level is a live one
    ask(world, "kernel", query_text([PAIR[0]], 10))
    assert _series(node, lv, "live") == before[1] + 3 * live
    assert _series(node, lv, "empty") == before[2] + 2 * (6 - live)


def test_each_tier_is_the_one_it_says(world, monkeypatch):
    from dgraph_tpu.ops import pallas_bfs as pb

    calls = {"fused": 0, "step": 0}
    for name in calls:
        real = getattr(pb, "recurse_" + name)
        monkeypatch.setattr(
            pb, "recurse_" + name,
            lambda *a, _n=name, _r=real, **k: (
                calls.__setitem__(_n, calls[_n] + 1) or _r(*a, **k)))
    ask(world, "host", query_text([3], 3))
    assert calls == {"fused": 0, "step": 0}
    ask(world, "kernel", query_text([3], 3))
    assert calls == {"fused": 1, "step": 0}
    ask(world, "kernel", query_text([3], 10))
    assert calls["fused"] == 1 and calls["step"] > 0
    mesh = world["mesh"]
    n0 = mesh.metrics.counter("dgraph_mesh_dispatches_total").value
    ask(world, "mesh", query_text([3], 3))
    assert mesh.metrics.counter("dgraph_mesh_dispatches_total").value == n0 + 1
    assert calls["fused"] == 1


def test_rendered_block_and_variable_together(world):
    """A block that renders and names the variable: the SubGraph chain and
    the union come from the same levels."""
    q = ("{ q(func: uid(0x3)) @recurse(depth: 3) { v as follows } "
         "n(func: uid(v)) { count(uid) } }")
    edges = world["edges"]
    _, want = khop_levels(edges[:, 0], edges[:, 1], [3], 3)
    host = ask(world, "host", q)
    assert host["n"] == [{"count": len(want)}]
    for tier in ("kernel", "mesh", "wire"):
        assert json.dumps(ask(world, tier, q), sort_keys=True) == \
            json.dumps(host, sort_keys=True)


def test_two_uid_children_each_name_a_variable():
    """Sibling predicates dedup in depth-first order (build_level): each
    child's variable is the union of that child's own levels, the same on
    the host mirror and on the stepped kernel."""
    node = Node()
    node.alter(schema_text="follows: [uid] .\nknows: [uid] .")
    rng = np.random.default_rng(9)
    quads = []
    for attr in ("follows", "knows"):
        for a, b in rng.integers(1, 25, size=(60, 2)).tolist():
            quads.append(f"<0x{a:x}> <{attr}> <0x{b:x}> .")
    node.mutate(set_nquads="\n".join(quads), commit_now=True)
    node.plan_cache = node.task_cache = node.result_cache = None
    q = ("{ var(func: uid(0x2)) @recurse(depth: 3) "
         "{ f as follows k as knows } "
         "f(func: uid(f)) { uid } k(func: uid(k)) { uid } }")
    host = node.query(q)[0]
    assert host["f"] and host["k"] and host["f"] != host["k"]
    recmod.KERNEL_MIN_EDGES = 0
    try:
        assert node.query(q)[0] == host
    finally:
        recmod.KERNEL_MIN_EDGES = None


@pytest.fixture(scope="module")
def served(world):
    srvs = {name: serve_forever(world[name], port=0)
            for name in ("plain", "mesh")}
    yield {name: f"http://127.0.0.1:{s.server_address[1]}"
           for name, s in srvs.items()}
    for s in srvs.values():
        s.shutdown()


def _post(base: str, q: str) -> dict:
    req = urllib.request.Request(base + "/query?edgeLimit=1000000",
                                 data=q.encode(), method="POST")
    with urllib.request.urlopen(req, timeout=120) as r:
        return json.loads(r.read())["data"]


@pytest.mark.parametrize("tier,k", [("host", 3), ("kernel", 3),
                                    ("kernel", 10), ("mesh", 3)])
def test_over_http(world, served, tier, k):
    edges = world["edges"]
    _, want = khop_levels(edges[:, 0], edges[:, 1], [3], k)
    q = (f"{{ var(func: uid(0x3)) @recurse(depth: {k}) {{ v as follows }} "
         f"khop(func: uid(v)) {{ count(uid) }} }}")
    recmod.KERNEL_MIN_EDGES = 0 if tier == "kernel" else None
    try:
        got = _post(served["mesh" if tier == "mesh" else "plain"], q)
    finally:
        recmod.KERNEL_MIN_EDGES = None
    assert got == {"khop": [{"count": len(want)}]}


@pytest.mark.parametrize("tier", ["host", "kernel", "mesh"])
def test_traversed_edges_reach_the_cost_ledger(world, tier):
    """A recurse expanded here books its levels' edges as a dispatched
    task books its own: the request's record says what it traversed (and
    is not dropped from /debug/top as one that ran nothing)."""
    edges = world["edges"]
    seen = np.zeros(len(edges), dtype=bool)
    frontier, want = np.asarray([3]), 0
    for _ in range(3):       # a level reads every out-edge of its frontier
        reads = np.isin(edges[:, 0], frontier)
        want += int(reads.sum())
        frontier = np.unique(edges[reads & ~seen, 1])
        seen |= reads
    node = world["mesh" if tier == "mesh" else "plain"]
    ask(world, tier, query_text([3], 3))
    assert node.cost_book.last()["total"]["edges"] == want


@pytest.mark.parametrize("tier", ["host", "kernel", "mesh", "wire"])
def test_the_reading_block_may_come_first(world, tier):
    """Dependency waves, not block order: a block that reads the variable
    waits for the recurse block whose child defines it."""
    edges = world["edges"]
    _, want = khop_levels(edges[:, 0], edges[:, 1], [3], 2)
    q = ("{ khop(func: uid(v)) { count(uid) } "
         "var(func: uid(0x3)) @recurse(depth: 2) { v as follows } }")
    assert ask(world, tier, q) == {"khop": [{"count": len(want)}]}


def test_a_count_of_the_variable_builds_no_object_a_uid(world, monkeypatch):
    """`khop(func: uid(v)) { count(uid) }` over a large variable: the
    encoder has one object to build, not one (empty) a uid."""
    from dgraph_tpu.query import outputnode

    calls = []
    real = outputnode.pre_traverse
    monkeypatch.setattr(outputnode, "pre_traverse",
                        lambda *a, **k: calls.append(1) or real(*a, **k))
    edges = world["edges"]
    _, want = khop_levels(edges[:, 0], edges[:, 1], [3], 6)
    q = ("{ var(func: uid(0x3)) @recurse(depth: 6) { v as follows } "
         "khop(func: uid(v)) { count(uid) } }")
    assert ask(world, "host", q) == {"khop": [{"count": len(want)}]}
    assert not calls
    out = ask(world, "host", q.replace("count(uid) }", "count(uid) uid }"))
    assert len(calls) == len(want) == len(out["khop"]) - 1
