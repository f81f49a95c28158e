"""The Pallas kernel IS the production @recurse path (VERDICT r4 #1).

Forcing KERNEL_MIN_EDGES=0 routes DQL @recurse through
ops/pallas_bfs.recurse_fused / recurse_step (interpret mode on the CPU test
mesh — the same program Mosaic compiles on TPU) and the full JSON output
must be identical to the host-mirror path for every query shape: fused
single-child, multi-child stepped, value children, filters, loops, reverse
edges, depth exhaustion, and the edge budget error.
"""

import json

import numpy as np
import pytest

from dgraph_tpu.api.server import Node
from dgraph_tpu.query import recurse as recmod


def _graph_node(rng, n=48):
    node = Node()
    node.alter(schema_text="name: string .\nfollow: uid @reverse .\n"
                           "knows: uid .")
    quads = [f'<0x{u:x}> <name> "p{u}" .' for u in range(1, n + 1)]
    for _ in range(n * 3):
        a, b = int(rng.integers(1, n + 1)), int(rng.integers(1, n + 1))
        if a != b:
            quads.append(f"<0x{a:x}> <follow> <0x{b:x}> .")
    for _ in range(n * 2):
        a, b = int(rng.integers(1, n + 1)), int(rng.integers(1, n + 1))
        if a != b:
            quads.append(f"<0x{a:x}> <knows> <0x{b:x}> .")
    node.mutate(set_nquads="\n".join(quads), commit_now=True)
    return node


QUERIES = [
    # fused shape: single uid child, no filter
    "{ q(func: uid(0x1, 0x2)) @recurse(depth: 3) { follow } }",
    "{ q(func: uid(0x1)) @recurse(depth: 4, loop: true) { follow } }",
    # stepped: two uid children
    "{ q(func: uid(0x1, 0x3)) @recurse(depth: 3) { follow knows } }",
    # stepped: value child at every level
    "{ q(func: uid(0x2)) @recurse(depth: 3) { name follow } }",
    # filter on the uid child
    "{ q(func: uid(0x1)) @recurse(depth: 3) "
    "{ follow @filter(uid(0x2, 0x4, 0x6, 0x8, 0xa)) } }",
    # reverse edge
    "{ q(func: uid(0x5)) @recurse(depth: 2) { ~follow } }",
    # until exhaustion (stepped: depth cap 64 exceeds FUSED_MAX_DEPTH)
    "{ q(func: uid(0x1)) @recurse { follow } }",
]


def _canon(out) -> str:
    return json.dumps(out, sort_keys=True, default=str)


@pytest.mark.parametrize("qidx", range(len(QUERIES)))
def test_recurse_kernel_matches_host(rng, qidx):
    node = _graph_node(rng)
    q = QUERIES[qidx]
    host_out, _ = node.query(q)
    recmod.KERNEL_MIN_EDGES = 0
    try:
        kern_out, _ = node.query(q)
    finally:
        recmod.KERNEL_MIN_EDGES = None
    assert _canon(host_out) == _canon(kern_out)


def test_fused_path_taken(rng, monkeypatch):
    """The single-child no-filter shape must run ONE fused dispatch."""
    node = _graph_node(rng)
    from dgraph_tpu.ops import pallas_bfs as pb

    calls = {"fused": 0, "step": 0}
    real_fused, real_step = pb.recurse_fused, pb.recurse_step
    monkeypatch.setattr(pb, "recurse_fused", lambda *a, **k: (
        calls.__setitem__("fused", calls["fused"] + 1) or real_fused(*a, **k)))
    monkeypatch.setattr(pb, "recurse_step", lambda *a, **k: (
        calls.__setitem__("step", calls["step"] + 1) or real_step(*a, **k)))
    recmod.KERNEL_MIN_EDGES = 0
    try:
        node.query("{ q(func: uid(0x1, 0x2)) @recurse(depth: 3) { follow } }")
        assert calls == {"fused": 1, "step": 0}
        node.query("{ q(func: uid(0x1)) @recurse(depth: 3) { follow knows } }")
        assert calls["fused"] == 1 and calls["step"] > 0
    finally:
        recmod.KERNEL_MIN_EDGES = None


def test_kernel_steps_fence_inside_the_gate_slot(rng, monkeypatch):
    """Dispatch is asynchronous on an accelerator: the fetch is the fence.
    Both recurse kernel paths hand the gate a closure that returns HOST
    arrays, so the cost timer's device_ms and the gate's "recurse" step
    estimate cover the device step and not only its launch."""
    node = _graph_node(rng)
    gate = node.dispatch_gate
    real, outs = gate.run, []

    def spy(fn, klass=None):
        out = real(fn, klass=klass)
        if klass == "recurse":
            outs.append(out)
        return out

    monkeypatch.setattr(gate, "run", spy)
    recmod.KERNEL_MIN_EDGES = 0
    try:
        node.query("{ q(func: uid(0x1, 0x2)) @recurse(depth: 3) { follow } }")
        assert len(outs) == 1                       # fused: one dispatch
        masks_h, trav_h = outs.pop()
        assert isinstance(masks_h, np.ndarray)
        assert isinstance(trav_h, np.ndarray)
        node.query("{ q(func: uid(0x1)) @recurse(depth: 3) { follow knows } }")
        assert outs                                 # stepped: one per level
        for (dest_words_h, trav_h), _expanded in outs:
            assert isinstance(dest_words_h, np.ndarray)
            assert isinstance(trav_h, np.ndarray)
        assert gate.expected_step("recurse") > 0
    finally:
        recmod.KERNEL_MIN_EDGES = None


def test_kernel_edge_budget(rng):
    """The budget error must fire on the kernel path too (recurse.go:167)."""
    from dgraph_tpu.query import engine as eng

    node = _graph_node(rng)
    recmod.KERNEL_MIN_EDGES = 0
    old = eng.MAX_QUERY_EDGES
    eng.set_query_edge_limit(5)
    try:
        with pytest.raises(Exception, match="ErrTooBig|edge budget"):
            node.query("{ q(func: uid(0x1, 0x2)) @recurse(depth: 3) "
                       "{ follow } }")
    finally:
        eng.set_query_edge_limit(old)
        recmod.KERNEL_MIN_EDGES = None


def test_shortest_kernel_bfs_matches_host(rng, monkeypatch):
    """Large-CSR shortest runs the Pallas bfs_dist kernel; cost must equal
    the host Dijkstra and the path must be a real edge path."""
    from dgraph_tpu.query import shortest as sh

    node = _graph_node(rng, n=60)
    # this test probes WHICH execution path runs (host Dijkstra vs Pallas
    # kernel) by replaying identical queries after flipping module floors;
    # the whole-query result cache would legitimately serve the replay
    # without executing anything, so opt out of that tier here
    node.result_cache = None
    # pick reachable pairs from the host path first
    monkeypatch.setattr(sh, "DEVICE_SSSP_MIN_EDGES", 1 << 62)  # host Dijkstra
    pairs = []
    for dst in range(2, 40):
        out, _ = node.query(
            f"{{ p as shortest(from: 0x1, to: 0x{dst:x}) {{ follow }} "
            f"  r(func: uid(p)) {{ uid }} }}")
        if out.get("_path_"):
            pairs.append((dst, out["_path_"][0]["_weight_"]))
    assert pairs, "no reachable pairs in random graph"

    monkeypatch.setattr(sh, "SSSP_KERNEL_MIN", 0)
    monkeypatch.setattr(sh, "DEVICE_SSSP_MIN_EDGES", 0)
    from dgraph_tpu.ops import pallas_bfs as pb

    calls = []
    real = pb.shortest_bfs
    monkeypatch.setattr(pb, "shortest_bfs",
                        lambda *a, **k: calls.append(1) or real(*a, **k))
    for dst, want_cost in pairs[:6]:
        out, _ = node.query(
            f"{{ p as shortest(from: 0x1, to: 0x{dst:x}) {{ follow }} "
            f"  r(func: uid(p)) {{ uid }} }}")
        assert out["_path_"], f"kernel path missed dst 0x{dst:x}"
        assert out["_path_"][0]["_weight_"] == want_cost
        # validate the path is a real edge chain
        uids = []
        nodep = out["_path_"][0]
        while True:
            uids.append(int(nodep["uid"], 16))
            nxt = nodep.get("follow")
            if not nxt:
                break
            nodep = nxt[0]
        assert uids[0] == 0x1 and uids[-1] == dst
    assert calls, "kernel shortest_bfs was not used"


def test_shortest_kernel_unreachable(rng, monkeypatch):
    from dgraph_tpu.query import shortest as sh

    node = Node()
    node.alter(schema_text="follow: uid .")
    node.mutate(set_nquads="<0x1> <follow> <0x2> .\n<0x3> <follow> <0x4> .",
                commit_now=True)
    monkeypatch.setattr(sh, "SSSP_KERNEL_MIN", 0)
    monkeypatch.setattr(sh, "DEVICE_SSSP_MIN_EDGES", 0)
    out, _ = node.query("{ p as shortest(from: 0x1, to: 0x4) { follow } "
                        "  r(func: uid(p)) { uid } }")
    assert not out.get("_path_")


def test_set_query_edge_limit_bounds_shortest(rng):
    """Behavioral guard for the single-binding refactor: the setter must
    bound the shortest-path expansion too (a by-value re-import in
    shortest.py would silently escape it)."""
    from dgraph_tpu.query import engine as eng

    node = _graph_node(rng)
    old = eng.MAX_QUERY_EDGES
    eng.set_query_edge_limit(2)
    try:
        with pytest.raises(Exception, match="ErrTooBig|edge budget"):
            node.query("{ p as shortest(from: 0x1, to: 0x2f, numpaths: 2) "
                       "{ follow } r(func: uid(p)) { uid } }")
    finally:
        eng.set_query_edge_limit(old)
