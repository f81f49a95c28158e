"""What crosses the host-device boundary for one fused `@recurse` (ISSUE
37), and how its first level is read.

In: beside the resident graph, ONE host array — the seeds as ranks
(`int32[2, S]`; `int32[max_batch, 2, S]` for a stacked launch), an argument
of the one jitted program (`ops/pallas_bfs.recurse_fused` /
`recurse_fused_multi`). Nothing else may run on the device per request — no
eager `jnp` program builds an argument — so the first request of a depth
loads one program and a request from another root none. Level 1 reads the
seeds' forward rows (out-degree sum <= FIRST_HOP_CAP: "push") or streams
every in-edge ("stream"): `dgraph_recurse_first_hop_total{mode=}` counts
each traversal under its branch, from 0 at start-up, and the request's
`device_kernel` span says which.
"""

import random
import time

import jax
import numpy as np
import pytest

from dgraph_tpu.api.http import serve_forever
from dgraph_tpu.api.server import Node
from dgraph_tpu.obs import prom
from dgraph_tpu.ops import pallas_bfs as pb
from dgraph_tpu.query import recurse as recmod
from dgraph_tpu.query.batch import DeviceBatcher
from test_khop_concurrent import at_once, parse_metrics, post, query_text

HUB = 50             # 41 out-edges
CAP = 8              # a first_hop_cap the hub is over and a ring uid under
SERIES = 'dgraph_recurse_first_hop_total{mode="%s"}'


def _edges():
    """Uids 1..40 in a ring, both directions; the hub -> every ring uid
    and itself, 40 -> the hub."""
    e = [(u, u % 40 + 1) for u in range(1, 41)]
    e += [(b, a) for a, b in e]
    e += [(HUB, u) for u in range(1, 41)] + [(HUB, HUB), (40, HUB)]
    return sorted(set(e))


@pytest.fixture(scope="module")
def served():
    """One node on the ring-and-hub graph behind HTTP, every request
    sampled, caches off, the kernel tier forced (interpret mode here) with
    a first_hop_cap of CAP: the programs and the host's counter read the
    module constant when a request is served."""
    node = Node(span_sample=1.0, trace_rng=random.Random(7),
                task_cache_mb=0, result_cache_mb=0)
    node.alter(schema_text="follows: [uid] .")
    node.mutate(set_nquads="\n".join(
        f"<0x{s:x}> <follows> <0x{d:x}> ." for s, d in _edges()),
        commit_now=True)
    srv = serve_forever(node, port=0)
    cap = pb.FIRST_HOP_CAP
    recmod.KERNEL_MIN_EDGES, pb.FIRST_HOP_CAP = 0, CAP
    try:
        yield node, f"http://127.0.0.1:{srv.server_address[1]}"
    finally:
        recmod.KERNEL_MIN_EDGES, pb.FIRST_HOP_CAP = None, cap
        srv.shutdown()
        node.close()


def ask(base: str, root: int, k: int) -> int:
    return post(base, "/query?edgeLimit=1000000",
                query_text(root, k))["data"]["khop"][0]["count"]


def want(root: int, k: int) -> int:
    """The distinct vertices a k-level recurse with edge dedup reaches:
    on this graph, stored in both directions around the ring, every
    vertex within k hops, the root too once something leads back."""
    out: dict[int, list[int]] = {}
    for a, b in _edges():
        out.setdefault(a, []).append(b)
    seen_edges, frontier, reached = set(), {root}, set()
    for _ in range(k):
        fresh = {(u, v) for u in frontier for v in out.get(u, ())}
        fresh -= seen_edges
        seen_edges |= fresh
        frontier = {v for _u, v in fresh}
        reached |= frontier
    return len(reached)


def first_hops(base: str) -> dict[str, float]:
    prom_now = parse_metrics(base)
    return {m: prom_now[SERIES % m] for m in ("push", "stream")}


def programs_loaded(base: str) -> int:
    """XLA programs compiled, or fetched from the persistent cache, so
    far (obs/devprof.py counts either once)."""
    return int(post(base, "/debug/compiles")["compiles"])


def kernel_spans(node, since: float, family: str) -> list[dict]:
    """The device_kernel spans of `family` in the query traces started
    since `since`, oldest first."""
    deadline = time.monotonic() + 10
    while node.tracer.active_traces() and time.monotonic() < deadline:
        time.sleep(0.002)      # a handler flushes after its client reads
    sink = node.tracer.sink
    recs = sorted((r for r in sink.index(4096) if r.get("root") == "query"
                   and r.get("start", since) >= since),
                  key=lambda r: r["start"])
    return [s["attrs"] for r in recs for s in sink.get(r["trace_id"])["spans"]
            if s["name"] == "device_kernel"
            and s["attrs"].get("kernel") == family]


def spy_on(monkeypatch, name: str) -> list[tuple]:
    """Every call of pb.<name> from here on: its positional arguments."""
    calls, real = [], getattr(pb, name)
    monkeypatch.setattr(pb, name,
                        lambda *a, **k: calls.append(a) or real(*a, **k))
    return calls


def host_arrays(args: tuple) -> list[np.ndarray]:
    """The arguments of one program call that are not on the device yet."""
    return [a for a in jax.tree_util.tree_leaves(args)
            if not isinstance(a, jax.Array)]


def test_both_modes_show_from_start_up_at_zero():
    node = Node()
    try:
        series = prom.parse(prom.render(node.metrics))
    finally:
        node.close()
    assert sorted((lb["mode"], v) for lb, v in
                  series["dgraph_recurse_first_hop_total"]) == \
        [("push", 0), ("stream", 0)]


def test_a_solo_request_puts_one_host_array_across(served, monkeypatch):
    """Depth 2 from three roots, one at a time: each call of the program
    takes the resident graph and ONE host array, int32[2, 1]; the first
    loads the program, the others nothing — no eager program builds an
    argument, or it would load beside it — and the counter and the span
    name the branch: the ring uids push, the hub streams."""
    node, base = served
    calls = spy_on(monkeypatch, "recurse_fused")
    before, t0 = first_hops(base), time.time()
    loaded = programs_loaded(base)
    assert ask(base, 3, 2) == want(3, 2)
    assert programs_loaded(base) == loaded + 1
    loaded += 1
    assert ask(base, 17, 2) == want(17, 2)
    assert programs_loaded(base) == loaded
    # the hub is over the cap: the same program, its other branch
    assert ask(base, HUB, 2) == want(HUB, 2)
    assert programs_loaded(base) == loaded
    assert len(calls) == 3
    for args in calls:
        (seeds,) = host_arrays(args)
        assert seeds.dtype == np.int32 and seeds.shape == (2, 1)
    after = first_hops(base)
    assert {m: after[m] - before[m] for m in after} == \
        {"push": 2, "stream": 1}
    spans = kernel_spans(node, t0, "pb.recurse_fused")
    assert [s["first_hop"] for s in spans] == ["push", "push", "stream"]


def test_a_stacked_launch_puts_one_host_array_across(served, monkeypatch):
    """Four requests in one launch, twice, the hub among them: the launch
    takes ONE host array, int32[max_batch, 2, 1], a row a member; the
    second round, from other roots, loads no program; each member counts
    its own first level and names it on its own span of the launch."""
    node, base = served
    batcher = node.batcher
    node.batcher = DeviceBatcher(node.dispatch_gate, node.metrics,
                                 window_ms=1500, max_batch=4,
                                 idle_fire=False)
    calls = spy_on(monkeypatch, "recurse_fused_multi")

    def round_of(roots):
        assert at_once([lambda r=r: ask(base, r, 1) for r in roots]) == \
            [want(r, 1) for r in roots]

    try:
        round_of([5, 9, 21, 33])
        before, t0 = first_hops(base), time.time()
        loaded = programs_loaded(base)
        round_of([7, HUB, 12, 29])
        assert programs_loaded(base) == loaded
    finally:
        node.batcher = batcher
    assert len(calls) == 2
    for args in calls:
        (seeds,) = host_arrays(args)
        assert seeds.dtype == np.int32 and seeds.shape == (4, 2, 1)
    after = first_hops(base)
    assert {m: after[m] - before[m] for m in after} == \
        {"push": 3, "stream": 1}
    spans = kernel_spans(node, t0, "batch.recurse")
    assert sorted(s["role"] for s in spans) == ["follower"] * 3 + ["leader"]
    assert sorted(s["first_hop"] for s in spans) == ["push"] * 3 + ["stream"]
    assert {s["batch"] for s in spans} == {4}


def test_an_edge_inside_the_pad_block_moves_no_shape():
    """A write from an existing subject to an existing destination that
    keeps the edge count inside its block: every argument of the fused
    programs keeps its shape, so the compiled programs (and the persistent
    cache's entries: chip_smoke.py's restart leans on it) still fit. The
    forward rows are read from the padded array for that."""
    def layout(edges):
        src, dst = (np.asarray(c, dtype=np.int64) for c in zip(*edges))
        subjects, counts = np.unique(src, return_counts=True)
        indptr = np.zeros(len(subjects) + 1, dtype=np.int64)
        np.cumsum(counts, out=indptr[1:])
        return pb.prep_pull(subjects, indptr, dst, 64)

    before = layout(_edges())
    after = layout(sorted(_edges() + [(3, 7)]))
    assert after.num_edges == before.num_edges + 1
    shapes = [jax.tree_util.tree_map(lambda a: (a.shape, a.dtype),
                                     pb.fused_graph_args(g))
              for g in (before, after)]
    assert shapes[0] == shapes[1]
    assert after.fwd_dst_rank.shape != before.fwd_dst_rank.shape
    np.testing.assert_array_equal(
        np.asarray(after.fwd_dst_pad)[: after.num_edges],
        np.asarray(after.fwd_dst_rank))
