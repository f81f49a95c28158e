"""The k-hop count from many clients at once: the batcher's stacked
`pb.recurse_fused_multi` launch, its gate and its demultiplexing.

    { var(func: uid(r)) @recurse(depth: 1) { v as follows }
      khop(func: uid(v)) { count(uid) } }

Twenty-two threads (RedisGraph's parallel-requests test) over HTTP against
one in-process node on a scale-10 Kronecker graph stored in both
directions, the kernel tier forced (KERNEL_MIN_EDGES = 0: interpret mode
here). Every request must get the count of ITS OWN root, as the plain
reference gives it (dgraph_tpu/models/khop.py), whatever launch it rode
in; every occupancy hands each member the solo program's arrays bit for
bit; a second round loads no program; the stage clock still tiles a
request with `batch.wait` in it; a follower's trace names the launch."""

import json
import random
import threading
import time
import urllib.request

import jax
import numpy as np
import pytest

from dgraph_tpu.api.http import serve_forever
from dgraph_tpu.api.server import Node
from dgraph_tpu.models.khop import khop_levels
from dgraph_tpu.models.rmat import rmat_edges
from dgraph_tpu.ops import pallas_bfs as pb
from dgraph_tpu.query import recurse as recmod
from dgraph_tpu.query.batch import DeviceBatcher
from dgraph_tpu.utils.metrics import Registry

CLIENTS = 22
SCALE = 10
SINK = (1 << SCALE) + 7       # reached by one edge, has no out-edge


def query_text(root: int, k: int = 1) -> str:
    return (f"{{ var(func: uid({hex(root)})) @recurse(depth: {k}) "
            f"{{ v as follows }} khop(func: uid(v)) {{ count(uid) }} }}")


@pytest.fixture(scope="module")
def world():
    e = rmat_edges(SCALE, 16, seed=7) + 1
    e = np.unique(np.concatenate([e, e[:, ::-1]]), axis=0)
    hub = int(np.bincount(e[:, 0]).argmax())
    edges = np.concatenate([e, [[hub, SINK]]]).astype(np.int64)
    node = Node(span_sample=1.0, trace_rng=random.Random(5),
                task_cache_mb=0, result_cache_mb=0)
    node.alter(schema_text="follows: [uid] .")
    node.mutate(set_nquads="\n".join(
        f"<0x{s:x}> <follows> <0x{d:x}> ." for s, d in edges.tolist()),
        commit_now=True)
    default_batcher = node.batcher
    srv = serve_forever(node, port=0)
    recmod.KERNEL_MIN_EDGES = 0
    try:
        yield {"node": node, "edges": edges,
               "default_batcher": default_batcher,
               "base": f"http://127.0.0.1:{srv.server_address[1]}"}
    finally:
        recmod.KERNEL_MIN_EDGES = None
        srv.shutdown()
        node.close()


def want_count(world, root: int, k: int = 1) -> int:
    edges = world["edges"]
    return len(khop_levels(edges[:, 0], edges[:, 1], [root], k)[1])


def post(base: str, path: str, body: str | None = None):
    req = urllib.request.Request(
        base + path, data=None if body is None else body.encode(),
        method="GET" if body is None else "POST")
    with urllib.request.urlopen(req, timeout=120) as r:
        return json.loads(r.read())


def at_once(fns):
    """Run the closures from one barrier; their results in order."""
    out = [None] * len(fns)
    barrier = threading.Barrier(len(fns))

    def run(i):
        barrier.wait(timeout=60)
        try:
            out[i] = fns[i]()
        except BaseException as e:  # noqa: BLE001 — surfaced to the assert
            out[i] = e

    ts = [threading.Thread(target=run, args=(i,)) for i in range(len(fns))]
    for t in ts:
        t.start()
    for t in ts:
        t.join(120)
    return out


def roots_for(world, rng) -> list[int]:
    """CLIENTS roots: random vertices with an edge, one of them asked for
    by three clients at once, and the vertex without an out-edge."""
    subjects = np.unique(world["edges"][:, 0])
    roots = rng.choice(subjects, size=CLIENTS - 3, replace=False).tolist()
    return [int(r) for r in roots] + [int(roots[0])] * 2 + [SINK]


def parse_metrics(base: str) -> dict[str, float]:
    with urllib.request.urlopen(base + "/metrics", timeout=60) as r:
        lines = r.read().decode().splitlines()
    return {name: float(val) for name, _, val in
            (ln.rpartition(" ") for ln in lines if not ln.startswith("#"))}


def programs_loaded(base: str) -> int:
    comp = post(base, "/debug/compiles")
    return int(comp.get("compiles") or 0) + \
        int((comp.get("persistent_cache") or {}).get("hits") or 0)


def closed(node, n: int) -> None:
    """Wait until `n` request clocks have closed (a client has its answer
    before the handler flushes its clock and its root span)."""
    c = node.metrics.counter("dgraph_stage_requests_total")
    deadline = time.monotonic() + 10
    while c.value < n and time.monotonic() < deadline:
        time.sleep(0.002)
    assert c.value >= n, (c.value, n)


@pytest.mark.parametrize("mode", ["default", "full_batches"])
def test_every_client_gets_the_count_of_its_own_root(world, mode):
    """`default`: the node's own batcher (window 2 ms, idle fire), so the
    batches are whatever 22 arrivals form. `full_batches`: no idle fire
    and a long window, so the first sixteen arrivals ride ONE launch.
    Two rounds with the same roots after one request alone: the second
    round loads no program."""
    node, base = world["node"], world["base"]
    node.batcher = world["default_batcher"] if mode == "default" else \
        DeviceBatcher(node.dispatch_gate, node.metrics, window_ms=1500,
                      max_batch=16, idle_fire=False)
    roots = roots_for(world, np.random.default_rng(11))
    want = [{"khop": [{"count": want_count(world, r)}]} for r in roots]
    assert want[-1] == {"khop": [{"count": 0}]}         # the sink
    assert min(w["khop"][0]["count"] for w in want[:-1]) > 0
    def launches():
        """(launches, tasks, launches of one task alone) off /metrics: the
        occupancy histogram's le="1" bucket is the solo launches."""
        prom = parse_metrics(base)
        return (prom["dgraph_batch_formed_total"],
                prom["dgraph_batch_tasks_total"],
                prom['dgraph_batch_occupancy_bucket{le="1"}'])

    # a request alone first, as a warm-up does: the solo program is the
    # stacked launch's only companion
    assert post(base, "/query?edgeLimit=1000000",
                query_text(roots[1]))["data"] == want[1]
    before = launches()
    sends = [lambda r=r: post(base, "/query?edgeLimit=1000000",
                              query_text(r))["data"] for r in roots]
    assert at_once(sends) == want
    loaded = programs_loaded(base)
    assert at_once(sends) == want
    assert programs_loaded(base) == loaded
    d_launches, d_tasks, d_solo = (
        a - b for a, b in zip(launches(), before))
    # every request went through the seam once; what a launch of one did
    # not answer, a launch of two or more did
    assert d_tasks == 2 * CLIENTS
    assert d_tasks - d_solo >= 2 and d_launches < d_tasks
    assert d_launches - d_solo >= 1
    if mode == "full_batches":
        occ = node.metrics.histogram("dgraph_batch_occupancy").snapshot()
        assert occ["max"] == 16, occ


@pytest.fixture(scope="module")
def layout(world):
    """The node's PullGraph and each root's solo arrays, by root."""
    csr = world["node"].snapshot().pred("follows").csr
    g = pb.pull_graph_for(csr)
    cache: dict = {}

    def solo(roots: tuple, depth: int):
        key = (roots, depth)
        if key not in cache:
            seeds = pb.stack_seeds(g, [pb.seed_ranks(g, roots)], 1)[0]
            cache[key] = jax.device_get(pb.recurse_fused(
                *pb.fused_graph_args(g), seeds, depth=depth,
                chunks=g.chunks, chunks_d=g.chunks_d, allow_loop=False))
        return cache[key]

    return g, solo


@pytest.mark.parametrize("n", range(2, 17))
def test_each_occupancy_hands_back_the_solo_arrays(world, layout, n):
    """n members of one launch (the array always has sixteen rows): each
    gets the arrays a solo `pb.recurse_fused` gives its own seeds, bit for
    bit — two members with the same root, a member with two roots, one
    whose root has no out-edge and one whose root the graph never saw."""
    g, solo = layout
    rng = np.random.default_rng([n, 3])
    subjects = np.unique(world["edges"][:, 0])
    picks = [(int(r),) for r in rng.choice(subjects, size=n, replace=False)]
    picks[1] = picks[0]
    for i, odd in enumerate([(SINK,), (g.num_nodes + 5,),
                             tuple(sorted(picks[0] + picks[-1]))]):
        if 2 + i < n:
            picks[2 + i] = odd
    depth = 2

    def member(batcher, roots):
        ranks = pb.seed_ranks(g, roots)
        return lambda: batcher.dispatch_recurse(
            g, ranks, pb.recurse_first_hop_mode(g, ranks), depth, False,
            solo=lambda: solo(roots, depth))

    for _attempt in range(4):      # a straggler may miss the window
        batcher = DeviceBatcher(None, Registry(), window_ms=250,
                                max_batch=16, idle_fire=False)
        got = at_once([member(batcher, p) for p in picks])
        for roots, (masks, trav) in zip(picks, got):
            want_masks, want_trav = solo(roots, depth)
            assert masks.dtype == want_masks.dtype
            np.testing.assert_array_equal(masks, want_masks)
            np.testing.assert_array_equal(trav, want_trav)
        occ = batcher.metrics.histogram("dgraph_batch_occupancy").snapshot()
        if occ["max"] == n:
            break
    assert occ["max"] == n and occ["count"] == 1, occ
    for roots, (masks, _trav) in zip(picks, got):
        if roots in ((SINK,), (g.num_nodes + 5,)):
            assert not masks.any()


def _spans_by_trace(node, since: float) -> list[list[dict]]:
    sink = node.tracer.sink
    return [sink.get(r["trace_id"])["spans"] for r in sink.index(4096)
            if r.get("root") == "query" and r.get("start", since) >= since]


def test_stage_clock_tiles_a_batched_request_and_names_its_launch(world):
    """Sixteen requests in one launch, every one sampled: a follower's
    clock is in `batch.wait` while its leader runs the launch, and its
    trace holds a device_kernel span of the family with its role and the
    batch's size; the leader's holds the launch itself, with the
    dev.dispatch / dev.wait split. Either way the stage segments never
    overlap and add up to the root span."""
    node, base = world["node"], world["base"]
    node.batcher = DeviceBatcher(node.dispatch_gate, node.metrics,
                                 window_ms=1500, max_batch=16,
                                 idle_fire=False)
    roots = roots_for(world, np.random.default_rng(12))[:16]
    n0 = node.metrics.counter("dgraph_stage_requests_total").value
    t_before = time.time()
    got = at_once([lambda r=r: post(base, "/query?edgeLimit=1000000",
                                    query_text(r))["data"] for r in roots])
    assert got == [{"khop": [{"count": want_count(world, r)}]}
                   for r in roots]
    closed(node, n0 + 16)
    traces = [sp for sp in _spans_by_trace(node, t_before)
              if any(s["name"] == "query" and query_text(0)[:12] in
                     s["attrs"].get("query", "") for s in sp)][-16:]
    assert len(traces) == 16
    roles = []
    eps = 300e-6       # start is the wall clock, dur the monotonic one
    for spans in traces:
        root = next(s for s in spans if s["name"] == "query")
        stages = sorted((s for s in spans if s["kind"] == "stage"),
                        key=lambda s: s["start"])
        for a, b in zip(stages, stages[1:]):
            assert a["start"] + a["dur"] <= b["start"] + eps, (a, b)
        total = sum(s["dur"] for s in stages)
        assert total <= root["dur"] + eps
        assert root["dur"] - total <= max(0.02 * root["dur"], 500e-6), \
            (root["dur"], total)
        names = {s["name"] for s in stages}
        assert "batch.wait" in names, names
        kernels = [s for s in spans if s["name"] == "device_kernel"]
        assert [k["attrs"]["kernel"] for k in kernels] == ["batch.recurse"]
        attrs = kernels[0]["attrs"]
        assert attrs["batch"] == 16
        roles.append(attrs["role"])
        waits = [s for s in stages if s["name"] == "batch.wait"]
        if attrs["role"] == "follower":
            # the wait is the kernel span's own time, and nothing of the
            # device's stages is on a follower's clock
            assert not names & {"dev.dispatch", "dev.wait", "dev.window"}
            assert all(w["parent_id"] == kernels[0]["span_id"]
                       for w in waits)
        else:
            assert {"dev.dispatch", "dev.wait"} <= names
    assert sorted(roles) == ["follower"] * 15 + ["leader"]


def test_gate_queue_is_a_stage_of_its_own(world):
    """Width 1 and no batcher: of two requests at once one waits for the
    other's slot, and that wait is `gate.wait` on its clock (and the
    ledger's gate_wait_ms), not `exec`."""
    from dgraph_tpu.query.qcache import DispatchGate

    node = world["node"]
    gate, batcher = node.dispatch_gate, node.batcher
    node.dispatch_gate, node.batcher = DispatchGate(1, node.metrics), None
    series = node.metrics.keyed("dgraph_stage_us_total", labels=("stage",))
    try:
        roots = roots_for(world, np.random.default_rng(13))[:6]
        before = series.snapshot().get("gate.wait", 0)
        got = at_once([lambda r=r: node.query(query_text(r, 3))[0]
                       for r in roots])
        assert got == [{"khop": [{"count": want_count(world, r, 3)}]}
                       for r in roots]
        assert node.dispatch_gate._waits.value >= 1
        assert series.snapshot().get("gate.wait", 0) > before
    finally:
        node.dispatch_gate, node.batcher = gate, batcher


def test_listen_backlog_holds_the_clients(world):
    """http.server's backlog of 5 reset connections of a 22-client round
    (the second round of the test above, before the server had its own)."""
    from dgraph_tpu.api.http import make_server

    srv = make_server(world["node"], port=0)
    try:
        assert srv.request_queue_size >= 4 * CLIENTS
    finally:
        srv.server_close()
