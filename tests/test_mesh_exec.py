"""Mesh deployment mode on the virtual 8-device CPU mesh (conftest forces
`XLA_FLAGS=--xla_force_host_platform_device_count=8`; this module is a
no-op anywhere that fixture is absent).

Covers the ISSUE-6 acceptance gates: shard_csr padding/sentinel rows,
the fused multi-hop chain executing as ONE device dispatch (vs one per
hop on the per-task path), and mesh-mode results byte-identical to the single-device executor on the golden query
corpus (tests/golden/expected.json — the same battery the wire cluster is
diffed against in contrib/scripts/smoke_mesh.sh)."""

import json
import os

import numpy as np
import pytest
import jax

from dgraph_tpu.api.server import Node
from dgraph_tpu.parallel import dist
from dgraph_tpu.parallel.mesh import make_mesh
from dgraph_tpu.query.engine import set_query_edge_limit

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs the conftest-provided 8-virtual-device CPU mesh")


# ---------------------------------------------------------------------------
# shard_csr: padding / sentinel invariants
# ---------------------------------------------------------------------------

def _toy_csr():
    # 5 subject rows over 8 shards: shards 5-7 are pure padding
    subjects = np.asarray([2, 5, 7, 11, 13], dtype=np.int32)
    indptr = np.asarray([0, 2, 3, 6, 6, 8], dtype=np.int32)
    indices = np.asarray([5, 7, 2, 1, 5, 9, 2, 7], dtype=np.int32)
    return subjects, indptr, indices


def test_shard_csr_padding_and_sentinel_rows():
    subjects, indptr, indices = _toy_csr()
    mesh = make_mesh(8)
    sh = dist.shard_csr(subjects, indptr, indices, mesh)
    assert sh.n_shards == 8
    sub = np.asarray(sh.subjects)
    ptr = np.asarray(sh.indptr)
    idx = np.asarray(sh.indices)
    snt = int(dist.SNT)
    # every shard is padded to the same row/edge capacity
    assert sub.shape == (8, 1) and ptr.shape == (8, 2)
    assert idx.shape[0] == 8
    for s in range(8):
        if s < 5:
            assert sub[s, 0] == subjects[s]
            deg = int(indptr[s + 1] - indptr[s])
            assert ptr[s, 1] - ptr[s, 0] == deg
            got = idx[s, : deg]
            np.testing.assert_array_equal(got, indices[indptr[s]: indptr[s + 1]])
            # padding beyond the shard's real edges is sentinel
            assert (idx[s, deg:] == snt).all()
        else:
            # pure padding shard: sentinel subject, zero degree, sentinel edges
            assert sub[s, 0] == snt
            assert (ptr[s] == 0).all()
            assert (idx[s] == snt).all()
    # row 3 has zero degree (indptr[3] == indptr[4]): its shard's ptr is flat
    assert ptr[3, 0] == ptr[3, 1] == 0


def test_expand_matrix_matches_host_and_stages_frontier():
    subjects, indptr, indices = _toy_csr()
    mesh = make_mesh(8)
    csr = dist.DistPredCSR(subjects, indptr, indices, mesh)
    uids = np.asarray([2, 7, 11, 99], dtype=np.int64)   # 11: empty row, 99: missing
    matrix, total = csr.expand_matrix(uids)
    assert total == 5
    np.testing.assert_array_equal(matrix[0], [5, 7])
    np.testing.assert_array_equal(matrix[1], [1, 5, 9])
    assert len(matrix[2]) == 0 and len(matrix[3]) == 0
    # the merged dest set is staged on device: replaying it skips the upload
    staged_uids, staged_dev = csr._staged
    np.testing.assert_array_equal(staged_uids, [1, 5, 7, 9])
    m2, _ = csr.expand_matrix(staged_uids)
    # rows must match the host mirrors exactly
    host = {int(s): indices[indptr[i]: indptr[i + 1]].tolist()
            for i, s in enumerate(subjects)}
    for u, row in zip(staged_uids, m2):
        np.testing.assert_array_equal(row, host.get(int(u), []))


def test_expand_program_cached_across_calls():
    subjects, indptr, indices = _toy_csr()
    mesh = make_mesh(8)
    csr = dist.DistPredCSR(subjects, indptr, indices, mesh)
    csr.expand_matrix(np.asarray([2, 5], dtype=np.int64))
    before = dist._expand_program.cache_info()
    for _ in range(3):
        csr.expand_matrix(np.asarray([2, 5], dtype=np.int64))
    after = dist._expand_program.cache_info()
    assert after.misses == before.misses       # no rebuild per call
    assert after.hits > before.hits


# ---------------------------------------------------------------------------
# mesh-mode Node vs single-device executor
# ---------------------------------------------------------------------------

from tests.test_golden import QUERIES as GOLDEN_QUERIES  # noqa: E402
from tests.test_golden import SCHEMA as GOLDEN_SCHEMA  # noqa: E402
from tests.test_golden import GOLDEN_PATH, _dataset  # noqa: E402


@pytest.fixture(scope="module")
def mesh_node():
    n = Node(mesh_devices=8, mesh_min_edges=1)
    n.alter(schema_text=GOLDEN_SCHEMA)
    n.mutate(set_nquads=_dataset(), commit_now=True)
    return n


def test_mesh_golden_corpus_byte_identical(mesh_node):
    """Every golden-corpus query answers byte-identically in mesh mode."""
    if not os.path.exists(GOLDEN_PATH):
        pytest.skip("golden file not generated yet")
    with open(GOLDEN_PATH) as f:
        want = json.load(f)
    for qname, q in GOLDEN_QUERIES:
        res, _ = mesh_node.query(q)
        got = json.loads(json.dumps(res, default=str))
        assert got == want[qname], f"mesh golden diff in {qname!r}"
    assert mesh_node.metrics.counter(
        "dgraph_mesh_sharded_tablets").value > 0


CHAIN_SCHEMA = """
name: string @index(exact) .
p0: [uid] .
p1: [uid] .
p2: [uid] @reverse .
follows: [uid] .
"""


@pytest.fixture(scope="module")
def chain_pair():
    """(plain node, mesh node) over an identical 3-predicate chain graph +
    a self-referencing follows graph — caches disabled so every query
    reaches the dispatch seam (dispatch counting must not be short-
    circuited by the result tiers)."""
    rng = np.random.default_rng(11)
    quads = [f'_:n{i} <name> "node{i}" .' for i in range(80)]
    for i in range(80):
        for attr, mul, off in (("p0", 3, 1), ("p1", 5, 2), ("p2", 7, 3)):
            for k in range(3):
                quads.append(f"_:n{i} <{attr}> _:n{(i * mul + off + k) % 80} .")
        for j in sorted(rng.choice(80, size=3, replace=False)):
            if j != i:
                quads.append(f"_:n{i} <follows> _:n{j} .")
    nodes = []
    for mesh in (0, 8):
        n = Node(mesh_devices=mesh, mesh_min_edges=1)
        n.alter(schema_text=CHAIN_SCHEMA)
        n.mutate(set_nquads="\n".join(quads), commit_now=True)
        n.plan_cache = n.task_cache = n.result_cache = None
        nodes.append(n)
    return nodes


CHAIN_BATTERY = [
    # the acceptance shape: a 3-hop traversal crossing 3 predicate shards
    '{ q(func: eq(name, "node3")) { p0 { p1 { p2 } } } }',
    '{ q(func: eq(name, "node3")) { p0 { p1 { p2 { name } } } } }',
    '{ q(func: uid(0x1, 0x2)) { p0 { p0 { p0 } } } }',
    '{ q(func: eq(name, "node5")) { p2 { ~p2 } } }',
    '{ q(func: eq(name, "node1")) @recurse(depth: 3) { follows } }',
    '{ q(func: eq(name, "node1")) @recurse(depth: 4, loop: true) { p0 } }',
    '{ p as shortest(from: 0x1, to: 0x30) { follows } r(func: uid(p)) { uid } }',
    '{ p as shortest(from: 0x1, to: 0x30, numpaths: 2) { follows } '
    'r(func: uid(p)) { uid } }',
]


def test_mesh_battery_byte_identical(chain_pair):
    plain, mesh = chain_pair
    for q in CHAIN_BATTERY:
        a, _ = plain.query(q)
        b, _ = mesh.query(q)
        assert json.dumps(a, sort_keys=True, default=str) == \
            json.dumps(b, sort_keys=True, default=str), q


def test_chain_is_one_dispatch_vs_hops_on_per_task_path(chain_pair):
    """The headline gate: a 3-hop traversal crossing 3 predicate shards is
    ONE device dispatch in mesh mode; the same query forced through the
    per-task seam (the shape gRPC/ProcessTaskOverNetwork pays per hop)
    costs one dispatch per hop."""
    _plain, mesh = chain_pair
    q = '{ q(func: eq(name, "node3")) { p0 { p1 { p2 } } } }'
    c = mesh.metrics.counter("dgraph_mesh_dispatches_total")
    before = c.value
    out, _ = mesh.query(q)
    assert c.value - before == 1, "fused chain must be one dispatch"
    # same placed snapshot, fusion off -> one dispatch per hop (the N×hops
    # shape the gRPC fan-out pays per group, minus the wire). Force the
    # device regime: this test graph is far below the real cutover.
    from dgraph_tpu.query import dql, task as task_mod
    from dgraph_tpu.query.engine import Executor

    snap = mesh.snapshot()
    before = c.value
    old = task_mod.HOST_EXPAND_MAX
    task_mod.HOST_EXPAND_MAX = 0
    try:
        out2 = Executor(snap, mesh.store.schema,
                        mesh=None).execute(dql.parse(q))
    finally:
        task_mod.HOST_EXPAND_MAX = old
    assert c.value - before == 3, "per-task path pays one dispatch per hop"
    assert json.dumps(out, sort_keys=True) == json.dumps(out2, sort_keys=True)


def test_per_task_mesh_expand_is_size_adaptive(chain_pair):
    """Below the host/device cutover a per-task expand over a sharded
    tablet serves from the host mirrors — no mesh dispatch (the planner's
    cutover machinery applies to mesh tablets unchanged)."""
    _plain, mesh = chain_pair
    from dgraph_tpu.query import dql
    from dgraph_tpu.query.engine import Executor

    snap = mesh.snapshot()
    c = mesh.metrics.counter("dgraph_mesh_dispatches_total")
    before = c.value
    Executor(snap, mesh.store.schema, mesh=None).execute(
        dql.parse('{ q(func: uid(0x1)) { p0 { uid } } }'))
    assert c.value == before, "tiny frontier must take the host mirror"


def test_mesh_recurse_one_dispatch(chain_pair):
    _plain, mesh = chain_pair
    c = mesh.metrics.counter("dgraph_mesh_dispatches_total")
    before = c.value
    mesh.query('{ q(func: eq(name, "node1")) @recurse(depth: 3) { follows } }')
    assert c.value - before == 1


def test_mesh_recurse_edge_budget(chain_pair):
    _plain, mesh = chain_pair
    set_query_edge_limit(3)     # conftest restores the module default
    with pytest.raises(Exception, match="ErrTooBig"):
        mesh.query(
            '{ q(func: eq(name, "node1")) @recurse(depth: 3) { follows } }')


def test_mesh_fallback_shapes_still_classic(chain_pair):
    """Shapes the fused program does not cover (filters between hops,
    pagination) stay byte-identical via the per-task fallback."""
    plain, mesh = chain_pair
    for q in [
        '{ q(func: eq(name, "node3")) { p0 @filter(uid(0x1, 0x2, 0x3)) '
        '{ p1 } } }',
        '{ q(func: eq(name, "node3")) { p0 (first: 2) { p1 } } }',
    ]:
        a, _ = plain.query(q)
        b, _ = mesh.query(q)
        assert json.dumps(a, sort_keys=True) == json.dumps(b, sort_keys=True)


def test_mesh_write_then_read_fresh(chain_pair):
    """A commit lands as a delta overlay (host fallback) and is visible
    immediately; the tablet re-shards after compaction."""
    _plain, mesh = chain_pair
    mesh.mutate(set_nquads='<0x1> <p0> <0x4f> .', commit_now=True)
    out, _ = mesh.query('{ q(func: uid(0x1)) { p0 { uid } } }')
    uids = {x["uid"] for x in out["q"][0]["p0"]}
    assert "0x4f" in uids
