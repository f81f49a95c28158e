"""Regression tests for the round-3 advisor findings.

1. (high) Numeric frontier-compare fast path must not fire for [type] list
   predicates: num_values_host holds one representative element per subject,
   so eq/lt/... must check every list element (reference matches any).
2. (high) Root eq on a lossy-indexed [string] list predicate: the lossy
   post-filter must re-check against pd.list_values, not just the single
   representative host value.
3. (medium) FollowerReader builds its read snapshot at ts =
   max_seen_commit_ts (not ts+1): a commit landing at exactly ts+1 mid-build
   must not become partially visible.
4. (low) Idle-txn reaper exempts young txns: a slow client that opened a
   txn lazily and mutates later must not get "unknown txn".
"""

import pytest

from dgraph_tpu.api.server import Node


@pytest.fixture()
def node():
    n = Node()
    n.alter(schema_text="""
        name: string @index(exact) .
        score: [int] @index(int) .
        nick: [string] @index(term) .
    """)
    n.mutate(set_nquads="""
        _:a <name> "ann" .
        _:a <score> "9"^^<xs:int> .
        _:a <score> "10"^^<xs:int> .
        _:a <nick> "bob" .
        _:a <nick> "zed" .
        _:b <name> "bea" .
        _:b <score> "11"^^<xs:int> .
        _:b <nick> "carol" .
    """, commit_now=True)
    return n


def _names(out, block="q"):
    return sorted(x["name"] for x in out.get(block, []))


def test_list_int_frontier_eq_matches_any_element(node):
    # score = {9, 10}: sorted-by-string representative is 10, so the old
    # vector fast path compared only 10 and dropped the eq(score, 9) match
    out, _ = node.query(
        '{ q(func: has(name)) @filter(eq(score, 9)) { name } }')
    assert _names(out) == ["ann"]


def test_list_int_frontier_lt_matches_any_element(node):
    # lt(score, 10) must match via element 9 even though representative is 10
    out, _ = node.query(
        '{ q(func: has(name)) @filter(lt(score, 10)) { name } }')
    assert _names(out) == ["ann"]


def test_list_int_frontier_no_false_positive(node):
    out, _ = node.query(
        '{ q(func: has(name)) @filter(eq(score, 12)) { name } }')
    assert _names(out) == []


def test_root_eq_lossy_list_predicate(node):
    # term index is lossy → post-filter; representative host value is "bob",
    # so eq(nick, "zed") used to return empty
    out, _ = node.query('{ q(func: eq(nick, "zed")) { name } }')
    assert _names(out) == ["ann"]
    out, _ = node.query('{ q(func: eq(nick, "bob")) { name } }')
    assert _names(out) == ["ann"]
    out, _ = node.query('{ q(func: eq(nick, "nope")) { name } }')
    assert _names(out) == []


def test_follower_snapshot_covers_max_seen_commit_ts(tmp_path):
    # functional guard for the read_ts fix: everything shipped (including the
    # newest commit, which lands at exactly max_seen_commit_ts) must be
    # visible at the follower's build ts
    from dgraph_tpu.coord.replication import ReplicaGroup

    g = ReplicaGroup(str(tmp_path / "grp"), n=3, serve_reads=True)
    try:
        g.node.alter(schema_text="balance: int .")
        g.node.mutate(set_nquads='_:x <balance> "42"^^<xs:int> .',
                      commit_now=True)
        follower = next(m.reader for m in g.members if m.reader is not None)
        got = follower.query("{ q(func: has(balance)) { balance } }")
        assert got["q"] == [{"balance": 42}]
    finally:
        g.close()


def test_idle_txn_reaper_spares_young_txns():
    n = Node()
    n.alter(schema_text="v: int .")
    n.MAX_IDLE_TXNS = 8  # keep the test fast
    slow = n.new_txn()   # lazily-opened, pristine, young
    for _ in range(20):
        n.new_txn()
    # the slow client finally mutates + commits — must still be known
    n.mutate(set_nquads='_:x <v> "1"^^<xs:int> .', start_ts=slow.start_ts)
    assert n.commit(slow.start_ts) > slow.start_ts


def test_idle_txn_reaper_still_reaps_stale_txns():
    n = Node()
    n.MAX_IDLE_TXNS = 8
    stale = [n.new_txn() for _ in range(12)]
    for ctx in stale:
        ctx.last_active -= n.IDLE_TXN_GRACE_S + 1
    n.new_txn()  # triggers the reap
    assert sum(1 for c in stale if c.start_ts not in n._txns) > 0


def test_regexp_matches_any_list_element(node):
    node.alter(schema_text="nick: [string] @index(trigram) .")
    node.mutate(set_nquads='_:c <name> "cyd" .\n_:c <nick> "aaa" .\n'
                           '_:c <nick> "zedding" .', commit_now=True)
    out, _ = node.query('{ q(func: regexp(nick, /zedd/)) { name } }')
    assert _names(out) == ["cyd"]


# -- incremental snapshots on workers + followers (VERDICT r3 #6) ------------

def test_worker_snapshot_rebuilds_one_predicate(tmp_path):
    """A commit touching one predicate re-folds that predicate only — every
    other PredData keeps array identity on the worker wire service."""
    pytest.importorskip("grpc")
    from dgraph_tpu.parallel.remote import WorkerService
    from dgraph_tpu.query import mutation as mut
    from dgraph_tpu.query import rdf
    from dgraph_tpu.storage.postings import DirectedEdge, Op
    from dgraph_tpu.storage.store import Store
    from dgraph_tpu.utils.schema import parse_schema
    from dgraph_tpu.utils.types import TypeID, Val

    s = Store()
    for e in parse_schema("a: int .\nb: int ."):
        s.set_schema(e)
    for ts, (attr, val) in ((1, ("a", 1)), (3, ("b", 2))):
        touched, _, _ = mut.apply_mutations(
            s, [DirectedEdge(1, attr, value=Val(TypeID.INT, val))], ts)
        s.commit(ts, ts + 1, touched)
    svc = WorkerService(s)
    snap1 = svc._snapshot(10)
    pd_a1, pd_b1 = snap1.preds["a"], snap1.preds["b"]

    # commit touching ONLY b
    touched, _, _ = mut.apply_mutations(
        s, [DirectedEdge(2, "b", value=Val(TypeID.INT, 9))], 20)
    s.commit(20, 21, touched)
    snap2 = svc._snapshot(30)
    assert snap2.preds["a"] is pd_a1          # untouched: same arrays
    assert snap2.preds["b"] is not pd_b1      # re-folded past the commit
    assert 2 in snap2.preds["b"].host_values


def test_follower_snapshot_rebuilds_one_predicate(tmp_path):
    from dgraph_tpu.coord.replication import ReplicaGroup

    g = ReplicaGroup(str(tmp_path / "grp"), n=3, serve_reads=True)
    try:
        g.node.alter(schema_text="a: int .\nb: int .")
        g.node.mutate(set_nquads='<0x1> <a> "1"^^<xs:int> .\n'
                                 '<0x1> <b> "2"^^<xs:int> .', commit_now=True)
        f = next(m.reader for m in g.members if m.reader is not None)
        assert f.query("{ q(func: has(a)) { a b } }")["q"] == [
            {"a": 1, "b": 2}]
        snap1 = f._assembler.snapshot(f.store.max_seen_commit_ts)
        pd_a1, pd_b1 = snap1.preds["a"], snap1.preds["b"]

        g.node.mutate(set_nquads='<0x2> <b> "9"^^<xs:int> .', commit_now=True)
        out = f.query("{ q(func: has(b)) { b } }")
        assert sorted(x["b"] for x in out["q"]) == [2, 9]
        snap2 = f._assembler.snapshot(f.store.max_seen_commit_ts)
        assert snap2.preds["a"] is pd_a1
        assert snap2.preds["b"] is not pd_b1
    finally:
        g.close()


def test_old_ts_snapshot_stays_cached_after_newer_commit():
    """A newer commit must NOT invalidate cached snapshots at older read
    timestamps — they are immutable views (review r4 on _stale)."""
    from dgraph_tpu.parallel.remote import WorkerService
    from dgraph_tpu.query import mutation as mut
    from dgraph_tpu.storage.postings import DirectedEdge
    from dgraph_tpu.storage.store import Store
    from dgraph_tpu.utils.schema import parse_schema
    from dgraph_tpu.utils.types import TypeID, Val

    s = Store()
    for e in parse_schema("a: int ."):
        s.set_schema(e)
    touched, _, _ = mut.apply_mutations(
        s, [DirectedEdge(1, "a", value=Val(TypeID.INT, 1))], 1)
    s.commit(1, 2, touched)
    svc = WorkerService(s)
    old = svc._snapshot(2)
    touched, _, _ = mut.apply_mutations(
        s, [DirectedEdge(2, "a", value=Val(TypeID.INT, 5))], 10)
    s.commit(10, 11, touched)
    assert svc._snapshot(2) is old          # immutable old view: cache hit
    new = svc._snapshot(11)
    assert new is not old
    assert 2 in new.preds["a"].host_values
    assert 2 not in old.preds["a"].host_values
