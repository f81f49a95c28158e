"""Regression tests for the round-1 advisor findings.

1. Untagged value reads must NOT fall back to lang-tagged values; only the
   explicit "." tag does (reference posting/list.go postingForLangs).
2. ops.csr.expand with an empty adjacency returns an all-sentinel result.
3. Nested count(uid) inside a child block emits {"count": n} per parent.
4. Frontier-level eq(pred, v1, v2, ...) matches any listed value.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from dgraph_tpu.ops import csr as csrops
from dgraph_tpu.query import dql
from dgraph_tpu.query.engine import Executor
from dgraph_tpu.storage import index as idx
from dgraph_tpu.storage.csr_build import build_snapshot
from dgraph_tpu.storage.postings import DirectedEdge, PostingList, Posting, lang_uid
from dgraph_tpu.storage.store import Store
from dgraph_tpu.utils.schema import parse_schema
from dgraph_tpu.utils.types import TypeID, Val


@pytest.fixture(scope="module")
def env():
    s = Store()
    for e in parse_schema("""
        name: string @index(exact) @lang .
        age: int .
        friend: uid .
    """):
        s.set_schema(e)
    # uid 1: only a French name. uid 2: untagged + French. uid 3: untagged only.
    idx.add_mutation_with_index(
        s, DirectedEdge(1, "name", value=Val(TypeID.STRING, "Michel"), lang="fr"), 1)
    idx.add_mutation_with_index(
        s, DirectedEdge(2, "name", value=Val(TypeID.STRING, "Rick")), 1)
    idx.add_mutation_with_index(
        s, DirectedEdge(2, "name", value=Val(TypeID.STRING, "Rique"), lang="fr"), 1)
    idx.add_mutation_with_index(
        s, DirectedEdge(3, "name", value=Val(TypeID.STRING, "Glenn")), 1)
    for u, a in [(1, 10), (2, 15), (3, 20)]:
        idx.add_mutation_with_index(s, DirectedEdge(u, "age", value=Val(TypeID.INT, a)), 1)
    for b in (1, 2, 3):
        idx.add_mutation_with_index(s, DirectedEdge(4, "friend", object_uid=b), 1)
    s.commit(1, 2, list(s.lists.keys()))
    return s, build_snapshot(s, read_ts=3)


def run(env, q):
    s, snap = env
    return Executor(snap, s.schema).execute(dql.parse(q))


# -- 1. lang fallback ---------------------------------------------------------

def test_untagged_read_ignores_lang_only_values():
    pl = PostingList()
    pl.add_mutation(1, Posting(lang_uid("fr"), value=Val(TypeID.STRING, "chat"),
                               lang="fr"))
    pl.commit(1, 2)
    assert pl.value(3) is None                 # untagged read: nothing
    assert pl.value(3, "fr").value == "chat"   # exact tag
    assert pl.value(3, ".").value == "chat"    # any-language tag


def test_query_untagged_name_on_lang_only_node(env):
    # uid 1 holds only name@fr: plain `name` must NOT surface the French value
    out = run(env, '{ q(func: uid(1)) { name } }')
    assert "name" not in out.get("q", [{}])[0] if out.get("q") else True
    out = run(env, '{ q(func: uid(1)) { name@fr } }')
    assert out["q"][0]["name@fr"] == "Michel"
    out = run(env, '{ q(func: uid(1)) { name@. } }')
    assert out["q"][0]["name@."] == "Michel"


def test_any_lang_prefers_untagged(env):
    out = run(env, '{ q(func: uid(2)) { name@. } }')
    assert out["q"][0]["name@."] == "Rick"


def test_has_matches_lang_only_nodes(env):
    out = run(env, '{ q(func: has(name)) { uid } }')
    uids = {x["uid"] for x in out["q"]}
    assert uids == {"0x1", "0x2", "0x3"}
    # frontier-level has() too
    out = run(env, '{ q(func: uid(4)) { friend @filter(has(name)) { uid } } }')
    uids = {x["uid"] for x in out["q"][0]["friend"]}
    assert uids == {"0x1", "0x2", "0x3"}


# -- 2. empty expand ----------------------------------------------------------

def test_expand_empty_indices():
    indptr = jnp.zeros(3, dtype=jnp.int32)
    indices = jnp.zeros(0, dtype=jnp.int32)
    rows = jnp.asarray([0, 1], dtype=jnp.int32)
    res = csrops.expand(indptr, indices, rows, out_cap=8)
    assert int(res.total) == 0
    assert np.all(np.asarray(res.seg) == -1)
    res2 = csrops.expand(indptr, indices, jnp.zeros(0, jnp.int32), out_cap=4)
    assert int(res2.total) == 0


# -- 3. nested count(uid) -----------------------------------------------------

def test_nested_count_uid(env):
    out = run(env, '{ q(func: uid(4)) { friend { count(uid) } } }')
    assert out["q"][0]["friend"] == [{"count": 3}]
    # respects filters
    out = run(env, '{ q(func: uid(4)) { friend @filter(ge(age, 15)) { count(uid) } } }')
    assert out["q"][0]["friend"] == [{"count": 2}]
    # mixed with sibling attributes: count is one more list entry (ref query.go:472)
    out = run(env, '{ q(func: uid(4)) { friend { count(uid) name } } }')
    objs = out["q"][0]["friend"]
    assert {"count": 3} in objs and {"name": "Glenn"} in objs


# -- 4. multi-value eq on frontier --------------------------------------------

def test_multivalue_eq_filter(env):
    out = run(env, '{ q(func: uid(4)) { friend @filter(eq(age, 10, 20)) { uid } } }')
    uids = {x["uid"] for x in out["q"][0]["friend"]}
    assert uids == {"0x1", "0x3"}
