"""End-to-end query engine tests: DQL in → JSON out.

Mirrors the reference's query/query_test.go pattern (embedded single-process
cluster, golden JSON assertions; SURVEY.md §4).
"""

import numpy as np
import pytest

from dgraph_tpu.query import dql
from dgraph_tpu.query.engine import Executor, QueryError, _known_uids
from dgraph_tpu.storage import index as idx
from dgraph_tpu.storage.csr_build import build_snapshot
from dgraph_tpu.storage.postings import DirectedEdge, Op
from dgraph_tpu.storage.store import Store
from dgraph_tpu.utils.schema import parse_schema
from dgraph_tpu.utils.types import TypeID, Val


@pytest.fixture(scope="module")
def env():
    s = Store()
    for e in parse_schema("""
        name: string @index(term, exact) @lang .
        age: int @index(int) .
        friend: uid @reverse @count .
        follows: uid .
    """):
        s.set_schema(e)
    people = {1: ("Michonne", 38), 2: ("Rick Grimes", 15), 3: ("Glenn Rhee", 15),
              4: ("Daryl Dixon", 17), 5: ("Andrea", 19), 6: ("Carl", 10)}
    for uid, (nm, age) in people.items():
        idx.add_mutation_with_index(s, DirectedEdge(uid, "name", value=Val(TypeID.STRING, nm)), 1)
        idx.add_mutation_with_index(s, DirectedEdge(uid, "age", value=Val(TypeID.INT, age)), 1)
    friends = [(1, 2), (1, 3), (1, 4), (1, 5), (2, 1), (3, 1), (4, 5), (5, 6)]
    for a, b in friends:
        fac = (("weight", Val(TypeID.FLOAT, 0.5 if (a, b) == (1, 2) else 1.0)),
               ("close", Val(TypeID.BOOL, (a, b) in [(1, 2), (1, 3)])))
        idx.add_mutation_with_index(s, DirectedEdge(a, "friend", object_uid=b, facets=fac), 1)
    idx.add_mutation_with_index(s, DirectedEdge(1, "name", value=Val(TypeID.STRING, "Michonne-fr"), lang="fr"), 1)
    s.commit(1, 2, list(s.lists.keys()))
    return s, build_snapshot(s, read_ts=3)


def run(env, q, variables=None):
    s, snap = env
    return Executor(snap, s.schema).execute(dql.parse(q, variables))


def test_basic_query(env):
    out = run(env, '{ me(func: eq(name, "Michonne")) { uid name age } }')
    assert out == {"me": [{"uid": "0x1", "name": "Michonne", "age": 38}]}


def test_children_and_nesting(env):
    out = run(env, '{ me(func: uid(1)) { name friend { name age } } }')
    me = out["me"][0]
    assert me["name"] == "Michonne"
    names = {f["name"] for f in me["friend"]}
    assert names == {"Rick Grimes", "Glenn Rhee", "Daryl Dixon", "Andrea"}


def test_filters_and_or_not(env):
    out = run(env, '''{
      me(func: uid(1)) {
        friend @filter(eq(age, 15) or eq(name, "Andrea")) { name }
      }
    }''')
    names = {f["name"] for f in out["me"][0]["friend"]}
    assert names == {"Rick Grimes", "Glenn Rhee", "Andrea"}
    out = run(env, '{ me(func: uid(1)) { friend @filter(not eq(age, 15)) { name } } }')
    names = {f["name"] for f in out["me"][0]["friend"]}
    assert names == {"Daryl Dixon", "Andrea"}


def test_root_filter(env):
    out = run(env, '{ q(func: has(friend)) @filter(ge(age, 17)) { name } }')
    names = {f["name"] for f in out["q"]}
    assert names == {"Michonne", "Daryl Dixon", "Andrea"}


def test_pagination_and_order(env):
    out = run(env, '{ q(func: has(name), orderasc: age, first: 3) { name age } }')
    assert [x["age"] for x in out["q"]] == [10, 15, 15]
    out = run(env, '{ q(func: has(name), orderdesc: age, offset: 1, first: 2) { age } }')
    assert [x["age"] for x in out["q"]] == [19, 17]


def test_count_children(env):
    out = run(env, '{ me(func: uid(1, 2)) { name fc: count(friend) } }')
    by_name = {x["name"]: x.get("fc") for x in out["me"]}
    assert by_name == {"Michonne": 4, "Rick Grimes": 1}
    out = run(env, '{ q(func: has(friend)) { count(uid) } }')
    assert out["q"] == [{"count": 5}]


def test_count_at_root(env):
    out = run(env, '{ q(func: eq(count(friend), 4)) { name } }')
    assert out["q"] == [{"name": "Michonne"}]


def test_reverse_edge(env):
    out = run(env, '{ q(func: uid(5)) { ~friend { name } } }')
    names = {x["name"] for x in out["q"][0]["~friend"]}
    assert names == {"Michonne", "Daryl Dixon"}


def test_uid_vars(env):
    out = run(env, '''{
      A as var(func: uid(1)) { friend { friend } }
      q(func: uid(A)) { name }
    }''')
    assert {x["name"] for x in out["q"]} == {"Michonne"}  # only 1 in A... wait
    # A = uids of var block root = [1]; check friend-of-friend var instead
    out = run(env, '''{
      var(func: uid(1)) { friend { B as friend } }
      q(func: uid(B), orderasc: name) { name }
    }''')
    assert [x["name"] for x in out["q"]] == ["Andrea", "Carl", "Michonne"]


def test_value_vars_and_math(env):
    out = run(env, '''{
      var(func: uid(1)) { friend { a as age } }
      q(func: uid(2, 3), orderasc: name) {
        name
        doubled: math(a * 2)
      }
    }''')
    by = {x["name"]: x["doubled"] for x in out["q"]}
    assert by == {"Glenn Rhee": 30, "Rick Grimes": 30}


def test_aggregates(env):
    out = run(env, '''{
      var(func: has(name)) { a as age }
      q() {
        mn: min(val(a)) mx: max(val(a)) total: sum(val(a)) mean: avg(val(a))
      }
    }''')
    vals = {}
    for obj in out["q"]:
        vals.update(obj)
    assert vals["mn"] == 10 and vals["mx"] == 38
    assert vals["total"] == 38 + 15 + 15 + 17 + 19 + 10
    assert vals["mean"] == pytest.approx(19.0)


def test_eq_valvar_at_root(env):
    out = run(env, '''{
      var(func: has(name)) { a as age }
      q(func: eq(val(a), 15), orderasc: name) { name }
    }''')
    assert [x["name"] for x in out["q"]] == ["Glenn Rhee", "Rick Grimes"]


def test_cascade(env):
    # Carl(6) has no friend edges: cascade drops him
    out = run(env, '{ q(func: has(name)) @cascade { name friend { name } } }')
    names = {x["name"] for x in out["q"]}
    assert names == {"Michonne", "Rick Grimes", "Glenn Rhee", "Daryl Dixon", "Andrea"}


def test_normalize(env):
    out = run(env, '''{
      q(func: uid(1)) @normalize {
        n: name
        friend { fn: name }
      }
    }''')
    rows = out["q"]
    assert all(r.get("n") == "Michonne" for r in rows)
    assert {r["fn"] for r in rows} == {"Rick Grimes", "Glenn Rhee", "Daryl Dixon", "Andrea"}


def test_groupby(env):
    out = run(env, '''{
      q(func: has(name)) @groupby(age) { count(uid) }
    }''')
    groups = {g["age"]: g["count"] for g in out["q"][0]["@groupby"]}
    assert groups == {38: 1, 15: 2, 17: 1, 19: 1, 10: 1}


def test_recurse(env):
    out = run(env, '''{
      q(func: uid(1)) @recurse(depth: 2) { name friend }
    }''')
    me = out["q"][0]
    assert me["name"] == "Michonne"
    level1 = {f["name"] for f in me["friend"]}
    assert level1 == {"Rick Grimes", "Glenn Rhee", "Daryl Dixon", "Andrea"}
    # depth 2: Rick's friend = Michonne (edge 1->2 seen, 2->1 new)
    rick = [f for f in me["friend"] if f["name"] == "Rick Grimes"][0]
    assert {f["name"] for f in rick.get("friend", [])} == {"Michonne"}


def test_shortest_path(env):
    out = run(env, '''{
      path as shortest(from: 0x1, to: 0x6) { friend }
      path(func: uid(path), orderasc: name) { name }
    }''')
    p = out["_path_"][0]
    assert p["uid"] == "0x1"
    assert p["friend"][0]["uid"] == "0x5"
    assert p["friend"][0]["friend"][0]["uid"] == "0x6"
    assert {x["name"] for x in out["path"]} == {"Michonne", "Andrea", "Carl"}


def test_shortest_path_weighted(env):
    out = run(env, '''{
      sp as shortest(from: 0x2, to: 0x5, numpaths: 2) { friend @facets(weight) }
      q(func: uid(sp)) { name }
    }''')
    paths = out["_path_"]
    assert len(paths) == 2
    assert paths[0]["_weight_"] <= paths[1]["_weight_"]


def test_facets_output(env):
    out = run(env, '{ q(func: uid(1)) { friend @facets(close) { name } } }')
    friends = out["q"][0]["friend"]
    close = {f["name"]: f.get("friend|close") for f in friends}
    assert close["Rick Grimes"] is True and close["Andrea"] is False


def test_facet_filter(env):
    out = run(env, '{ q(func: uid(1)) { friend @facets(eq(close, true)) { name } } }')
    names = {f["name"] for f in out["q"][0]["friend"]}
    assert names == {"Rick Grimes", "Glenn Rhee"}


def test_lang(env):
    out = run(env, '{ q(func: uid(1)) { name@fr } }')
    assert out["q"] == [{"name@fr": "Michonne-fr"}]


def test_graphql_vars(env):
    out = run(env, 'query t($n: string) { q(func: eq(name, $n)) { age } }',
              variables={"$n": "Andrea"})
    assert out["q"] == [{"age": 19}]


def test_edge_budget(env):
    s, snap = env
    import dgraph_tpu.query.engine as eng

    old = eng.MAX_QUERY_EDGES
    eng.MAX_QUERY_EDGES = 2
    try:
        with pytest.raises(QueryError, match="edge budget"):
            Executor(snap, s.schema).execute(
                dql.parse("{ q(func: has(name)) { friend { friend } } }"))
    finally:
        eng.MAX_QUERY_EDGES = old


def test_missing_var_errors(env):
    with pytest.raises(QueryError, match="missing variable"):
        run(env, "{ q(func: uid(NOPE)) { name } }")


def test_leaf_child_filter(env):
    # regression: @filter on a leaf child (no sub-block) must prune results
    out = run(env, '{ q(func: uid(1)) { friend @filter(eq(age, 15)) } }')
    uids = {f["uid"] for f in out["q"][0]["friend"]}
    assert uids == {"0x2", "0x3"}


def test_child_pagination_with_filter(env):
    out = run(env, '{ q(func: uid(1)) { friend @filter(not eq(age, 10)) (first: 2) { name } } }')
    assert len(out["q"][0]["friend"]) == 2


def test_math_division_twice(env):
    # regression: two '/' in one query must not lex as a regex literal
    out = run(env, '''{
      var(func: uid(1)) { a as age }
      q(func: uid(1)) { half: math(a / 2 / 1) }
    }''')
    assert out["q"][0]["half"] == 19.0


def test_uid_in_hex(env):
    out = run(env, '{ q(func: has(friend)) @filter(uid_in(friend, 0x6)) { name } }')
    assert {x["name"] for x in out["q"]} == {"Andrea"}


def test_uid_var_in_filter(env):
    # regression: uid(x) in @filter must register the var dependency even when
    # the defining block comes later in the query text
    out = run(env, '''{
      q(func: has(name)) @filter(uid(a)) { name }
      a as var(func: eq(age, 15)) { uid }
    }''')
    assert {x["name"] for x in out["q"]} == {"Rick Grimes", "Glenn Rhee"}


def test_negative_first(env):
    out = run(env, '{ q(func: has(name), orderasc: age, first: -2) { age } }')
    assert [x["age"] for x in out["q"]] == [19, 38]


def test_orderdesc_string_prefix(env):
    # regression: descending string order with prefix pairs
    s, snap = env
    out = run(env, '{ q(func: eq(age, 15), orderdesc: name) { name } }')
    assert [x["name"] for x in out["q"]] == ["Rick Grimes", "Glenn Rhee"]


def test_eq_list_form_valvar(env):
    # regression: eq(val(x), [v1, v2]) must flatten at parse time so the
    # value-var compare path matches ANY listed value
    out = run(env, '''{
      v as var(func: has(name)) { a as age }
      q(func: eq(val(a), [15, 17]), orderasc: val(a)) @filter(uid(v)) { name }
    }''')
    assert [x["name"] for x in out["q"]] == [
        "Rick Grimes", "Glenn Rhee", "Daryl Dixon"]


def test_eq_list_form_root(env):
    out = run(env, '{ q(func: eq(name, ["Andrea", "Carl"]), orderasc: name) { name } }')
    assert [x["name"] for x in out["q"]] == ["Andrea", "Carl"]


def test_eq_empty_list(env):
    # degenerate eq(pred, []) matches nothing instead of crashing
    out = run(env, '{ q(func: eq(name, [])) { name } }')
    assert out == {}


def test_two_math_var_defs_one_block(env):
    # regression: two `x as math(...)` defs in one block must not collide on
    # the "math" output key
    out = run(env, '''{
      q(func: uid(1)) { a as math(1 + 1) b as math(2 + 2) name }
    }''')
    row = out["q"][0]
    assert row["a"] == 2 and row["b"] == 4 and row["name"] == "Michonne"


def test_eq_count_list_form(env):
    # eq(count(pred), [n1, n2]) matches ANY listed degree — root and filter
    out = run(env, '{ q(func: eq(count(friend), [1, 4]), orderasc: name) { name } }')
    assert [x["name"] for x in out["q"]] == [
        "Andrea", "Daryl Dixon", "Glenn Rhee", "Michonne", "Rick Grimes"]
    out = run(env, '''{
      q(func: has(name), orderasc: name) @filter(eq(count(friend), [1, 4])) { name }
    }''')
    assert [x["name"] for x in out["q"]] == [
        "Andrea", "Daryl Dixon", "Glenn Rhee", "Michonne", "Rick Grimes"]


def test_facet_eq_list_form(env):
    # @facets(eq(key, [v1, v2])) matches ANY listed facet value
    out = run(env, '''{
      q(func: uid(1)) { friend @facets(eq(close, [true, false])) { name } }
    }''')
    names = {x["name"] for x in out["q"][0]["friend"]}
    assert names == {"Andrea", "Daryl Dixon", "Glenn Rhee", "Rick Grimes"}
    out = run(env, '''{
      q(func: uid(1)) { friend @facets(eq(close, [false])) { name } }
    }''')
    names = {x["name"] for x in out["q"][0]["friend"]}
    assert names == {"Andrea", "Daryl Dixon"}


def test_ineq_missing_rhs_errors(env):
    with pytest.raises(Exception):
        run(env, '{ q(func: lt(age)) { name } }')


def test_regexp_case_insensitive():
    # values store raw-case trigrams; /rick/i must still find "Rick Grimes"
    # through the case-variant trigram probe (not a full scan)
    from dgraph_tpu.api.server import Node
    n = Node()
    n.alter(schema_text="name: string @index(trigram) .")
    n.mutate(set_nquads="""
        _:a <name> "Rick Grimes" .
        _:b <name> "GLENN RHEE" .
        _:c <name> "daryl dixon" .
    """, commit_now=True)
    out, _ = n.query('{ q(func: regexp(name, /rick/i)) { name } }')
    assert [x["name"] for x in out["q"]] == ["Rick Grimes"]
    out, _ = n.query('{ q(func: regexp(name, /GRIMES|rhee/i)) { name } }')
    assert {x["name"] for x in out["q"]} == {"Rick Grimes", "GLENN RHEE"}
    out, _ = n.query('{ q(func: regexp(name, /dixon$/i)) { name } }')
    assert [x["name"] for x in out["q"]] == ["daryl dixon"]


def test_lang_fallback_chain():
    from dgraph_tpu.api.server import Node
    n = Node()
    n.alter(schema_text="name: string @index(exact) @lang .")
    n.mutate(set_nquads='_:a <name> "Alice" .\n_:a <name> "Alicia"@es .\n'
                        '_:b <name> "Bobby"@en .', commit_now=True)
    out, _ = n.query('{ q(func: eq(name, "Alice")) { name@fr:es:. } }')
    assert out == {"q": [{"name@fr:es:.": "Alicia"}]}
    out, _ = n.query('{ q(func: has(name)) { name@fr:. } }')
    assert {r["name@fr:."] for r in out["q"]} == {"Alice", "Bobby"}
    out, _ = n.query('{ q(func: has(name)) { name@fr:de } }')
    assert out == {}                      # chain without "." can miss


def test_count_reverse_at_root(env):
    # eq(count(~friend), n): degree compare over the REVERSE index
    out = run(env, '{ q(func: eq(count(~friend), 2), orderasc: name) { name } }')
    assert [x["name"] for x in out["q"]] == ["Andrea", "Michonne"]


def test_uid_in_list_form(env):
    out = run(env, '{ q(func: has(friend)) @filter(uid_in(friend, [0x2, 0x6])) '
                   '{ name } }')
    assert {x["name"] for x in out["q"]} == {"Michonne", "Andrea"}


def test_has_reverse_at_root(env):
    # has(~friend): nodes with INCOMING friend edges (Carl has none outgoing
    # but one incoming; uid2/3 have incoming from Michonne, etc.)
    out = run(env, '{ q(func: has(~friend), orderasc: name) { name } }')
    assert [x["name"] for x in out["q"]] == [
        "Andrea", "Carl", "Daryl Dixon", "Glenn Rhee", "Michonne",
        "Rick Grimes"]


def test_bad_lang_chain_rejected():
    from dgraph_tpu.query.dql import ParseError, parse
    with pytest.raises(ParseError):
        parse('{ q(func: has(name)) { name@en:2 } }')
    with pytest.raises(ParseError):
        parse('{ q(func: has(name)) { name@en: } }')


def test_checkpwd_child():
    from dgraph_tpu.api.server import Node
    n = Node()
    n.alter(schema_text="name: string @index(exact) .\npwd: password .")
    n.mutate(set_nquads='_:a <name> "A" .\n'
                        '_:a <pwd> "secret123"^^<xs:password> .',
             commit_now=True)
    out, _ = n.query('{ q(func: eq(name, "A")) { checkpwd(pwd, "secret123") } }')
    assert out == {"q": [{"checkpwd(pwd)": True}]}
    out, _ = n.query('{ q(func: eq(name, "A")) { checkpwd(pwd, "wrong1") } }')
    assert out == {"q": [{"checkpwd(pwd)": False}]}


def test_fulltext_stemming_inflections():
    from dgraph_tpu.api.server import Node
    n = Node()
    n.alter(schema_text="bio: string @index(fulltext) .\n"
                        "name: string @index(exact) .")
    n.mutate(set_nquads='_:a <name> "A" .\n'
                        '_:a <bio> "loves hiking in the mountains" .\n'
                        '_:b <name> "B" .\n_:b <bio> "agreed to run fast" .',
             commit_now=True)
    out, _ = n.query('{ q(func: alloftext(bio, "mountain hike")) { name } }')
    assert out == {"q": [{"name": "A"}]}
    out, _ = n.query('{ q(func: anyoftext(bio, "agree running")) { name } }')
    assert out == {"q": [{"name": "B"}]}


def test_math_comparisons_and_cond():
    from dgraph_tpu.api.server import Node
    n = Node()
    n.alter(schema_text="name: string @index(exact) .\nscore: float .")
    n.mutate(set_nquads='_:a <name> "hi" .\n_:a <score> "7.5"^^<xs:float> .\n'
                        '_:b <name> "lo" .\n_:b <score> "3.0"^^<xs:float> .',
             commit_now=True)
    out, _ = n.query('''{
      var(func: has(score)) { s as score
        c as math(cond(s > 5.0, 1, 0))
        d as math(cond(s <= 3.0, 1, 0)) }
      q(func: has(score), orderasc: name) { name val(c) val(d) }
    }''')
    assert out["q"] == [{"name": "hi", "val(c)": 1, "val(d)": 0},
                       {"name": "lo", "val(c)": 0, "val(d)": 1}]


def test_facet_filter_not_and_parens(env):
    out = run(env, '''{
      q(func: uid(1)) { friend @facets(NOT eq(close, true)) { name } }
    }''')
    names = {x["name"] for x in out["q"][0]["friend"]}
    assert names == {"Daryl Dixon", "Andrea"}
    out = run(env, '''{
      q(func: uid(1)) { friend @facets((eq(close, true))) { name } }
    }''')
    names = {x["name"] for x in out["q"][0]["friend"]}
    assert names == {"Rick Grimes", "Glenn Rhee"}


def test_list_value_predicates():
    from dgraph_tpu.api.server import Node
    n = Node()
    n.alter(schema_text="nick: [string] @index(term) .\n"
                        "name: string @index(exact) .")
    n.mutate(set_json={"name": "Jay", "nick": ["jj", "jbird"]},
             commit_now=True)
    out, _ = n.query('{ q(func: eq(name, "Jay")) { nick } }')
    assert out == {"q": [{"nick": ["jbird", "jj"]}]}
    out, _ = n.query('{ q(func: anyofterms(nick, "jbird")) { name } }')
    assert out == {"q": [{"name": "Jay"}]}
    ju = n.query('{ q(func: eq(name, "Jay")) { uid } }')[0]["q"][0]["uid"]
    n.mutate(del_nquads=f'<{ju}> <nick> "jj" .', commit_now=True)
    out, _ = n.query('{ q(func: eq(name, "Jay")) { nick } }')
    assert out == {"q": [{"nick": "jbird"}]}
    n.mutate(del_nquads=f'<{ju}> <nick> * .', commit_now=True)
    out, _ = n.query('{ q(func: has(nick)) { uid } }')
    assert out == {}


def test_value_edge_facets():
    from dgraph_tpu.api.server import Node
    n = Node()
    n.alter(schema_text="name: string @index(exact) .")
    n.mutate(set_nquads='_:a <name> "Fay" (since=2021-01-01T00:00:00, '
                        'by="import") .', commit_now=True)
    out, _ = n.query('{ q(func: eq(name, "Fay")) { name @facets } }')
    row = out["q"][0]
    assert row["name"] == "Fay" and row["name|by"] == "import"
    assert row["name|since"].startswith("2021-01-01")
    out, _ = n.query('{ q(func: eq(name, "Fay")) { name @facets(src: by) } }')
    assert out["q"][0] == {"name": "Fay", "name|src": "import"}


def test_groupby_numeric_fast_path_matches_generic():
    """Single-numeric-key groupby takes the vectorized path and must equal
    the generic per-uid path exactly (keys, order, members, aggregates)."""
    from dgraph_tpu.api.server import Node
    from dgraph_tpu.query import groupby as gbmod

    n = Node()
    n.alter(schema_text="name: string .\nage: int .\nscore: float .")
    quads = []
    for i in range(1, 40):
        quads.append(f'<0x{i:x}> <name> "p{i}" .')
        quads.append(f'<0x{i:x}> <age> "{20 + i % 5}"^^<xs:int> .')
        quads.append(f'<0x{i:x}> <score> "{i}.25"^^<xs:float> .')
    n.mutate(set_nquads="\n".join(quads), commit_now=True)
    q = ('{ q(func: has(name)) @groupby(age) { count(uid) m : max(val(s)) } '
         '  var(func: has(name)) { s as score } }')
    spy = {"n": 0}
    real = gbmod._numeric_single_key_groups

    def counting(*a, **kw):
        out = real(*a, **kw)
        if out is not None:
            spy["n"] += 1
        return out

    gbmod._numeric_single_key_groups = counting
    try:
        fast, _ = n.query(q)
    finally:
        gbmod._numeric_single_key_groups = real
    assert spy["n"] == 1, "fast path was not taken"
    gbmod._numeric_single_key_groups = lambda *a, **kw: None
    try:
        generic, _ = n.query(q)
    finally:
        gbmod._numeric_single_key_groups = real
    assert fast == generic
    counts = {g["age"]: g["count"] for g in fast["q"][0]["@groupby"]}
    assert sum(counts.values()) == 39 and len(counts) == 5


def test_groupby_fast_path_exactness_guards():
    """Cases where the float64 mirror is lossy/ambiguous must take the
    generic path and keep exact semantics (review r4)."""
    from dgraph_tpu.api.server import Node

    n = Node()
    n.alter(schema_text="big: int .\nx: float .\nwhen: datetime .")
    n.mutate(set_nquads=f'''
        <0x1> <big> "{2**53}"^^<xs:int> .
        <0x2> <big> "{2**53 + 1}"^^<xs:int> .
        <0x3> <x> "NaN"^^<xs:float> .
        <0x4> <x> "1.5"^^<xs:float> .
        <0x5> <when> "2021-01-01T00:00:00+00:00" .
        <0x6> <when> "2021-01-01T01:00:00+01:00" .
    ''', commit_now=True)
    # distinct int64 keys above 2^53 stay distinct
    out, _ = n.query('{ q(func: has(big)) @groupby(big) { count(uid) } }')
    assert len(out["q"][0]["@groupby"]) == 2
    # stored float NaN keeps its group
    out, _ = n.query('{ q(func: has(x)) @groupby(x) { count(uid) } }')
    assert len(out["q"][0]["@groupby"]) == 2
    # same instant, different tz offsets: distinct display keys
    out, _ = n.query('{ q(func: has(when)) @groupby(when) { count(uid) } }')
    assert len(out["q"][0]["@groupby"]) == 2


# ---- uid(0x…) roots: membership in the snapshot is a binary search (PR 39)

@pytest.fixture(scope="module")
def sparse_env():
    """Known uids with gaps: 10 20 30 40 through `follows` (30 and 40 as
    objects only), 25 through the value-only predicate `name`."""
    s = Store()
    for e in parse_schema("name: string .\nfollows: uid ."):
        s.set_schema(e)
    for a, b in [(10, 20), (20, 30), (10, 40)]:
        idx.add_mutation_with_index(s, DirectedEdge(a, "follows", object_uid=b), 1)
    idx.add_mutation_with_index(
        s, DirectedEdge(25, "name", value=Val(TypeID.STRING, "value-only")), 1)
    s.commit(1, 2, list(s.lists.keys()))
    return s, build_snapshot(s, read_ts=3)


@pytest.fixture(scope="module")
def empty_env():
    s = Store()
    for e in parse_schema("follows: uid ."):
        s.set_schema(e)
    return s, build_snapshot(s, read_ts=3)


ROOT_CASES = {
    "known": ("sparse", [20]),
    "unknown-below-smallest": ("sparse", [3]),
    "unknown-between-two": ("sparse", [27]),
    "unknown-above-largest": ("sparse", [41]),
    "duplicates-unsorted": ("sparse", [40, 10, 40, 27, 10, 30]),
    "several-some-unknown": ("sparse", [10, 11, 20, 39, 40, 1000]),
    "more-roots-than-known": ("sparse", list(range(1, 64))),
    "empty-store": ("empty", [7, 3, 7]),
    "value-only-predicate": ("sparse", [25, 26]),
}


@pytest.mark.parametrize("case", list(ROOT_CASES))
def test_root_uids_match_isin_reference(case, sparse_env, empty_env):
    which, roots = ROOT_CASES[case]
    s, snap = sparse_env if which == "sparse" else empty_env
    present = _known_uids(snap)
    assert (len(present) == 0) == (which == "empty")
    want = np.unique(np.asarray(roots, np.int64))
    ref = want[np.isin(want, present)] if len(present) else want
    gq = dql.parse("{ q(func: uid(%s)) { uid } }"
                   % ", ".join(map(str, roots))).queries[0]
    got = Executor(snap, s.schema)._root_uids(gq)
    assert got.dtype == np.int64
    np.testing.assert_array_equal(got, ref)
    if which == "sparse":
        assert set(got.tolist()) == set(roots) & {10, 20, 25, 30, 40}


def test_root_uids_never_scan_the_store(sparse_env, monkeypatch):
    """The store-sized pass cannot come back unnoticed: np.isin / np.in1d
    raise for the length of the call."""
    s, snap = sparse_env
    _known_uids(snap)           # the snapshot's cache is built outside
    ex = Executor(snap, s.schema)
    gq = dql.parse("{ q(func: uid(40, 27, 10)) { uid } }").queries[0]

    def scan(*a, **k):
        raise AssertionError("_root_uids scanned the store")

    monkeypatch.setattr(np, "isin", scan)
    if hasattr(np, "in1d"):
        monkeypatch.setattr(np, "in1d", scan)
    got = ex._root_uids(gq)
    monkeypatch.undo()
    assert got.tolist() == [10, 40]
