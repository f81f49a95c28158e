"""The `graphalytics-lcc` cell (config g500-s18-graphalytics-lcc-1chip,
traffic graphalytics-lcc, op gx_lcc): whole rehearsal runs on the CPU at
scale 10, the control, the op's reference against a brute-force count, the
lcc.* readers on hand-made RunData, and that the cell came as new files and
new entries. Every entry is looked up by name, never by its place in a
list."""

import json
import os
import subprocess

import numpy as np
import pytest

import run as runmod
from harness import lcc
from harness.graph import Graph
from harness.roofline import peaks
from test_runs import BENCH, ROOT, bench_json, listed, run_cell

CELL = "graphalytics-lcc"
CONFIG = "g500-s18-graphalytics-lcc-1chip"
SHARES_GRAPH_WITH = "g500-s18-graphalytics-1chip"
PARENT = "174af9ce8a4ecfb1fd6f4cb891acf3e194c99d7e"
READERS = ["lcc.p50_ms", "lcc.device_run_share", "lcc.wait_ms_per_op",
           "lcc.host_ms_per_op", "lcc.idle_share", "lcc.compile_s",
           "lcc.compiles_in_window", "lcc.compare_share", "lcc.roofline"]
# read from a profiler trace's device plane, which a CPU has none of
TRACE_ONLY = {"lcc.idle_share", "lcc.roofline"}


def config(name):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)


def values(out):
    return {k: v["value"] for k, v in out["metrics"].items()}


def test_rehearsal_traced_line_prints_every_reader():
    out, res = run_cell(CELL, "--trace", "1", seed=2147487201)
    assert out["correct"] is False and out["checks_passed"] is True, \
        res.stderr[-2000:]
    assert listed("per_layer", CELL) == READERS
    assert set(out["metrics"]) == set(READERS) - TRACE_ONLY
    m = values(out)
    assert m["lcc.p50_ms"] > 0 and m["lcc.device_run_share"] == 100
    assert 0 < m["lcc.compare_share"] < 100
    assert m["lcc.compiles_in_window"] == 0 and m["lcc.compile_s"] > 0
    assert m["lcc.wait_ms_per_op"] > 0 and m["lcc.host_ms_per_op"] > 0
    assert set(out["info"]["by_op"]) == {"gx_lcc"}


def test_rehearsal_untraced_line():
    out, res = run_cell(CELL, "--trace", "0", seed=2147487202)
    assert out["checks_passed"] is True, res.stderr[-2000:]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"ops_per_s", "p95_ms", "setup_s"}
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert all(v["value"] <= v["limit"] for v in out["compared"].values())


def test_control_fails_every_request():
    out, res = run_cell(CELL, "--trace", "0", "--control", "approx")
    assert out["checks_passed"] is False and out["control"] == "approx"
    n = out["compared"]["wrong_or_failed_in_window"]
    assert n["value"] > n["limit"] and out["failed"] == out["attempted"]
    problems = [ln for ln in res.stderr.splitlines()
                if ln.startswith("problem:")]
    assert problems and all(ln.split()[1] == "gx_lcc" for ln in problems)


def test_the_configuration_is_the_graphalytics_graph_node_and_flags():
    cfg, base = config(CONFIG), config(SHARES_GRAPH_WITH)
    for key in ("data", "chips", "serve_args", "edge_limit"):
        assert cfg[key] == base[key], key
    assert "--mesh" not in cfg["serve_args"]
    assert list(cfg["reduced"]) == ["scale"]
    assert cfg["algorithms"] == {"lcc": {}} and cfg["probes"] == lcc.PROBES
    assert cfg["device_kernels"] == {"gx_lcc": "pb.analytics_lcc"}
    with open(os.path.join(BENCH, "traffic", f"{CELL}.json")) as f:
        traffic = json.load(f)
    assert (traffic["loop"], traffic["clients"], traffic["ops"]) == \
        ("closed", 1, {"gx_lcc": 1.0})
    bj = bench_json()
    entry = next(c for c in bj["configs"] if c["name"] == CONFIG)
    assert entry["source"] == cfg["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == ["scale"]
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    cell = next(w for w in bj["workloads"] if w["name"] == CELL)
    assert cell == {"name": CELL, "config": CONFIG, "traffic": CELL,
                    "chips": 1, "why": cell["why"]}
    assert len(cell["why"]) <= 200
    mine = {m["name"]: m for m in bj["per_layer"] if m["name"] in READERS}
    assert set(mine) == set(READERS)
    assert all(m["workloads"] == [CELL] for m in mine.values())
    assert mine["lcc.compile_s"]["moves"] == "setup_s"
    assert mine["lcc.compiles_in_window"]["moves"] == "p95_ms"
    assert {m["moves"] for n, m in mine.items()
            if n not in ("lcc.compile_s", "lcc.compiles_in_window")} == \
        {"ops_per_s"}


def _brute(g):
    """t(v) by uid from the dense symmetrised adjacency: diag(A^3) / 2."""
    a = np.zeros((g.n, g.n), dtype=np.int64)
    src = np.repeat(g.subjects, np.diff(g.indptr))
    a[src, g.indices] = 1
    a[g.indices, src] = 1
    np.fill_diagonal(a, 0)
    return np.einsum("ij,jk,ki->i", a, a, a) // 2, a.sum(axis=1)


@pytest.mark.parametrize("seed", [13, 2147487313])
def test_the_reference_agrees_with_a_brute_force_count(seed):
    """reference() (scipy over the benchmark's own CSR, oriented) against
    diag(A^3) / 2 over a dense matrix, every uid; answer() passes verify()
    while a nudged answer does not."""
    data = dict(config(CONFIG)["data"], scale=8)
    g = Graph.from_config(data, seed)
    tri, deg = _brute(g)
    ref = lcc.reference(g)
    assert np.array_equal(ref["tri"], tri)
    with np.errstate(divide="ignore", invalid="ignore"):
        want = np.where(deg > 1, tri / (deg * (deg - 1) / 2), 0.0)
    assert np.allclose(ref["lcc"], want, rtol=1e-12, atol=0)
    assert ref["total"] == tri.sum() // 3 > 0
    assert ref["edges"] == len(g.indices)
    assert ref["sum"] == pytest.approx(want.sum(), rel=1e-12)
    ctx = type("Ctx", (), {"g": g, "edge_limit": 1 << 30})
    op = runmod.load_module("ops", "gx_lcc")
    p = op.draw(ctx, np.random.default_rng(seed))
    assert len(p["uids"]) == lcc.PROBES == len(set(p["uids"]))
    got = op.answer(g, p)
    problem, stats = op.verify(g, p, got)
    assert problem is None
    assert op.needed_bytes(stats) == 4 * ref["merge"] + 8 * ref["nodes"]
    method, path, body = op.request(p, ctx)
    assert (method, path) == ("POST", "/analytics")
    assert json.loads(body)["kind"] == "lcc"
    u = next(x for x in p["uids"] if ref["lcc"][x] > 0)
    for bad in (dict(got, triangles={**got["triangles"],
                                     u: got["triangles"][u] + 1}),
                dict(got, values={**got["values"],
                                  u: got["values"][u] * (1 + 3e-4)}),
                dict(got, total=got["total"] - 1),
                dict(got, sum=got["sum"] * (1 - 3e-4))):
        assert op.verify(g, p, bad)[0]
    assert op.verify(g, p, op.parse({}))[0]


DEV = 'dgraph_analytics_device_runs_total{kind="lcc"}'
HOST = 'dgraph_analytics_host_runs_total{kind="lcc",reason="%s"}'
OTHER = 'dgraph_analytics_host_runs_total{kind="pr",reason="overlay"}'

COUNTER_CASES = {
    # name: (reader, series before, after, what is read)
    "every_run_on_the_device": (
        "lcc.device_run_share", {DEV: 2}, {DEV: 32, OTHER: 5}, 100.0),
    "a_quarter_on_the_host": (
        "lcc.device_run_share", {DEV: 0}, {DEV: 30, HOST % "overlay": 6,
                                           HOST % "one_way": 4}, 75.0),
    "a_program_without_the_counter": ("lcc.device_run_share", {}, {}, None),
    "compares_a_merge_needs": (
        "lcc.compare_share", {lcc.COMPARES: 1000, lcc.MERGE: 10},
        {lcc.COMPARES: 21000, lcc.MERGE: 210}, 1.0),
    "no_compares_in_the_window": (
        "lcc.compare_share", {lcc.COMPARES: 5, lcc.MERGE: 1},
        {lcc.COMPARES: 5, lcc.MERGE: 1}, None),
    "a_program_without_the_compare_counters": (
        "lcc.compare_share", {}, {}, None),
}


@pytest.mark.parametrize("case", sorted(COUNTER_CASES))
def test_counter_readers(case):
    name, before, after, want = COUNTER_CASES[case]
    rd = runmod.RunData()
    rd.before = {"prom": {k: float(v) for k, v in before.items()}}
    rd.after = {"prom": {k: float(v) for k, v in after.items()}}
    got = runmod.load_module("layer_metrics", name).read(rd)
    assert got == (want if want is None else pytest.approx(want))


def test_roofline_reader_takes_the_programs_own_seconds():
    rd = runmod.RunData()
    rd.reqs = [{"op": "gx_lcc", "ok": True, "wrong": False, "judged": True,
                "t_send": 10.0 + 0.3 * i, "t_done": 10.25 + 0.3 * i,
                "needed_bytes": 4.3e9} for i in range(12)]
    read = lambda n: runmod.load_module("layer_metrics", n).read(rd)  # noqa
    assert read("lcc.roofline") is None             # no trace
    rd.device = {"kind": "TPU v5 lite"}
    rd.trace_span = (10.0, 12.0)
    rd.trace = {"busy_s": 1.9, "window_s": 2.0, "device_ops": [
        ["program jit_analytics_lcc", 1.8], ["op fusion.3", 0.5]]}
    done = sum(1 for r in rd.reqs if 10.0 <= r["t_done"] <= 12.0)
    hbm = peaks("TPU v5 lite")["hbm_bytes_per_s"]
    assert read("lcc.roofline") == pytest.approx(
        100 * done * 4.3e9 / hbm / 1.8)
    assert read("lcc.idle_share") == pytest.approx(5.0)
    rd.trace["device_ops"] = [["program jit_analytics_pr", 1.9]]
    assert read("lcc.roofline") is None


def _git(*args):
    return subprocess.run(["git", *args], cwd=ROOT, capture_output=True,
                          timeout=60)


def test_the_cell_came_as_new_files_and_new_entries():
    """Every file under benchmarks/ at the parent commit is byte for byte
    what it was; BENCHMARK.json kept every entry it had, in place."""
    if _git("cat-file", "-e", PARENT + "^{commit}").returncode != 0:
        pytest.skip("no git history with the parent commit here")
    names = _git("ls-tree", "-r", "--name-only", PARENT, "--",
                 "benchmarks").stdout.decode().split()
    assert len(names) > 100
    for name in names:
        with open(os.path.join(ROOT, name), "rb") as f:
            assert f.read() == _git("show", f"{PARENT}:{name}").stdout, \
                f"{name} was edited"
    old = json.loads(_git("show", f"{PARENT}:BENCHMARK.json").stdout)
    new = bench_json()
    for key in ("command", "paths", "run_seconds", "end_to_end"):
        assert new[key] == old[key]
    for key in ("configs", "workloads", "per_layer"):
        assert new[key][:len(old[key])] == old[key], key
    assert [w["name"] for w in new["workloads"][len(old["workloads"]):]] \
        == [CELL]
    added = [m["name"] for m in new["per_layer"][len(old["per_layer"]):]]
    assert added == READERS
