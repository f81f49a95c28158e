"""The `graphalytics` cell (config g500-s18-graphalytics-1chip, traffic
graphalytics, ops gx_pr / gx_wcc): whole rehearsal runs on the CPU at
scale 10, the control, the ops' own reference against the program's host
oracles (dgraph_tpu/query/analytics.py), the gx.* readers on hand-made
RunData, and that the cell came as new files and new entries. Every entry
is looked up by name, never by its place in a list."""

import json
import os
import subprocess

import numpy as np
import pytest

import run as runmod
from harness import graphalytics as gx
from harness.graph import Graph
from harness.roofline import peaks
from test_runs import BENCH, ROOT, bench_json, listed, run_cell

CELL = "graphalytics"
CONFIG = "g500-s18-graphalytics-1chip"
SHARES_GRAPH_WITH = "g500-s18-1chip"
OPS = ("gx_pr", "gx_wcc")
PARENT = "df7ab5abd4b60b5e3f9b111095e82d09131d940e"
READERS = ["gx.pr_p50_ms", "gx.wcc_p50_ms", "gx.device_run_share",
           "gx.wcc_rounds_per_op", "gx.pr_roofline", "gx.wcc_roofline",
           "gx.idle_share", "gx.host_ms_per_op", "gx.dispatch_ms_per_op",
           "gx.wait_ms_per_op", "gx.post_ms_per_op", "gx.compile_s",
           "gx.compiles_in_window"]
# read from a profiler trace's device plane, which a CPU has none of
TRACE_ONLY = {"gx.pr_roofline", "gx.wcc_roofline", "gx.idle_share"}


def config(name):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)


def values(out):
    return {k: v["value"] for k, v in out["metrics"].items()}


def test_rehearsal_traced_line_prints_every_reader():
    out, res = run_cell(CELL, "--trace", "1", seed=2147487101)
    assert out["correct"] is False and out["checks_passed"] is True, \
        res.stderr[-2000:]
    assert listed("per_layer", CELL) == READERS
    assert set(out["metrics"]) == set(READERS) - TRACE_ONLY
    m = values(out)
    assert m["gx.pr_p50_ms"] > 0 and m["gx.wcc_p50_ms"] > 0
    assert m["gx.device_run_share"] == 100
    assert m["gx.wcc_rounds_per_op"] >= 2
    assert m["gx.compiles_in_window"] == 0 and m["gx.compile_s"] > 0
    for name in ("gx.dispatch_ms_per_op", "gx.wait_ms_per_op",
                 "gx.post_ms_per_op", "gx.host_ms_per_op"):
        assert m[name] > 0, name
    by_op = out["info"]["by_op"]
    assert set(by_op) == set(OPS) and abs(by_op["gx_pr"]
                                          - by_op["gx_wcc"]) <= 1


def test_rehearsal_untraced_line():
    out, res = run_cell(CELL, "--trace", "0", seed=2147487102)
    assert out["checks_passed"] is True, res.stderr[-2000:]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"ops_per_s", "p95_ms", "setup_s"}
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert all(v["value"] <= v["limit"] for v in out["compared"].values())


def test_control_fails_both_ops():
    out, res = run_cell(CELL, "--trace", "0", "--control", "approx")
    assert out["checks_passed"] is False and out["control"] == "approx"
    n = out["compared"]["wrong_or_failed_in_window"]
    assert n["value"] > n["limit"] and out["failed"] == n["value"]
    assert out["failed"] == out["attempted"]      # every request, both ops
    assert set(out["info"]["by_op"]) == set(OPS)
    problems = [ln for ln in res.stderr.splitlines()
                if ln.startswith("problem:")]
    assert {ln.split()[1] for ln in problems} == set(OPS)


def test_the_configuration_is_the_search_graph_node_and_flags():
    cfg, base = config(CONFIG), config(SHARES_GRAPH_WITH)
    for key in ("data", "chips", "serve_args", "edge_limit"):
        assert cfg[key] == base[key], key
    assert "--mesh" not in cfg["serve_args"]
    assert list(cfg["reduced"]) == ["scale"]
    assert cfg["algorithms"]["pr"] == {"iterations": gx.ITERATIONS,
                                       "damping": gx.DAMPING}
    assert cfg["probes"] == gx.PROBES
    assert cfg["device_kernels"] == {"gx_pr": "pb.analytics_pr",
                                     "gx_wcc": "pb.analytics_wcc"}
    with open(os.path.join(BENCH, "traffic", f"{CELL}.json")) as f:
        traffic = json.load(f)
    assert (traffic["loop"], traffic["clients"], traffic["ops"]) == \
        ("closed", 1, {"gx_pr": 0.5, "gx_wcc": 0.5})
    bj = bench_json()
    entry = next(c for c in bj["configs"] if c["name"] == CONFIG)
    assert entry["source"] == cfg["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == ["scale"]
    cell = next(w for w in bj["workloads"] if w["name"] == CELL)
    assert cell == {"name": CELL, "config": CONFIG, "traffic": CELL,
                    "chips": 1, "why": cell["why"]}
    assert len(cell["why"]) <= 200
    mine = {m["name"]: m for m in bj["per_layer"] if m["name"] in READERS}
    assert set(mine) == set(READERS)
    assert all(m["workloads"] == [CELL] for m in mine.values())
    assert mine["gx.compile_s"]["moves"] == "setup_s"
    assert mine["gx.compiles_in_window"]["moves"] == "p95_ms"


@pytest.mark.parametrize("seed", [13, 2147487313])
def test_the_ops_reference_agrees_with_the_programs_host_oracles(seed):
    """pr_reference / wcc_reference (scipy over the benchmark's own CSR)
    against dgraph_tpu's host oracles over the same edges, every vertex;
    and answer() passes verify() while a nudged answer does not."""
    from dgraph_tpu.query import analytics as an

    data = dict(config(CONFIG)["data"], scale=8)
    g = Graph.from_config(data, seed)
    src = np.repeat(g.subjects, np.diff(g.indptr))
    nodes = np.unique(np.concatenate([src, g.indices]))
    s = np.searchsorted(nodes, src).astype(np.int32)
    t = np.searchsorted(nodes, g.indices).astype(np.int32)
    r, _ = an.pagerank_host(s, t, len(nodes), tol=-1.0,
                            max_iters=gx.ITERATIONS)
    ref = gx.pr_reference(g)
    assert np.allclose(ref["rank"][nodes], r, rtol=1e-12, atol=0)
    lab = nodes[an.cc_host(s, t, len(nodes))]
    assert np.array_equal(gx.wcc_reference(g)["label"][nodes], lab)
    ctx = type("Ctx", (), {"g": g, "edge_limit": 1 << 30})
    rng = np.random.default_rng(seed)
    for name in OPS:
        op = runmod.load_module("ops", name)
        p = op.draw(ctx, rng)
        assert len(p["uids"]) == gx.PROBES == len(set(p["uids"]))
        got = op.answer(g, p)
        assert op.verify(g, p, got)[0] is None
        method, path, body = op.request(p, ctx)
        assert (method, path) == ("POST", "/analytics")
        assert json.loads(body)["kind"] == name[3:]
        u = p["uids"][0]
        if name == "gx_pr":
            bad = dict(got, values={**got["values"],
                                    u: got["values"][u] * (1 + 3e-4)})
            stats = op.verify(g, p, got)[1]
            assert stats == {"edges": 10 * len(g.indices),
                             "nodes": 10 * len(nodes)}
        else:
            bad = dict(got, labels={**got["labels"], u: u + 1})
        assert op.verify(g, p, bad)[0]
        assert op.verify(g, p, op.parse({}))[0]


def _reqs(op, n, need, lo=10.0, step=0.05):
    return [{"op": op, "ok": True, "wrong": False, "judged": True,
             "t_send": lo + i * step, "t_done": lo + i * step + 0.04,
             "needed_bytes": need} for i in range(n)]


DEV = 'dgraph_analytics_device_runs_total{kind="%s"}'
HOST = 'dgraph_analytics_host_runs_total{kind="%s",reason="%s"}'
STEPS = 'dgraph_analytics_steps_total{kind="%s"}'

COUNTER_CASES = {
    # name: (reader, series before, after, what is read)
    "every_run_on_the_device": (
        "gx.device_run_share", {DEV % "pr": 2, DEV % "wcc": 2},
        {DEV % "pr": 52, DEV % "wcc": 52}, 100.0),
    "a_quarter_on_the_host": (
        "gx.device_run_share", {DEV % "pr": 0, DEV % "wcc": 0},
        {DEV % "pr": 30, DEV % "wcc": 30, HOST % ("wcc", "overlay"): 20},
        75.0),
    "a_program_without_the_counters": (
        "gx.device_run_share", {}, {}, None),
    "six_rounds_a_wcc": (
        "gx.wcc_rounds_per_op", {DEV % "pr": 0, DEV % "wcc": 1,
                                 STEPS % "wcc": 6},
        {DEV % "pr": 0, DEV % "wcc": 11, STEPS % "wcc": 66}, 6.0),
    "no_wcc_ran": (
        "gx.wcc_rounds_per_op", {DEV % "pr": 0, DEV % "wcc": 0},
        {DEV % "pr": 5, DEV % "wcc": 0}, None),
}


@pytest.mark.parametrize("case", sorted(COUNTER_CASES))
def test_counter_readers(case):
    name, before, after, want = COUNTER_CASES[case]
    rd = runmod.RunData()
    rd.before = {"prom": {k: float(v) for k, v in before.items()}}
    rd.after = {"prom": {k: float(v) for k, v in after.items()}}
    got = runmod.load_module("layer_metrics", name).read(rd)
    assert got == (want if want is None else pytest.approx(want))


def test_roofline_readers_take_each_programs_own_seconds():
    rd = runmod.RunData()
    rd.reqs = _reqs("gx_pr", 40, 300e6) + _reqs("gx_wcc", 40, 30e6, 10.02)
    read = lambda n: runmod.load_module("layer_metrics", n).read(rd)  # noqa
    assert read("gx.pr_roofline") is None          # no trace
    rd.device = {"kind": "TPU v5 lite"}
    rd.trace_span = (10.0, 12.0)
    rd.trace = {"busy_s": 1.9, "window_s": 2.0, "device_ops": [
        ["program jit_analytics_pr", 1.2], ["program jit_analytics_wcc",
                                            0.6], ["op fusion.3", 0.5]]}
    hbm = peaks("TPU v5 lite")["hbm_bytes_per_s"]
    done_pr = sum(1 for r in rd.reqs if r["op"] == "gx_pr"
                  and 10.0 <= r["t_done"] <= 12.0)
    done_wcc = sum(1 for r in rd.reqs if r["op"] == "gx_wcc"
                   and 10.0 <= r["t_done"] <= 12.0)
    assert read("gx.pr_roofline") == pytest.approx(
        100 * done_pr * 300e6 / hbm / 1.2)
    assert read("gx.wcc_roofline") == pytest.approx(
        100 * done_wcc * 30e6 / hbm / 0.6)
    assert read("gx.idle_share") == pytest.approx(5.0)
    rd.trace["device_ops"] = [["program jit_bfs_dist", 1.9]]
    assert read("gx.pr_roofline") is None and read("gx.wcc_roofline") is None


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
    assert len(names) > 90
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
    added = {m["name"] for m in new["per_layer"][len(old["per_layer"]):]}
    assert set(READERS) <= added
