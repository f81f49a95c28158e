"""The `khop` cell (config g500-s18-khop-1chip, traffic khop, ops
khop1/2/3/6): whole rehearsal runs on the CPU at scale 10, the control,
the ops' own reference against the program's level-by-level one
(dgraph_tpu/models/khop.py), the readers of the two new counters on
hand-made RunData, and that the cell came as new files and new entries:
no file the benchmark had at the parent commit differs."""

import json
import os
import subprocess

import numpy as np
import pytest

import run as runmod
from harness import khop
from harness.graph import Graph
from test_runs import ROOT, bench_json, listed, run_cell

CELL = "khop"
CONFIG = "g500-s18-khop-1chip"
OPS = ("khop1", "khop2", "khop3", "khop6")
PARENT = "f6baf2ccae1a32623d1f60d54f4292c1b27ffced"
# read from a profiler trace's device plane, which a CPU has none of
TRACE_ONLY = {"khop.recurse_roofline", "khop.idle_share"}


def test_rehearsal_traced_line_prints_every_reader():
    out, res = run_cell(CELL, "--trace", "1", seed=2147485123)
    assert out["correct"] is False and out["checks_passed"] is True, \
        res.stderr[-2000:]
    names = set(listed("per_layer", CELL))
    assert names == {m["name"] for m in bench_json()["per_layer"]
                     if m["name"].startswith("khop.")} and len(names) == 15
    assert set(out["metrics"]) == names - TRACE_ONLY
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert min(m[f"khop.k{k}_p50_ms"] for k in (1, 2, 3, 6)) > 0
    assert m["khop.compiles_in_window"] == 0
    assert m["khop.materialized_per_op"] == 0
    assert m["khop.host_ms_per_op"] > 0
    # a CPU serves the traversal from the host mirror: no device window
    assert m["khop.device_path_share"] == 0
    # the deck holds each k equally often
    by_op = out["info"]["by_op"]
    assert set(by_op) == set(OPS)
    assert max(by_op.values()) - min(by_op.values()) <= 1


def test_rehearsal_untraced_line():
    out, res = run_cell(CELL, "--trace", "0", seed=2147485124)
    assert out["checks_passed"] is True, res.stderr[-2000:]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"ops_per_s", "p95_ms", "setup_s"}
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert all(v["value"] <= v["limit"] for v in out["compared"].values())


def test_control_is_not_correct():
    out, _ = run_cell(CELL, "--trace", "0", "--control", "approx")
    assert out["checks_passed"] is False and out["control"] == "approx"
    n = out["compared"]["wrong_or_failed_in_window"]
    assert n["value"] > n["limit"] and out["failed"] == n["value"]


@pytest.mark.parametrize("seed", [11, 2147484321, 2147491999])
def test_the_ops_reference_agrees_with_the_programs(seed):
    """bfs_tree + the root rule (harness/khop.py) against the level
    expansion with its edge-seen array (dgraph_tpu/models/khop.py), on the
    configuration's own generator at a small scale."""
    from dgraph_tpu.models.khop import khop_levels

    with open(os.path.join(ROOT, "benchmarks", "configs",
                           f"{CONFIG}.json")) as f:
        data = dict(json.load(f)["data"], scale=8)
    g = Graph.from_config(data, seed)
    src = np.repeat(g.subjects, np.diff(g.indptr))
    rng = np.random.default_rng(seed)
    ctx = type("Ctx", (), {"g": g, "edge_limit": 1 << 30})
    for name in OPS:
        op = runmod.load_module("ops", name)
        for _ in range(6):
            p = op.draw(ctx, rng)
            assert p["k"] == int(name[4:]) and g.degree[p["root"]] > 0
            levels, union = khop_levels(src, g.indices, [p["root"]], p["k"])
            assert op.answer(g, p) == {"count": len(union)}
            problem, stats = op.verify(g, p, {"count": len(union)})
            assert problem is None
            # the plain BFS reads each out-edge of the vertices it expands
            # once; the edge-dedup traversal reads no other edge
            assert 0 < stats["edges"] <= len(src)
            assert stats["nodes"] == len(set(union.tolist()) | {p["root"]})
            assert op.verify(g, p, {"count": len(union) + 1})[0]
            assert op.verify(g, p, op.parse({"khop": []}))[0]
        method, path, body = op.request(p, ctx)
        assert (method, path) == ("POST", f"/query?edgeLimit={1 << 30}")
        assert f"@recurse(depth: {p['k']})" in body and "v as follows" in body
    assert khop.parse({"khop": [{"count": 7}]}) == {"count": 7}


LEVELS = 'dgraph_recurse_levels_total{state="%s"}'
MAT = "dgraph_recurse_materialized_total"
REQS = "dgraph_stage_requests_total"

READER_CASES = {
    # name: (reader, series before, after, what is read)
    "a_third_of_the_levels_were_empty": (
        "khop.empty_level_share", {LEVELS % "live": 10, LEVELS % "empty": 2},
        {LEVELS % "live": 210, LEVELS % "empty": 102}, 100.0 / 3),
    "no_level_ran": (
        "khop.empty_level_share", {LEVELS % "live": 0, LEVELS % "empty": 0},
        {LEVELS % "live": 0, LEVELS % "empty": 0}, 0.0),
    "a_program_without_the_level_counter": (
        "khop.empty_level_share", {}, {}, None),
    "nothing_materialised": (
        "khop.materialized_per_op", {MAT: 4, REQS: 10},
        {MAT: 4, REQS: 110}, 0.0),
    "three_matrices_a_request": (
        "khop.materialized_per_op", {MAT: 0, REQS: 10},
        {MAT: 300, REQS: 110}, 3.0),
    "a_program_without_the_matrix_counter": (
        "khop.materialized_per_op", {REQS: 10}, {REQS: 110}, None),
}


@pytest.mark.parametrize("case", sorted(READER_CASES))
def test_counter_readers(case):
    name, before, after, want = READER_CASES[case]
    rd = runmod.RunData()
    rd.before = {"prom": {k: float(v) for k, v in before.items()}}
    rd.after = {"prom": {k: float(v) for k, v in after.items()}}
    got = runmod.load_module("layer_metrics", name).read(rd)
    assert got == (want if want is None else pytest.approx(want))


def _git(*args):
    return subprocess.run(["git", *args], cwd=ROOT, capture_output=True,
                          timeout=60)


def test_the_cell_came_as_new_files_and_new_entries():
    """Every file under benchmarks/ at the parent commit is byte for byte
    what it was; BENCHMARK.json kept every entry it had, in place, and
    gained one configuration, one cell and the khop.* readers."""
    if _git("cat-file", "-e", PARENT + "^{commit}").returncode != 0:
        pytest.skip("no git history with the parent commit here")
    names = _git("ls-tree", "-r", "--name-only", PARENT, "--",
                 "benchmarks").stdout.decode().split()
    assert len(names) > 40
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
    assert [c["name"] for c in new["configs"][len(old["configs"]):]] == \
        [CONFIG]
    assert new["workloads"][len(old["workloads"]):] == [
        {"name": CELL, "config": CONFIG, "traffic": CELL, "chips": 1,
         "why": new["workloads"][-1]["why"]}]
    added = new["per_layer"][len(old["per_layer"]):]
    assert all(m["name"].startswith("khop.") and m["workloads"] == [CELL]
               for m in added) and len(added) == 15
    with open(os.path.join(ROOT, new["configs"][-1]["file"])) as f:
        cfg = json.load(f)
    with open(os.path.join(ROOT, old["configs"][0]["file"])) as f:
        base = json.load(f)
    for key in ("data", "chips", "serve_args", "edge_limit", "guarantees"):
        assert cfg[key] == base[key], key
    assert cfg["device_kernels"] == dict.fromkeys(OPS, "pb.recurse_fused")
