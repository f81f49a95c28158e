"""The `khop-par22` cell (config g500-s18-khop-par22-1chip, traffic
khop-par22, op khop1): the configuration against the one it shares its
graph with, whole rehearsal runs on the CPU — the cell as it is (a CPU
serves the traversal from the host mirror) and with the kernel tier forced
(tests/serve_stacked.py: the stacked pb.recurse_fused_multi launch in
interpret mode) — the control, a child that hands two stacked requests
each other's counts, the `par.*` readers on hand-made RunData, and that
the cell came as new files and new entries."""

import json
import os
import subprocess

import pytest

import run as runmod
from test_runs import BENCH, ROOT, bench_json, listed, run_cell

CELL = "khop-par22"
CONFIG = "g500-s18-khop-par22-1chip"
SHARES_GRAPH_WITH = "g500-s18-khop-1chip"
STACKED = os.path.join(BENCH, "tests", "serve_stacked.py")
# read from a profiler trace's device plane, which a CPU has none of
TRACE_ONLY = {"par.recurse_roofline", "par.idle_share"}
PAR = ["par.khop1_p50_ms", "par.batch_occupancy_mean", "par.stacked_share",
       "par.batch_wait_ms_per_op", "par.gate_wait_ms_per_op",
       "par.host_ms_per_op", "par.dispatch_ms_per_op", "par.wait_ms_per_op",
       "par.post_ms_per_op", "par.device_path_share", "par.idle_share",
       "par.recurse_roofline", "par.compiles_in_window", "par.compile_s"]


def config(name):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)


def values(out):
    return {k: v["value"] for k, v in out["metrics"].items()}


def test_the_configuration_is_khops_graph_node_and_flags():
    cfg, base = config(CONFIG), config(SHARES_GRAPH_WITH)
    for key in ("data", "chips", "serve_args", "edge_limit"):
        assert cfg[key] == base[key], key
    assert cfg["name"] == CONFIG != base["name"]      # its own store cache
    assert set(base["guarantees"]) < set(cfg["guarantees"])
    assert list(cfg["reduced"]) == ["scale"]
    assert cfg["device_kernels"] == {
        "khop1": ["batch.recurse", "pb.recurse_fused"]}
    with open(os.path.join(BENCH, "traffic", f"{CELL}.json")) as f:
        traffic = json.load(f)
    assert (traffic["loop"], traffic["clients"], traffic["ops"]) == \
        ("closed", 22, {"khop1": 1.0})
    entry = next(c for c in bench_json()["configs"] if c["name"] == CONFIG)
    assert entry["source"] == cfg["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == ["scale"]


def test_rehearsal_traced_line_prints_every_reader():
    """The cell as it is: on a CPU no task reaches the batcher, and the
    readers say 0, not nothing."""
    out, res = run_cell(CELL, "--trace", "1", seed=2147486101)
    assert out["correct"] is False and out["checks_passed"] is True, \
        res.stderr[-2000:]
    assert listed("per_layer", CELL) == PAR
    # the newest requests of a scale-10 window mostly repeat a root and
    # are answered by the result cache, which leaves no trace to read
    assert set(PAR) - TRACE_ONLY - {"par.device_path_share"} <= \
        set(out["metrics"]) <= set(PAR) - TRACE_ONLY
    m = values(out)
    assert m["par.khop1_p50_ms"] > 0 and m["par.host_ms_per_op"] > 0
    assert m["par.compiles_in_window"] == 0
    for name in ("par.batch_occupancy_mean", "par.stacked_share",
                 "par.batch_wait_ms_per_op", "par.gate_wait_ms_per_op",
                 "par.dispatch_ms_per_op", "par.wait_ms_per_op"):
        assert m[name] == 0, name
    assert out["info"]["by_op"] == {"khop1": out["attempted"]}


def test_rehearsal_untraced_line():
    out, res = run_cell(CELL, "--trace", "0", seed=2147486102)
    assert out["checks_passed"] is True, res.stderr[-2000:]
    assert out["attempted"] > 22 and out["failed"] == 0
    assert set(out["metrics"]) == {"ops_per_s", "p95_ms", "setup_s"}
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert all(v["value"] <= v["limit"] for v in out["compared"].values())


def test_stacked_launches_pass_the_checks():
    """The kernel tier forced: most requests ride a launch of two or more,
    every one gets the count of its own root, nothing loads inside the
    window, and every sampled request's trace names its launch."""
    out, res = run_cell(CELL, "--trace", "1", seed=2147486103, scale=13,
                        wrapper=STACKED, env={"BENCH_FAULT": "none"})
    assert out["checks_passed"] is True, res.stderr[-2000:]
    assert set(out["metrics"]) == set(PAR) - TRACE_ONLY
    m = values(out)
    assert m["par.stacked_share"] > 50 and m["par.batch_occupancy_mean"] > 2
    assert m["par.batch_wait_ms_per_op"] > 0
    assert m["par.dispatch_ms_per_op"] > 0 and m["par.wait_ms_per_op"] > 0
    assert m["par.device_path_share"] == 100
    assert m["par.compiles_in_window"] == 0 and m["par.compile_s"] > 0
    assert "pb.recurse_fused_multi" in res.stderr


def test_control_is_not_correct():
    out, _ = run_cell(CELL, "--trace", "0", "--control", "approx")
    assert out["checks_passed"] is False and out["control"] == "approx"
    n = out["compared"]["wrong_or_failed_in_window"]
    assert n["value"] > n["limit"] and out["failed"] == n["value"]


def test_two_requests_handed_each_others_counts_are_not_correct():
    """Every request is answered, by a count that is some root's: only
    the comparison with each request's OWN root can tell."""
    out, res = run_cell(CELL, "--trace", "0", seed=2147486104,
                        wrapper=STACKED, env={"BENCH_FAULT": "swap_pair"})
    assert out["checks_passed"] is False and out["correct"] is False
    assert out["compared"]["unanswered"]["value"] == 0
    assert out["compared"]["wrong_or_failed_in_window"]["value"] > 0
    assert out["failed"] > 0
    assert "the reference counts" in res.stderr


TASKS = "dgraph_batch_tasks_total"
LAUNCHES = "dgraph_batch_formed_total"
ALONE = 'dgraph_batch_occupancy_bucket{le="1"}'
STAGE = 'dgraph_stage_us_total{stage="%s"}'
REQS = "dgraph_stage_requests_total"

READER_CASES = {
    # name: (reader, series before, after, what is read)
    "ten_launches_took_fifty_tasks": (
        "par.batch_occupancy_mean", {TASKS: 7, LAUNCHES: 7},
        {TASKS: 57, LAUNCHES: 17}, 5.0),
    "no_task_reached_the_batcher": (
        "par.batch_occupancy_mean", {TASKS: 0, LAUNCHES: 0},
        {TASKS: 0, LAUNCHES: 0}, 0.0),
    "a_program_without_the_batcher": (
        "par.batch_occupancy_mean", {}, {}, None),
    "four_of_fifty_ran_alone": (
        "par.stacked_share", {TASKS: 7, ALONE: 7},
        {TASKS: 57, ALONE: 11}, 92.0),
    "every_task_ran_alone": (
        "par.stacked_share", {TASKS: 0, ALONE: 0},
        {TASKS: 40, ALONE: 40}, 0.0),
    "no_task_to_share_out": (
        "par.stacked_share", {TASKS: 3, ALONE: 3}, {TASKS: 3, ALONE: 3},
        0.0),
    "a_program_without_the_histogram": (
        "par.stacked_share", {TASKS: 0}, {TASKS: 40}, None),
    "followers_waited_six_ms_each": (
        "par.batch_wait_ms_per_op", {STAGE % "batch.wait": 0, REQS: 10},
        {STAGE % "batch.wait": 600_000, REQS: 110}, 6.0),
    "nobody_rode_a_batch": (
        "par.batch_wait_ms_per_op", {STAGE % "batch.wait": 0, REQS: 10},
        {STAGE % "batch.wait": 0, REQS: 110}, 0.0),
    "a_program_without_the_batch_stage": (
        "par.batch_wait_ms_per_op", {REQS: 10}, {REQS: 110}, None),
    "half_a_ms_in_the_gates_queue": (
        "par.gate_wait_ms_per_op", {STAGE % "gate.wait": 1000, REQS: 0},
        {STAGE % "gate.wait": 51_000, REQS: 100}, 0.5),
    "a_program_without_the_gate_stage": (
        "par.gate_wait_ms_per_op", {REQS: 10}, {REQS: 110}, None),
    "host_is_exec_and_prep": (
        "par.host_ms_per_op",
        {STAGE % "exec": 0, STAGE % "exec.prep": 0, REQS: 0},
        {STAGE % "exec": 190_000, STAGE % "exec.prep": 10_000,
         STAGE % "batch.wait": 999_000, REQS: 100}, 2.0),
    "leaders_dispatch_spread_over_everyone": (
        "par.dispatch_ms_per_op", {STAGE % "dev.dispatch": 0, REQS: 0},
        {STAGE % "dev.dispatch": 30_000, REQS: 100}, 0.3),
    "wait_is_the_fetch_and_unsplit_windows": (
        "par.wait_ms_per_op", {REQS: 0},
        {STAGE % "dev.wait": 70_000, STAGE % "dev.window": 10_000,
         REQS: 100}, 0.8),
    "post_is_every_requests": (
        "par.post_ms_per_op", {REQS: 0},
        {STAGE % "dev.post": 120_000, REQS: 100}, 1.2),
}


@pytest.mark.parametrize("case", sorted(READER_CASES))
def test_counter_readers(case):
    name, before, after, want = READER_CASES[case]
    rd = runmod.RunData()
    rd.before = {"prom": {k: float(v) for k, v in before.items()}}
    rd.after = {"prom": {k: float(v) for k, v in after.items()}}
    got = runmod.load_module("layer_metrics", name).read(rd)
    assert got == (want if want is None else pytest.approx(want))


def _reqs(n_good, n_wrong, lo, step, need):
    return [{"op": "khop1", "ok": True, "wrong": i >= n_good,
             "judged": True, "t_send": lo + i * step,
             "t_done": lo + i * step + 0.004, "needed_bytes": need}
            for i in range(n_good + n_wrong)]


def test_readers_of_the_log_the_trace_and_the_evidence():
    rd = runmod.RunData()
    rd.reqs = _reqs(100, 0, 10.0, 0.01, 4000)
    for r, ms in zip(rd.reqs, range(100)):
        r["t_done"] = r["t_send"] + (ms + 1) / 1e3
    read = lambda n: runmod.load_module("layer_metrics", n).read(rd)  # noqa
    assert read("par.khop1_p50_ms") == pytest.approx(50.0)
    # no trace, no evidence: nothing to read
    for name in ("par.idle_share", "par.recurse_roofline",
                 "par.device_path_share"):
        assert read(name) is None
    rd.kernel_evidence = [
        {"op": "khop1", "found": True}, {"op": "khop1", "found": True},
        {"op": "khop1", "found": False}, {"op": "shortest", "found": False}]
    assert read("par.device_path_share") == pytest.approx(200 / 3)
    # 2 s traced, the chip busy for 1.5 of them
    rd.trace, rd.trace_span = {"busy_s": 1.5, "window_s": 2.0}, (10.2, 10.7)
    rd.device = {"kind": "TPU v5 lite"}
    assert read("par.idle_share") == pytest.approx(25.0)
    done = sum(1 for r in rd.reqs if 10.2 <= r["t_done"] <= 10.7)
    from harness.roofline import peaks
    assert read("par.recurse_roofline") == pytest.approx(
        100 * done * 4000 / peaks("TPU v5 lite")["hbm_bytes_per_s"] / 1.5)
    rd.before = {"programs_loaded": 9, "compiles": {"compile_ms_total": 2500}}
    rd.after = {"programs_loaded": 11}
    assert read("par.compiles_in_window") == 2.0
    assert read("par.compile_s") == 2.5


def _git(*args):
    return subprocess.run(["git", *args], cwd=ROOT, capture_output=True,
                          timeout=60)


PARENT = "aaaff50b4ac9c08c50bc8633c7b28ed0f634b7b9"


def test_the_cell_came_as_new_files_and_new_entries():
    """Every file under benchmarks/ at the parent commit is byte for byte
    what it was; BENCHMARK.json kept every entry it had, in place, and
    gained — next after them — one configuration, one cell and the par.*
    readers (a later PR may have appended more after these)."""
    if _git("cat-file", "-e", PARENT + "^{commit}").returncode != 0:
        pytest.skip("no git history with the parent commit here")
    names = _git("ls-tree", "-r", "--name-only", PARENT, "--",
                 "benchmarks").stdout.decode().split()
    assert len(names) > 55
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
    assert new["configs"][len(old["configs"])]["name"] == CONFIG
    cell = new["workloads"][len(old["workloads"])]
    assert cell == {"name": CELL, "config": CONFIG, "traffic": CELL,
                    "chips": 1, "why": cell["why"]}
    assert "22 clients" in cell["why"] and len(cell["why"]) <= 200
    added = new["per_layer"][len(old["per_layer"]):][:len(PAR)]
    assert [m["name"] for m in added] == PAR
    assert all(m["workloads"] == [CELL] for m in added)
    moves = {m["name"]: m["moves"] for m in added}
    assert moves.pop("par.compiles_in_window") == "p95_ms"
    assert moves.pop("par.compile_s") == "setup_s"
    assert set(moves.values()) == {"ops_per_s"}
