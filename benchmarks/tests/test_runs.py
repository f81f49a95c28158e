"""Whole runs of the harness on the CPU (`--rehearsal`, toy scale): the
result line's shape, the controls, the timed path broken underneath, and
that a configuration, a traffic mix, an op and a per-layer metric are each
added as files plus entries, never by editing a file that is there."""

import json
import os
import shutil
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
FAULTY = os.path.join(BENCH, "tests", "serve_faulty.py")

# run.py with a child of the test's choosing in the serve child's place
WITH_WRAPPER = ("import sys; sys.path.insert(0, 'benchmarks'); import run; "
                "run.Run.serve_wrapper = sys.argv.pop(1); "
                "sys.exit(run.main())")


def run_cell(workload, *extra, root=ROOT, env=None, seed=424242, scale=10,
             seconds=4, wrapper=None):
    head = [sys.executable, "-c", WITH_WRAPPER, wrapper] if wrapper else \
        [sys.executable, os.path.join(root, "benchmarks", "run.py")]
    cmd = head + ["--workload", workload, "--seed", str(seed), "--seconds",
                  str(seconds), "--rehearsal", "--scale", str(scale), *extra]
    res = subprocess.run(cmd, cwd=root, capture_output=True, text=True,
                         timeout=600, env={**os.environ, **(env or {})})
    assert res.returncode == 0, res.stderr[-3000:]
    return json.loads(res.stdout.strip().splitlines()[-1]), res


def bench_json():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def checkout(dst, bj):
    """A checkout under dst: the program by symlink, a copy of benchmarks/
    and the given BENCHMARK.json."""
    for name in ("dgraph_tpu", "native"):
        os.symlink(os.path.join(ROOT, name), dst / name)
    shutil.copytree(BENCH, dst / "benchmarks",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    (dst / "BENCHMARK.json").write_text(json.dumps(bj))
    return dst


def listed(section, cell, bj=None):
    return [m["name"] for m in (bj or bench_json())[section]
            if "workloads" not in m or cell in m["workloads"]]


# read from a profiler trace's device plane, which a CPU has none of
TRACE_ONLY = {"device.idle_share", "kernel.traverse_roofline"}
CELL = "search"


def test_rehearsal_untraced_line():
    out, res = run_cell(CELL, "--trace", "0", seed=2147483999)
    assert out["correct"] is False and out["rehearsal"] is True
    assert out["checks_passed"] is True, res.stderr[-2000:]
    assert out["device"]["platform"] == "cpu"
    assert set(out["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == set(listed("end_to_end", CELL))
    for name, m in out["metrics"].items():
        assert m["value"] > 0 and m["unit"]
    assert list(out)[-1] == "compared"
    assert all(v["value"] <= v["limit"] for v in out["compared"].values())
    assert "compared:" in res.stderr.strip().splitlines()[-1]


def test_rehearsal_traced_line():
    out, _ = run_cell(CELL, "--trace", "1")
    assert out["correct"] is False and out["checks_passed"] is True
    assert set(out["metrics"]) == set(listed("per_layer", CELL)) - TRACE_ONLY
    assert out["metrics"]["setup.compiles_in_window"]["value"] == 0


def test_a_second_run_of_a_seed_finds_the_store():
    run_cell(CELL, "--trace", "0", seed=77)
    out, _ = run_cell(CELL, "--trace", "0", seed=77)
    assert out["info"]["timings"]["store"] == "cached"
    assert out["checks_passed"] is True


def test_control_is_not_correct():
    out, _ = run_cell(CELL, "--trace", "0", "--control", "approx")
    assert out["checks_passed"] is False and out["control"] == "approx"
    n = out["compared"]["wrong_or_failed_in_window"]
    assert n["value"] > n["limit"] and out["failed"] == n["value"]


def test_broken_timed_path_is_not_correct():
    """An answer altered where it is produced, under a whole run."""
    out, _ = run_cell(CELL, "--trace", "0", wrapper=FAULTY,
                      env={"BENCH_FAULT": "alter_answer"})
    assert out["checks_passed"] is False and out["correct"] is False
    assert out["compared"]["wrong_or_failed_in_window"]["value"] > 0
    assert out["failed"] > 0


def test_unknown_workload_and_open_loop_print_no_result(tmp_path):
    res = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", "nope",
         "--seed", "1", "--seconds", "1", "--rehearsal"], cwd=ROOT,
        capture_output=True, text=True, timeout=120)
    assert res.returncode != 0 and res.stdout.strip() == ""


def test_bare_directory_prints_no_result(tmp_path):
    """Only BENCHMARK.json and the files under `paths`: no program to run."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    res = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", "search",
         "--seed", "1", "--seconds", "1", "--trace", "0", "--rehearsal",
         "--scale", "8"], cwd=tmp_path, capture_output=True, text=True,
        timeout=300, env={**os.environ, "PYTHONPATH": ""})
    assert res.returncode != 0 and res.stdout.strip() == ""


@pytest.fixture
def grown(tmp_path):
    """A checkout in which a later PR added a configuration (`serve
    --mesh` on four devices), a traffic mix, an op and a per-layer metric:
    new files and new BENCHMARK.json entries only."""
    checkout(tmp_path, {})
    b = tmp_path / "benchmarks"
    before = {p: p.read_bytes() for p in b.rglob("*") if p.is_file()}
    cfg = json.loads((b / "configs" / "g500-s18-1chip.json").read_text())
    cfg.update(name="g500-s18-mesh4", chips=4, serve_args=["--mesh"],
               device_kernels={"shortest": "mesh.bfs"})
    (b / "configs" / "g500-s18-mesh4.json").write_text(json.dumps(cfg))
    (b / "traffic" / "hops.json").write_text(json.dumps({
        "name": "hops", "loop": "closed", "clients": 2,
        "ops": {"shortest": 0.5, "degree": 0.5}}))
    (b / "ops" / "degree.py").write_text(
        'def draw(ctx, rng):\n'
        '    s = ctx.g.subjects\n'
        '    return {"u": int(s[rng.integers(len(s))])}\n\n\n'
        'def request(p, ctx):\n'
        '    return ("POST", f"/query?edgeLimit={ctx.edge_limit}",\n'
        '            f"{{ q(func: uid({hex(p[\'u\'])})) '
        '{{ count(follows) }} }}")\n\n\n'
        'def parse(data):\n    return data.get("q", [])\n\n\n'
        'def answer(g, p):\n'
        '    return [{"count(follows)": int(g.degree[p["u"]])}]\n\n\n'
        'def verify(g, p, got):\n'
        '    ok = got == answer(g, p)\n'
        '    return (None if ok else f"{got}"), {"edges": 0, "nodes": 1}\n')
    (b / "layer_metrics" / "op.degree_p50_ms.py").write_text(
        "from harness import stats\n\n\ndef read(run):\n"
        "    lat = stats.latencies_ms(run.reqs, op=\"degree\")\n"
        "    return stats.percentile(lat, 50) if lat else None\n")
    bj = bench_json()
    bj["configs"].append({
        "name": "g500-s18-mesh4", "source": "as g500-s18-1chip",
        "file": "benchmarks/configs/g500-s18-mesh4.json",
        "reduced": ["scale"], "why": "serve --mesh on four chips"})
    bj["workloads"].append({
        "name": "mesh-hops", "config": "g500-s18-mesh4", "traffic": "hops",
        "chips": 4, "why": "test"})
    for m in bj["per_layer"]:
        if m["name"] == "kernel.edges_per_s":
            m["workloads"].append("mesh-hops")
    bj["per_layer"].append({
        "name": "op.degree_p50_ms", "unit": "ms", "better": "lower",
        "source": "host_clock", "layer": "served path by op",
        "moves": "ops_per_s", "workloads": ["mesh-hops"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bj))
    return tmp_path, before


def test_config_mix_op_and_metric_are_added_as_files(grown):
    root, before = grown
    # scale 13: past the mesh tier's floor, so `--mesh` shards a tablet
    out, res = run_cell("mesh-hops", "--trace", "1", root=str(root),
                        scale=13, seconds=5)
    assert out["checks_passed"] is True, res.stderr[-2000:]
    assert out["device"]["count"] == 4 and out["device"]["platform"] == "cpu"
    assert set(out["info"]["by_op"]) == {"shortest", "degree"}
    assert out["metrics"]["op.degree_p50_ms"]["value"] > 0
    assert out["metrics"]["kernel.edges_per_s"]["value"] > 0
    out0, _ = run_cell("mesh-hops", "--trace", "0", root=str(root),
                       scale=13, seconds=5)
    assert out0["metrics"]["ops_per_s"]["value"] > 0
    b = root / "benchmarks"
    for p, data in before.items():
        assert p.read_bytes() == data, f"{p} was edited"


def test_open_loop_is_refused_until_it_has_a_generator(grown):
    root, _ = grown
    t = root / "benchmarks" / "traffic" / "hops.json"
    t.write_text(t.read_text().replace('"closed"', '"open"'))
    res = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", "mesh-hops",
         "--seed", "1", "--seconds", "1", "--trace", "0", "--rehearsal",
         "--scale", "8"], cwd=root, capture_output=True, text=True,
        timeout=120)
    assert res.returncode != 0 and res.stdout.strip() == ""
    assert "open" in res.stderr and "no generator" in res.stderr
