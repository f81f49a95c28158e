"""The per-layer metrics that read what the stage clock says beside a
stage's wall time (PR 38; harness/stage_cpu.py): the CPU time of each
stage, the two stages before `do_POST`, the accept loop's busy time, the
collector's pauses, the process's CPU seconds. Each reader on hand-made
RunData — its value, None on a program without its series, and where it
divides, nothing to divide by — their entries looked up by name, and a
whole traced rehearsal of `khop-par22` whose line holds every one."""

import pytest

import run as runmod
from test_runs import bench_json, run_cell

WALL = 'dgraph_stage_us_total{stage="%s"}'
CPU = 'dgraph_stage_cpu_us_total{stage="%s"}'
REQS = "dgraph_stage_requests_total"
CPU_REQS = "dgraph_stage_cpu_requests_total"
PAUSE = 'dgraph_gc_pause_us_total{generation="%s"}'
COUNT = 'dgraph_gc_collections_total{generation="%s"}'
LOOP = "dgraph_http_accept_loop_us_total"
PROC = "dgraph_process_cpu_seconds_total"

# microseconds a request: (wall, CPU)
STAGES = {"http.accept": (30000, 0), "http.head": (9000, 300),
          "http.read": (800, 200), "http.write": (700, 100),
          "parse": (400, 100), "plan": (1600, 400),
          "exec": (7000, 2200), "exec.prep": (1000, 200),
          "dev.dispatch": (1200, 250), "dev.wait": (800, 40),
          "dev.window": (200, 10), "dev.post": (7500, 1100),
          "batch.wait": (9500, 60), "gate.wait": (0, 0),
          "encode": (300, 150), "gc": (500, 500)}
ALL = ["search", "khop", "khop-par22"]
CELLS = {
    "http.accept_ms_per_op": ALL, "http.head_ms_per_op": ALL,
    "http.accept_loop_busy_share": ALL, "stage.cpu_ms_per_op": ALL,
    "stage.offcpu_share": ALL, "proc.cpu_cores": ALL,
    "frontend.cpu_ms_per_op": ALL, "plan.cpu_ms_per_op": ALL,
    "exec.host_cpu_ms_per_op": ALL, "exec.dispatch_cpu_ms_per_op": ALL,
    "exec.post_cpu_ms_per_op": ALL, "encode.cpu_ms_per_op": ALL,
    "gc.pause_ms_per_op": ALL, "gc.full_pause_ms": ["khop-par22"],
    "trace.outside_share": ["khop", "khop-par22"],
}
WORKING_WALL = sum(w for s, (w, _) in STAGES.items()
                   if s not in ("http.accept", "gate.wait", "batch.wait",
                                "dev.wait", "dev.window"))
WORKING_CPU = sum(c for s, (_, c) in STAGES.items()
                  if s not in ("http.accept", "gate.wait", "batch.wait",
                               "dev.wait", "dev.window"))
# reader -> what it reads when every closed request grew STAGES, over a
# window of 10 s in which the loop was busy 6 s, the process used 11 CPU
# seconds, and the collector ran 900 + 90 + 2 times for 40 + 30 + 1000 ms
WANT = {
    "http.accept_ms_per_op": 30.0,
    "http.head_ms_per_op": 9.0,
    "http.accept_loop_busy_share": 60.0,
    "stage.cpu_ms_per_op": sum(c for _, c in STAGES.values()) / 1000.0,
    "stage.offcpu_share": 100.0 * (1 - WORKING_CPU / WORKING_WALL),
    "proc.cpu_cores": 1.1,
    "frontend.cpu_ms_per_op": 0.6,
    "plan.cpu_ms_per_op": 0.5,
    "exec.host_cpu_ms_per_op": 2.4,
    "exec.dispatch_cpu_ms_per_op": 0.25,
    "exec.post_cpu_ms_per_op": 1.1,
    "encode.cpu_ms_per_op": 0.15,
    "gc.pause_ms_per_op": 1070.0 / 200,
    "gc.full_pause_ms": 500.0,
    "trace.outside_share": 100.0 * (1 - sum(w for w, _ in STAGES.values())
                                    / 80000.0),
}


def run_data(requests=200, wall=True, cpu=True, extras=True, full=2):
    """A window of 10 s in which `requests` clocks closed, each STAGES
    long and 80 ms at the client, one in eight of them reading the CPU
    clock, after a warm-up that had closed 7 (one reading it)."""
    rd = runmod.RunData()
    before, after = {}, {}

    def grow(key, start, by):
        before[key], after[key] = float(start), float(start + by)

    if wall:
        grow(REQS, 7, requests)
        for s, (w, _) in STAGES.items():
            grow(WALL % s, 7 * w, requests * w)
    if cpu:
        grow(CPU_REQS, 1, requests // 8)
        for s, (_, c) in STAGES.items():
            grow(CPU % s, c, requests // 8 * c)
    if extras:
        grow(LOOP, 123456, 6_000_000)
        grow(PROC, 40.5, 11.0)
        for gen, n, us in (("0", 900, 40_000), ("1", 90, 30_000),
                           ("2", full, 1_000_000 if full else 0)):
            grow(COUNT % gen, 50, n)
            grow(PAUSE % gen, 9999, us)
    rd.before, rd.after = {"prom": before}, {"prom": after}
    rd.t0, rd.seconds = 100.0, 10.0
    step = 9.92 / max(requests - 1, 1)      # the last answer at 110.0
    rd.reqs = [{"op": "khop1", "ok": True, "t_send": 100.0 + i * step,
                "t_done": 100.08 + i * step} for i in range(requests)]
    return rd


def read(name, rd):
    return runmod.load_module("layer_metrics", name).read(rd)


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_value_and_a_program_without_its_series(name):
    assert read(name, run_data()) == pytest.approx(WANT[name])
    # PR 37's program: the wall stages and nothing this PR added
    parent = run_data(cpu=False, extras=False)
    for s in ("http.accept", "http.head", "gc"):
        for d in (parent.before["prom"], parent.after["prom"]):
            del d[WALL % s]
    if name == "trace.outside_share":       # its arithmetic on the parent
        inside = sum(w for s, (w, _) in STAGES.items()
                     if s not in ("http.accept", "http.head", "gc"))
        assert read(name, parent) == pytest.approx(
            100.0 * (1 - inside / 80000.0))
    else:
        assert read(name, parent) is None
    assert read(name, run_data(wall=False, cpu=False, extras=False)) is None


PER_REQUEST = sorted(set(WANT) - {"http.accept_loop_busy_share",
                                  "proc.cpu_cores", "gc.full_pause_ms"})


@pytest.mark.parametrize("name", PER_REQUEST)
def test_no_request_closed_in_the_window_reads_nothing(name):
    assert read(name, run_data(requests=0)) is None


def test_nothing_to_divide_by():
    # no full collection in the window: 0.0, not a division
    assert read("gc.full_pause_ms", run_data(full=0)) == 0.0
    # a window without a request is still `seconds` long
    rd = run_data(requests=0)
    assert read("http.accept_loop_busy_share", rd) == pytest.approx(60.0)
    assert read("proc.cpu_cores", rd) == pytest.approx(1.1)
    # every working stage at 0 (a window of waits only)
    rd = run_data()
    for d in (rd.before["prom"], rd.after["prom"]):
        for k in list(d):
            if k.startswith(WALL.split("%s")[0]) and \
                    k not in (WALL % "batch.wait", WALL % "dev.wait"):
                d[k] = 0.0
    assert read("stage.offcpu_share", rd) == 0.0


def test_a_stage_without_a_cpu_line_counts_zero():
    rd = run_data()
    for d in (rd.before["prom"], rd.after["prom"]):
        del d[CPU % "encode"]
    assert read("encode.cpu_ms_per_op", rd) == 0.0
    assert read("stage.cpu_ms_per_op", rd) == pytest.approx(
        WANT["stage.cpu_ms_per_op"] - 0.15)


def test_the_window_runs_to_its_last_answer():
    rd = run_data()
    rd.reqs[-1]["t_done"] = 112.0          # one answer 2 s after the rest
    assert read("proc.cpu_cores", rd) == pytest.approx(11.0 / 12.0)


@pytest.mark.parametrize("name", sorted(CELLS))
def test_entry_by_name(name):
    by_name = {m["name"]: m for m in bench_json()["per_layer"]}
    m = by_name[name]
    assert m["workloads"] == CELLS[name]
    assert (m["source"], m["better"]) == ("program_counter", "lower")
    assert m["moves"] == ("p95_ms" if name.startswith("gc.")
                          else "ops_per_s")
    cells = {w["name"] for w in bench_json()["workloads"]}
    assert set(m["workloads"]) <= cells


def test_traced_rehearsal_of_par22_prints_every_new_metric():
    out, res = run_cell("khop-par22", "--trace", "1", seed=2147487038)
    assert out["checks_passed"] is True, res.stderr[-2000:]
    got = {k: v["value"] for k, v in out["metrics"].items()}
    assert set(CELLS) <= set(got), sorted(set(CELLS) - set(got))
    assert got["http.accept_ms_per_op"] > 0 and got["http.head_ms_per_op"] > 0
    assert 0 < got["http.accept_loop_busy_share"] < 100
    assert got["stage.cpu_ms_per_op"] > 0 and got["proc.cpu_cores"] > 0
    assert -5 < got["stage.offcpu_share"] < 100
    for name in ("frontend.cpu_ms_per_op", "plan.cpu_ms_per_op",
                 "exec.host_cpu_ms_per_op", "encode.cpu_ms_per_op"):
        assert 0 < got[name] < got["stage.cpu_ms_per_op"], name
    # on a CPU the traversal stays on the host mirror: no device stage
    assert got["exec.dispatch_cpu_ms_per_op"] == 0
    assert got["exec.post_cpu_ms_per_op"] == 0
    assert got["gc.pause_ms_per_op"] > 0 and got["gc.full_pause_ms"] >= 0
    # the stages' work is a part of their wall time, and the clock now
    # holds most of what the client waited
    wall = sum(got[n] for n in ("par.host_ms_per_op", "par.post_ms_per_op",
                                "par.dispatch_ms_per_op",
                                "par.wait_ms_per_op",
                                "par.batch_wait_ms_per_op",
                                "http.accept_ms_per_op",
                                "http.head_ms_per_op"))
    assert wall > 0 and got["trace.outside_share"] < 100
