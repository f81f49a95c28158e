"""The per-layer metrics that read the program's stage clock and start-up
phases (layer_metrics/*_per_op.py, trace.unaccounted_share, setup.*_s;
harness/stages.py): each reader on hand-made RunData — series present,
series absent (a program without the clock: None, the metric is left out),
no request closed in the window (None) — and a whole traced rehearsal
whose line holds every one of them."""

import pytest

import run as runmod
from test_runs import CELL, bench_json, run_cell

STAGE_US = {"http.read": 300, "http.write": 200, "parse": 100, "plan": 400,
            "exec": 250, "exec.prep": 50, "dev.dispatch": 3000,
            "dev.wait": 20000, "dev.window": 1000, "dev.post": 2500,
            "encode": 150}

# reader -> ms a request, when every closed request grew STAGE_US
PER_OP = {
    "frontend.ms_per_op": 0.5,
    "plan.ms_per_op": 0.5,
    "exec.host_ms_per_op": 0.3,
    "exec.dispatch_ms_per_op": 3.0,
    "exec.wait_ms_per_op": 21.0,
    "exec.post_ms_per_op": 2.5,
    "encode.ms_per_op": 0.15,
}
NEW = sorted(PER_OP) + ["trace.unaccounted_share", "setup.backend_init_s",
                        "setup.store_open_s", "setup.compile_s"]


def run_data(requests=2, stages=True, startup=True, compile_ms=1500.0):
    """A window in which `requests` clocks closed, each STAGE_US long,
    after a warm-up that had closed 5 of twice that length."""
    rd = runmod.RunData()
    before, after = {}, {}
    if stages:
        before["dgraph_stage_requests_total"] = 5.0
        after["dgraph_stage_requests_total"] = 5.0 + requests
        for s, us in STAGE_US.items():
            key = 'dgraph_stage_us_total{stage="%s"}' % s
            before[key] = 10.0 * us
            after[key] = before[key] + requests * us
    if startup:
        for phase, ms in (("import", 7000), ("backend_init", 2500),
                          ("store_open", 8000), ("listen", 250)):
            before['dgraph_startup_ms{phase="%s"}' % phase] = float(ms)
    after.update({k: v for k, v in before.items() if k not in after})
    comp = {} if compile_ms is None else {"compile_ms_total": compile_ms}
    rd.before = {"prom": before, "compiles": comp}
    rd.after = {"prom": after, "compiles": comp}
    # the client saw each request take 30 ms; the stages hold 27.95 of them
    rd.reqs = [{"op": "shortest", "t_send": 1.0 + i, "t_done": 1.03 + i,
                "ok": True} for i in range(requests)]
    return rd


def read(name, rd):
    return runmod.load_module("layer_metrics", name).read(rd)


@pytest.mark.parametrize("name", sorted(PER_OP))
def test_per_op_reader_present_absent_and_no_request(name):
    assert read(name, run_data()) == pytest.approx(PER_OP[name])
    assert read(name, run_data(requests=7)) == pytest.approx(PER_OP[name])
    assert read(name, run_data(stages=False)) is None
    assert read(name, run_data(requests=0)) is None


def test_a_stage_no_request_entered_counts_zero():
    rd = run_data()
    for d in (rd.before["prom"], rd.after["prom"]):
        del d['dgraph_stage_us_total{stage="dev.post"}']
    assert read("exec.post_ms_per_op", rd) == 0.0


def test_unaccounted_share_present_absent_and_no_request():
    inside = sum(STAGE_US.values())
    want = 100.0 * (1.0 - inside / 30000.0)
    assert read("trace.unaccounted_share", run_data()) == pytest.approx(want)
    assert read("trace.unaccounted_share", run_data(stages=False)) is None
    assert read("trace.unaccounted_share", run_data(requests=0)) is None


def test_startup_readers_present_and_absent():
    assert read("setup.backend_init_s", run_data()) == pytest.approx(9.5)
    assert read("setup.store_open_s", run_data()) == pytest.approx(8.25)
    assert read("setup.backend_init_s", run_data(startup=False)) is None
    assert read("setup.store_open_s", run_data(startup=False)) is None
    # a phase under half a millisecond has no series: the other one counts
    rd = run_data()
    del rd.before["prom"]['dgraph_startup_ms{phase="listen"}']
    assert read("setup.store_open_s", rd) == pytest.approx(8.0)


def test_compile_reader_present_and_absent():
    assert read("setup.compile_s", run_data()) == pytest.approx(1.5)
    assert read("setup.compile_s", run_data(compile_ms=0.0)) == 0.0
    assert read("setup.compile_s", run_data(compile_ms=None)) is None


def test_every_new_metric_is_listed_for_search_only():
    by_name = {m["name"]: m for m in bench_json()["per_layer"]}
    for name in NEW:
        m = by_name[name]
        assert m["workloads"] == [CELL] and m["source"] == "program_counter"
        assert m["moves"] == ("setup_s" if name.startswith("setup.")
                              else "ops_per_s")


def test_traced_rehearsal_prints_every_new_metric():
    out, res = run_cell(CELL, "--trace", "1", seed=2147484099)
    assert out["checks_passed"] is True, res.stderr[-2000:]
    got = out["metrics"]
    assert set(NEW) <= set(got), sorted(set(NEW) - set(got))
    # at scale 10 on a CPU `shortest` stays on the host tiers: the request
    # passes through the handler, parse / plan, the executor and encode
    for name in ("frontend.ms_per_op", "plan.ms_per_op",
                 "exec.host_ms_per_op", "encode.ms_per_op",
                 "setup.backend_init_s", "setup.store_open_s"):
        assert got[name]["value"] > 0, name
    for name in ("exec.dispatch_ms_per_op", "exec.wait_ms_per_op",
                 "exec.post_ms_per_op", "setup.compile_s"):
        assert got[name]["value"] >= 0, name
    # can read below 0 here: a handler thread that waits for the
    # interpreter after its answer is out overlaps the next request
    assert -100 < got["trace.unaccounted_share"]["value"] < 100
    per_op = sum(got[n]["value"] for n in PER_OP)
    assert per_op < got["op.shortest_p50_ms"]["value"] * 3
