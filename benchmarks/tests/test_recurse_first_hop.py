"""khop.first_hop_push_share (layer_metrics/): the reader on hand-made
RunData — both modes grew, one mode only, no series at all (a program
without the counter, as the parent of the PR that brought it: None, the
metric is left out), series that did not grow in the window (0, as a
rehearsal on a CPU reads: `@recurse` stays on the host mirror there) — and
its entry in BENCHMARK.json, looked up by name."""

import pytest

import run as runmod
from test_runs import bench_json

NAME = "khop.first_hop_push_share"
SERIES = 'dgraph_recurse_first_hop_total{mode="%s"}'


def run_data(before, after):
    rd = runmod.RunData()
    rd.before = {"prom": {SERIES % m: float(v) for m, v in before.items()}}
    rd.after = {"prom": {SERIES % m: float(v) for m, v in after.items()}}
    return rd


CASES = {
    # name: (series before the window, after it, the share read)
    "both_modes_grew": ({"push": 40, "stream": 0},
                        {"push": 9031, "stream": 9}, 99.9),
    "push_only": ({"push": 5, "stream": 0}, {"push": 905, "stream": 0},
                  100.0),
    "stream_only": ({"push": 3, "stream": 2}, {"push": 3, "stream": 6}, 0.0),
    "one_series_is_enough": ({"push": 5}, {"push": 14}, 100.0),
    "no_series": ({}, {}, None),
    "no_traversal_in_the_window": ({"push": 5, "stream": 1},
                                   {"push": 5, "stream": 1}, 0.0),
    "a_fresh_node_shows_both_at_zero": ({"push": 0, "stream": 0},
                                        {"push": 0, "stream": 0}, 0.0),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_reader(case):
    before, after, want = CASES[case]
    got = runmod.load_module("layer_metrics", NAME).read(
        run_data(before, after))
    assert got == (want if want is None else pytest.approx(want))


def test_the_reader_does_not_read_the_searches_counter():
    """dgraph_bfs_first_hop_total is kernel.first_hop_push_share's."""
    rd = runmod.RunData()
    rd.before = {"prom": {'dgraph_bfs_first_hop_total{mode="push"}': 0.0}}
    rd.after = {"prom": {'dgraph_bfs_first_hop_total{mode="push"}': 9.0}}
    assert runmod.load_module("layer_metrics", NAME).read(rd) is None


def test_entry_lists_the_two_recurse_cells():
    (m,) = [m for m in bench_json()["per_layer"] if m["name"] == NAME]
    assert m == {"name": NAME, "unit": "%", "better": "higher",
                 "source": "program_counter", "layer": "kernels",
                 "moves": "ops_per_s", "workloads": ["khop", "khop-par22"]}
    cells = {w["name"] for w in bench_json()["workloads"]}
    assert set(m["workloads"]) <= cells
