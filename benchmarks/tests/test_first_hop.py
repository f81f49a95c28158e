"""kernel.first_hop_push_share (layer_metrics/): the reader on hand-made
RunData — both modes grew, one mode only (a mode no search took has no
series), no series at all (a program without the counter: None, the metric
is left out), series that did not grow in the window (0, as a rehearsal on
a CPU reads: `shortest` stays on the host tiers there) — and its entry in
BENCHMARK.json."""

import pytest

import run as runmod
from test_runs import CELL, bench_json

NAME = "kernel.first_hop_push_share"
SERIES = 'dgraph_bfs_first_hop_total{mode="%s"}'


def run_data(before, after):
    rd = runmod.RunData()
    rd.before = {"prom": {SERIES % m: float(v) for m, v in before.items()}}
    rd.after = {"prom": {SERIES % m: float(v) for m, v in after.items()}}
    return rd


CASES = {
    # name: (series before the window, after it, the share read)
    "both_modes_grew": ({"push": 5, "stream": 1},
                        {"push": 2003, "stream": 3}, 99.9),
    "push_only": ({"push": 5}, {"push": 905}, 100.0),
    "stream_appears_in_the_window": ({"push": 5},
                                     {"push": 14, "stream": 1}, 90.0),
    "stream_only": ({"stream": 2}, {"stream": 6}, 0.0),
    "no_series": ({}, {}, None),
    "no_search_in_the_window": ({"push": 5, "stream": 1},
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


def test_entry_is_the_last_and_lists_search_only():
    m = bench_json()["per_layer"][-1]
    assert m == {"name": NAME, "unit": "%", "better": "higher",
                 "source": "program_counter", "layer": "kernels",
                 "moves": "ops_per_s", "workloads": [CELL]}
