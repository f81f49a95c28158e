"""The `lcc.core_edge_share` reader (layer_metrics/) on hand-made
RunData."""

import pytest

import run as runmod

NAME = "lcc.core_edge_share"
CORE = "dgraph_analytics_lcc_core_edges_total"
ORIENTED = "dgraph_analytics_lcc_oriented_edges_total"

CASES = {
    # name: (series before, after, what is read)
    "half_the_edges_in_the_core": (
        {CORE: 500, ORIENTED: 1000}, {CORE: 2500, ORIENTED: 5000}, 50.0),
    "no_core": ({CORE: 0, ORIENTED: 10}, {CORE: 0, ORIENTED: 90}, 0.0),
    "no_run_in_the_window": ({CORE: 7, ORIENTED: 9}, {CORE: 7,
                                                      ORIENTED: 9}, None),
    "a_program_without_the_counters": ({}, {}, None),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_core_edge_share_reader(case):
    before, after, want = CASES[case]
    rd = runmod.RunData()
    rd.before = {"prom": {k: float(v) for k, v in before.items()}}
    rd.after = {"prom": {k: float(v) for k, v in after.items()}}
    got = runmod.load_module("layer_metrics", NAME).read(rd)
    assert got == (want if want is None else pytest.approx(want))

