"""Of the levels the device recurse programs ran in the window, the share
whose frontier held no vertex: 100 x growth of
`dgraph_recurse_levels_total{state="empty"}` / growth of both states
(/metrics; both show from the program's start, at 0). The fused scan runs
all `depth` levels, so each empty one is a whole stream of the graph for
nothing. A program without the counter has neither series: None."""

SERIES = 'dgraph_recurse_levels_total{state="%s"}'


def read(run):
    if not any(SERIES % s in run.after["prom"] for s in ("live", "empty")):
        return None
    live, empty = (run.grown(SERIES % s) for s in ("live", "empty"))
    return 100.0 * empty / (live + empty) if live + empty else 0.0
