"""Rounds a `wcc` request ran: growth of
`dgraph_analytics_steps_total{kind="wcc"}` (a device round reads every
edge once; a host union-find counts one pass) / growth of the `wcc` runs,
device and host. A program without the counters, or no `wcc` run: None."""

from harness import graphalytics


def read(run):
    got = graphalytics.runs(run, "wcc")
    if got is None or not sum(got):
        return None
    return run.grown(graphalytics.SERIES % ("steps", "wcc")) / sum(got)
