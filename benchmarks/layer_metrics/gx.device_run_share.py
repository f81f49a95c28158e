"""Of the window's Graphalytics runs (`pr` and `wcc`), the share that ran
on the device over the resident PullGraph: 100 x growth of
`dgraph_analytics_device_runs_total{kind=}` / growth of it and of every
`dgraph_analytics_host_runs_total{kind=,reason=}` (/metrics). A program
without the device counter: None."""

from harness import graphalytics


def read(run):
    got = graphalytics.runs(run)
    if got is None:
        return None
    dev, host = got
    return 100.0 * dev / (dev + host) if dev + host else None
