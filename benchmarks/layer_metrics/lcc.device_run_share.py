"""Of the window's `lcc` runs, the share that ran on the device over the
degree-ordered rows built from the resident PullGraph: 100 x growth of
`dgraph_analytics_device_runs_total{kind="lcc"}` / growth of it and of
every `dgraph_analytics_host_runs_total{kind="lcc",reason=}` (/metrics).
A program without the kind's device counter: None."""

from harness import lcc


def read(run):
    got = lcc.runs(run)
    if got is None:
        return None
    dev, host = got
    return 100.0 * dev / (dev + host) if dev + host else None
