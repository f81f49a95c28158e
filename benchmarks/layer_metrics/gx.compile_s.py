"""Seconds of XLA compiles before the `graphalytics` window:
`compile_ms_total` of /debug/compiles at the window's first reading —
pb.analytics_pr and pb.analytics_wcc at the graph's shapes. Near 0 when
the persistent cache held them."""


def read(run):
    ms = run.before["compiles"].get("compile_ms_total")
    return None if ms is None else ms / 1000.0
