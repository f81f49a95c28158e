"""Seconds of XLA compiles before the `graphalytics-lcc` window:
`compile_ms_total` of /debug/compiles at the window's first reading —
the LCC program at the graph's shapes. Near 0 when the persistent cache
held it."""


def read(run):
    ms = run.before["compiles"].get("compile_ms_total")
    return None if ms is None else ms / 1000.0
