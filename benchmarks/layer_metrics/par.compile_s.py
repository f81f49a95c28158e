"""Seconds of XLA compiles before the `khop-par22` window:
`compile_ms_total` of /debug/compiles at the window's first reading — the
solo pb.recurse_fused at depth 1, the stacked pb.recurse_fused_multi, the
seed mask's eager programs, the count's. Near 0 when the persistent cache
held them."""


def read(run):
    ms = run.before["compiles"].get("compile_ms_total")
    return None if ms is None else ms / 1000.0
