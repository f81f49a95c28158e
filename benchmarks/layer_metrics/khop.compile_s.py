"""Seconds of XLA compiles before the `khop` window: `compile_ms_total` of
/debug/compiles at the window's first reading — pb.recurse_fused once for
each of the four depths (a static argument), the seed mask's eager
programs, the count's. Near 0 when the persistent cache held them."""


def read(run):
    ms = run.before["compiles"].get("compile_ms_total")
    return None if ms is None else ms / 1000.0
