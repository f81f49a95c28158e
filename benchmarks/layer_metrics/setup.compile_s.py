"""Seconds of XLA compiles before the window (warm-up's, and whatever the
start compiled): `compile_ms_total` of /debug/compiles at the window's
first reading. Near 0 when the persistent cache held the programs."""

def read(run):
    ms = run.before["compiles"].get("compile_ms_total")
    return None if ms is None else ms / 1000.0
