"""1 - (union of device-op intervals / traced interval) in the
`graphalytics-lcc` cell, from the profiler trace of a few seconds of the
steady window. Nothing without a trace."""


def read(run):
    tr = run.trace
    if not tr or not tr.get("busy_s") or not tr.get("window_s"):
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
