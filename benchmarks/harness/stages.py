"""Readers' arithmetic over the program's stage clock (dgraph_tpu/obs/
costs.py StageClock): a request is, at every instant, in exactly one named
stage, and /metrics carries `dgraph_stage_us_total{stage="..."}` (integer
microseconds, summed over closed requests) beside
`dgraph_stage_requests_total`. A program without the clock has neither
series: every function here then returns None, and the metric is left out
of the line."""

from __future__ import annotations

SERIES = 'dgraph_stage_us_total{stage="%s"}'
REQUESTS = "dgraph_stage_requests_total"
STARTUP = 'dgraph_startup_ms{phase="%s"}'


def closed_requests(run) -> float | None:
    """Requests whose clock closed between the window's two readings; None
    without the series or without one such request."""
    if REQUESTS not in run.after["prom"]:
        return None
    return run.grown(REQUESTS) or None


def per_op_ms(run, *stages: str) -> float | None:
    """Milliseconds a request of the window spent in the named stages: the
    growth of their series / the growth of the request count / 1000. A
    stage no request entered has no series and counts 0."""
    n = closed_requests(run)
    if n is None:
        return None
    return sum(run.grown(SERIES % s) for s in stages) / n / 1000.0


def all_stages_us(run) -> float | None:
    """Growth of every stage's series over the window, in microseconds."""
    if closed_requests(run) is None:
        return None
    head = SERIES.split("%s")[0]
    return sum(run.grown(k) for k in run.after["prom"] if k.startswith(head))


def startup_s(run, *phases: str) -> float | None:
    """Seconds of serve's start-up phases, as it set them once before its
    banner; None when it set none of the named ones."""
    prom = run.before["prom"]
    found = [prom[STARTUP % p] for p in phases if STARTUP % p in prom]
    return sum(found) / 1000.0 if found else None
