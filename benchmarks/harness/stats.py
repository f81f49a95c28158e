"""Arithmetic of the end-to-end metrics, over the request log of a window.

A request is a dict: op, t_send, t_done (seconds on one monotonic clock),
ok (answered with HTTP 200 and a data envelope), and after the check
`wrong` (answer differs from the plain reference; False for a request
outside the compared sample). A failed or wrong
request counts as attempted and failed, never as a fast one: it adds no
operation to a rate, and its latency enters a percentile as the client's
time-out (FAILED_MS), whatever it took.
"""

from __future__ import annotations

import math

FAILED_MS = 120000.0      # harness.server.CLIENT_TIMEOUT_S, in ms


def good(r: dict) -> bool:
    return bool(r.get("ok")) and not r.get("wrong")


def percentile(values: list[float], q: float) -> float:
    """Nearest-rank percentile (q in 0..100) — the value below which at
    least q% of the samples lie."""
    if not values:
        raise ValueError("percentile of no samples")
    s = sorted(values)
    k = max(1, math.ceil(q / 100.0 * len(s)))
    return s[k - 1]


def latencies_ms(reqs: list[dict], op: str | None = None) -> list[float]:
    return [((r["t_done"] - r["t_send"]) * 1e3 if good(r) else FAILED_MS)
            for r in reqs if op is None or r["op"] == op]


def median_ms(reqs: list[dict], op: str) -> float | None:
    """Median client latency of one op's requests; None when it sent none."""
    lat = latencies_ms(reqs, op=op)
    return percentile(lat, 50) if lat else None


def rate(reqs: list[dict], t0: float, seconds: float) -> float:
    """Correct operations completed inside [t0, t0 + seconds], over the
    window's seconds."""
    return sum(1 for r in reqs
               if good(r) and r["t_done"] <= t0 + seconds) / seconds


def mean_of_compared(reqs: list[dict], field: str) -> float | None:
    """Mean of a field the comparison sets (`edges`, `needed_bytes`) over
    the correct requests it judged; None when it judged none. The
    comparison takes a sample drawn from the seed, so this mean times a
    count of operations estimates their sum."""
    vals = [float(r[field]) for r in reqs if r.get("judged") and good(r)]
    return sum(vals) / len(vals) if vals else None


def counts(reqs: list[dict]) -> tuple[int, int]:
    """(attempted, failed)."""
    return len(reqs), sum(1 for r in reqs if not good(r))
