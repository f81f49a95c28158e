"""The load generators. One general generator per loop kind reads a traffic
file's parameters; a traffic mix is data.

closed: `clients` threads, each sends its next request when the last one
answered. Each client deals its ops from a shuffled deck that holds every
op in exactly the mix's shares (80/20 is a deck of 5), again and again: a
seed fixes the work, and every seed gets the same composition in another
order — the count of a rare heavy op does not swing from run to run.
Responses are kept as bytes and parsed after the window: the generator's
threads do as little as they can inside it.
"""

from __future__ import annotations

import math
import threading
import time

import numpy as np

from harness.server import CLIENT_TIMEOUT_S

LOOPS = ("closed",)


def deck(shares: dict[str, float]) -> list[str]:
    """The smallest list of op names that holds each op in exactly its
    share (shares are whole percents)."""
    pct = {n: round(100 * s) for n, s in sorted(shares.items())}
    if sum(pct.values()) != 100 or min(pct.values()) < 1:
        raise ValueError(f"op shares must be whole percents that add up "
                         f"to 1: {shares}")
    g = math.gcd(*pct.values())
    return [n for n, c in pct.items() for _ in range(c // g)]


def op_stream(traffic: dict, ops: dict, ctx, seed: int, client: int):
    """Endless (op name, params) stream of one client."""
    rng = np.random.default_rng([seed, 7919, client])
    cards = deck(traffic["ops"])
    while True:
        for i in rng.permutation(len(cards)):
            yield cards[i], ops[cards[i]].draw(ctx, rng)


def send(srv, ops: dict, ctx, name: str, params: dict) -> dict:
    """One request, timed at the client from send to the last byte."""
    method, path, body = ops[name].request(params, ctx)
    rec = {"op": name, "params": params, "ok": False, "body": None,
           "error": None}
    rec["t_send"] = time.monotonic()
    try:
        rec["body"] = srv.raw(method, path, body, timeout=CLIENT_TIMEOUT_S)
        rec["ok"] = True
    except Exception as e:  # noqa: BLE001 — any failure: a failed request
        rec["error"] = f"{type(e).__name__}: {e}"[:300]
    rec["t_done"] = time.monotonic()
    return rec


def run_closed(srv, traffic: dict, ops: dict, ctx, seed: int,
               seconds: float, on_start=None) -> tuple[list[dict], float]:
    """Drive the closed loop for `seconds`; requests in flight at the end
    are waited for. Returns (request log, t0 of the window)."""
    n = int(traffic["clients"])
    logs: list[list[dict]] = [[] for _ in range(n)]
    streams = [op_stream(traffic, ops, ctx, seed, c) for c in range(n)]
    go = threading.Event()
    t_end = [0.0]

    def client(c: int) -> None:
        go.wait()
        for name, params in streams[c]:
            if time.monotonic() >= t_end[0]:
                return
            logs[c].append(send(srv, ops, ctx, name, params))

    threads = [threading.Thread(target=client, args=(c,), daemon=True)
               for c in range(n)]
    for t in threads:
        t.start()
    t0 = time.monotonic()
    t_end[0] = t0 + seconds
    if on_start:
        on_start(t0)
    go.set()
    for t in threads:
        t.join(timeout=seconds + 2 * CLIENT_TIMEOUT_S)
        if t.is_alive():
            raise RuntimeError("a client thread did not end")
    reqs = [r for log in logs for r in log]
    reqs.sort(key=lambda r: r["t_send"])
    return reqs, t0
