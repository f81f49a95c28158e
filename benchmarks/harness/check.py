"""The comparison that decides `correct`.

After the window, every answer of the warm-up and of the window is
compared with the plain reference on the benchmark's own Graph: each op's
verify() says whether the answer is one the graph allows. All comparisons
are exact. A window of more than SAMPLE requests (two and a half times
today's) is compared by a sample of SAMPLE of them, drawn from the seed,
so that the reference stays shorter than the window however many requests
a faster program completes; every other answer of such a window still has
to be an HTTP 200 with a data envelope.

A control (`--control`) puts the reference in the program's place with one
stated guarantee broken; it has to come out as not correct.
"""

from __future__ import annotations

import json

CONTROLS = {
    "approx": "answers are exact -> answers from a graph missing 5% of "
              "its edges",
}
APPROX_DROP = 0.05
SAMPLE = 4096


def draw_sample(n: int, seed: int, k: int = SAMPLE) -> list[int]:
    """Which of a window's n requests are compared: all of them up to k,
    else k drawn from the seed, in send order."""
    import numpy as np

    if n <= k:
        return list(range(n))
    rng = np.random.default_rng([seed, 65537])
    return sorted(rng.choice(n, size=k, replace=False).tolist())


def envelopes_only(reqs: list[dict]) -> None:
    """The requests outside the sample: answered or not, nothing more."""
    for r in reqs:
        parse_body(r)
        r["wrong"], r["judged"] = False, False


def parse_body(rec: dict):
    """The `data` envelope of a response, or None with rec['error'] set."""
    if not rec["ok"]:
        return None
    try:
        out = json.loads(rec["body"])
        data = out["data"]
    except (ValueError, KeyError, TypeError):
        rec["ok"] = False
        rec["error"] = f"no data envelope: {rec['body'][:200]!r}"
        return None
    return data


# what _judge reads; set by check_requests before it forks its workers, so
# that nothing but small result tuples crosses a process boundary
_JOB: dict = {}


def _judge(i: int) -> tuple:
    """(i, ok, error, wrong, edges, needed_bytes, problem) of request i."""
    from harness.roofline import needed_bytes

    j = _JOB
    r, g = j["reqs"][i], j["g"]
    op = j["ops"][r["op"]]
    data = parse_body(r)
    if data is None:
        return i, False, r["error"], False, 0, 0, f"{r['op']}: {r['error']}"
    if j["control"] == "approx":
        got = op.answer(j["control_g"], r["params"])
    else:
        got = op.parse(data)
    problem, stats = op.verify(g, r["params"], got)
    if problem is not None:
        problem = f"{r['op']} {json.dumps(r['params'])[:120]}: {problem}"
    return (i, True, None, problem is not None, stats["edges"],
            getattr(op, "needed_bytes", needed_bytes)(stats), problem)


def check_requests(g, reqs: list[dict], ops: dict,
                   control: str | None = None, control_g=None,
                   workers: int = 8) -> list[str]:
    """Judge each of these requests; sets r['wrong'], r['edges'],
    r['needed_bytes'], r['judged']. Returns the first few problems as text.

    The references of a long window take longer than the window in one
    process, so they run in forked workers that share the graph and the
    responses copy-on-write. Call it only when no other thread is alive
    (after the window and the server's stop)."""
    import multiprocessing
    import signal

    for graph in (g, control_g):
        if graph is not None:
            graph.csr                   # built once, before the workers fork
    _JOB.update(g=g, reqs=reqs, ops=ops, control=control,
                control_g=control_g)
    try:
        if workers > 1 and len(reqs) >= 4 * workers:
            # a worker takes SIGTERM's default action, not the handler it
            # inherits from run.py: that one turned the pool's terminate
            # into a Python-level exit, which hung on a lock now and then
            pool = multiprocessing.get_context("fork").Pool(
                workers, initializer=signal.signal,
                initargs=(signal.SIGTERM, signal.SIG_DFL))
            try:
                results = pool.map(_judge, range(len(reqs)), chunksize=4)
                pool.close()
            finally:
                pool.terminate()
                pool.join()
        else:
            results = [_judge(i) for i in range(len(reqs))]
    finally:
        _JOB.clear()
    problems = []
    for i, ok, error, wrong, edges, need, problem in results:
        r = reqs[i]
        r["ok"], r["error"], r["wrong"] = ok, error, wrong
        r["edges"], r["needed_bytes"], r["judged"] = edges, need, True
        if problem is not None:
            problems.append(problem)
    return problems[:20]
