"""Readers' arithmetic over what the program's stage clock says beside a
stage's wall time (dgraph_tpu/obs/costs.py StageClock, PR 38; the wall
series are harness/stages.py's): `dgraph_stage_cpu_us_total{stage="..."}`,
the CPU time of the request's own thread in each stage, integer
microseconds summed over the closed requests that read the CPU clock — one
in `costs.CPU_EVERY`, counted in `dgraph_stage_cpu_requests_total`: a read
is a system call — and the series the
server copies in when /metrics is rendered: the accept loop's, the
collector's pauses by generation, the process's CPU seconds. A program
without a series has none of its lines: every function here then returns
None, and the metric is left out of the line."""

from __future__ import annotations

from harness import stages

CPU = 'dgraph_stage_cpu_us_total{stage="%s"}'
CPU_REQUESTS = "dgraph_stage_cpu_requests_total"
GC_PAUSE = 'dgraph_gc_pause_us_total{generation="%s"}'
GC_COUNT = 'dgraph_gc_collections_total{generation="%s"}'

# stages in which a request waits by design — for another thread, for the
# device, for its batch; every other stage is the thread's own work, and
# what it lacks of its wall time there it stood in a queue for the
# interpreter or for a core
WAITING = ("http.accept", "gate.wait", "batch.wait", "dev.wait",
           "dev.window")


def by_stage(run, template: str) -> dict[str, float]:
    """{stage: growth over the window} of one stage-labelled series."""
    head, tail = template.split("%s")
    return {k[len(head):-len(tail)]: run.grown(k)
            for k in run.after["prom"] if k.startswith(head)}


def closed_with_cpu(run) -> float | None:
    """Requests closed in the window that read the CPU clock; None without
    the series or without one such request."""
    if CPU_REQUESTS not in run.after["prom"]:
        return None
    return run.grown(CPU_REQUESTS) or None


def cpu_per_op_ms(run, *names: str) -> float | None:
    """CPU milliseconds a request of the window spent in the named stages
    (all of them when none is named), over the requests that read it."""
    n = closed_with_cpu(run)
    if n is None:
        return None
    cpu = by_stage(run, CPU)
    return sum(v for s, v in cpu.items()
               if not names or s in names) / n / 1000.0


def window_s(run) -> float:
    """From the window's first request to its last answer."""
    if not run.reqs:
        return run.seconds
    return max(r["t_done"] for r in run.reqs) - run.t0
