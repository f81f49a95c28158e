"""Share of the working stages' wall time in which the request's thread was
not on a core: 100 x (1 - sum of CPU / sum of wall) over every stage but
the waiting ones (`http.accept`, `gate.wait`, `batch.wait`, `dev.wait`,
`dev.window`: harness/stage_cpu.py). A working stage does not block by
design, so what it lacks of its wall time it stood in the queue for the
interpreter (or for a core). One client is the control: near 0. The CPU
side is a request's mean over the requests that read the CPU clock (one
in `costs.CPU_EVERY`), the wall side over all of the window's. A program
without the CPU series: None; no working time in the window: 0."""

from harness import stage_cpu, stages


def read(run):
    n_cpu = stage_cpu.closed_with_cpu(run)
    if n_cpu is None:
        return None
    wall = stage_cpu.by_stage(run, stages.SERIES)
    cpu = stage_cpu.by_stage(run, stage_cpu.CPU)
    working = [s for s in wall if s not in stage_cpu.WAITING]
    wall_a_request = sum(wall[s] for s in working) \
        / stages.closed_requests(run)
    if not wall_a_request:
        return 0.0
    cpu_a_request = sum(cpu.get(s, 0.0) for s in working) / n_cpu
    return 100.0 * (1.0 - cpu_a_request / wall_a_request)
