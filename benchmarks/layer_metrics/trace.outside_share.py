"""`trace.unaccounted_share` in the two cells that have no such reader:
100 x (1 - every stage's wall microseconds, `http.accept` and `http.head`
with them / summed client latency). What is left is outside the program's
clock: the kernel's listen backlog and the client. Program counter:
harness/stages.py."""

from harness import stages


def read(run):
    inside_us = stages.all_stages_us(run)
    wall_us = sum((r["t_done"] - r["t_send"]) * 1e6 for r in run.reqs)
    if inside_us is None or not wall_us:
        return None
    return 100.0 * (1.0 - inside_us / wall_us)
