"""Share of the requests' client latency that no stage of the program's
clock holds: 100 x (1 - growth of every stage's microseconds over the
window / summed client latency of its requests). What is left is outside
the entry point: the socket, http.server's request line and headers, the
thread hand-off, the client. Program counter: harness/stages.py."""

from harness import stages


def read(run):
    inside_us = stages.all_stages_us(run)
    wall_us = sum((r["t_done"] - r["t_send"]) * 1e6 for r in run.reqs)
    if inside_us is None or not wall_us:
        return None
    return 100.0 * (1.0 - inside_us / wall_us)
