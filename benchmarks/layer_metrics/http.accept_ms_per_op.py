"""Milliseconds a request of the window spent in stage `http.accept`: from
`accept()` returning on the server's one accept-loop thread to the first
instruction of the handler thread made for the connection — `Thread`
creation, `start()`, the new thread's first turn at the interpreter. Wall
time; it spans two threads and has no CPU time by definition. What the
connection waited in the kernel's backlog before `accept()` is not in it
(`http.accept_loop_busy_share`). Shows on /metrics from start-up, at 0; a
program without the stage (before PR 38): None."""

from harness import stages


def read(run):
    if stages.SERIES % "http.accept" not in run.after["prom"]:
        return None
    return stages.per_op_ms(run, "http.accept")
