"""Milliseconds a request of the window spent in stage `http.head`: the
handler thread running (a kept-alive connection's later request: its
request line read) until `do_POST` opened the clock — the wait for the
request line, `http.server`'s `parse_request`, the header parse. Wall time
(`frontend.cpu_ms_per_op` holds its CPU beside the handler's other stages).
Shows from start-up, at 0; a program without the stage: None."""

from harness import stages


def read(run):
    if stages.SERIES % "http.head" not in run.after["prom"]:
        return None
    return stages.per_op_ms(run, "http.head")
