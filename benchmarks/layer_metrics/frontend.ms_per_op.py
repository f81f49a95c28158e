"""Milliseconds a request of the window spent in the HTTP handler itself:
stage `http.read` (body, query string, tenant, until the call of
Node.query; and the handler's epilogue) plus `http.write` (the answer's
headers and bytes into the socket). Program counter: harness/stages.py."""

from harness import stages


def read(run):
    return stages.per_op_ms(run, "http.read", "http.write")
