"""Seconds of serve's start before it opened its store: phases `import`
(process start until cmd_serve had its imports) and `backend_init` of
`dgraph_startup_ms`, read before the window. Program counter:
harness/stages.py."""

from harness import stages


def read(run):
    return stages.startup_s(run, "import", "backend_init")
