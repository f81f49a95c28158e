"""Seconds serve took to open its store and to listen: phases `store_open`
(the Node: WAL replay, snapshot open) and `listen` (gRPC and HTTP servers,
until the banner) of `dgraph_startup_ms`, read before the window. Program
counter: harness/stages.py."""

from harness import stages


def read(run):
    return stages.startup_s(run, "store_open", "listen")
