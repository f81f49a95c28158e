"""Milliseconds a request of the `khop-par22` window spent handing a
traversal to the device: stage `dev.dispatch`, over ALL the window's
requests — only a launch's leader (and a request that ran alone) has it,
so this is the launches' dispatch time spread over everyone they
answered. Program counter: harness/stages.py."""

from harness import stages


def read(run):
    return stages.per_op_ms(run, "dev.dispatch")
