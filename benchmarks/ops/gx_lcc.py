"""LDBC Graphalytics LCC over `follows`, for 64 probe vertices, the
triangle count and the sum of lcc: harness/lcc.py holds the op."""

from harness import lcc
from harness.lcc import answer, draw, needed_bytes, parse, verify  # noqa: F401


def request(p: dict, ctx):
    return lcc.request(p)
