"""k-hop neighbour count, k = 1: harness/khop.py holds the op."""

from harness.khop import answer, draw_for, parse, request, verify  # noqa: F401

draw = draw_for(1)
