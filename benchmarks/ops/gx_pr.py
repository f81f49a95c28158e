"""LDBC Graphalytics PR over `follows`, 10 iterations, d = 0.85, for 64
probe vertices and the top 20: harness/graphalytics.py holds the op."""

from harness.graphalytics import answer_pr as answer  # noqa: F401
from harness.graphalytics import draw, request as _request  # noqa: F401
from harness.graphalytics import parse_pr as parse  # noqa: F401
from harness.graphalytics import verify_pr as verify  # noqa: F401


def request(p: dict, ctx):
    return _request("pr", p)
