"""LDBC Graphalytics WCC over `follows`: the component of 64 probe
vertices, the component count and the largest's size:
harness/graphalytics.py holds the op."""

from harness.graphalytics import answer_wcc as answer  # noqa: F401
from harness.graphalytics import draw, request as _request  # noqa: F401
from harness.graphalytics import parse_wcc as parse  # noqa: F401
from harness.graphalytics import verify_wcc as verify  # noqa: F401


def request(p: dict, ctx):
    return _request("wcc", p)
