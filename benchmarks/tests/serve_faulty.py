"""Test-only child: `serve` with the timed path broken underneath, chosen
by $BENCH_FAULT. The harness must then say `correct: false`.

    alter_answer  every 3rd query's answer is altered where it is
                  produced (Node.query): the last row of its first list
                  is dropped
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _drop_last_row(obj) -> bool:
    if isinstance(obj, dict):
        return any(_drop_last_row(v) for v in obj.values())
    if isinstance(obj, list) and obj:
        obj.pop()
        return True
    return False


def main() -> int:
    sys.path.insert(0, ROOT)
    from dgraph_tpu.__main__ import main as dgraph_main
    from dgraph_tpu.api.server import Node

    fault = os.environ["BENCH_FAULT"]
    calls = {"n": 0}
    if fault == "alter_answer":
        orig_query = Node.query

        def query(self, *a, **kw):
            out, ctx = orig_query(self, *a, **kw)
            calls["n"] += 1
            if calls["n"] % 3 == 0:
                _drop_last_row(out)
            return out, ctx

        Node.query = query
    else:
        raise SystemExit(f"unknown BENCH_FAULT {fault!r}")
    return dgraph_main(sys.argv[1:])


if __name__ == "__main__":
    sys.exit(main())
