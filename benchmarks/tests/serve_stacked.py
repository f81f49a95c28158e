"""Test-only child: `serve` with the `@recurse` kernel tier forced (on a
CPU the traversal otherwise runs on the host mirror and never reaches the
batcher), so a rehearsal of `khop-par22` runs the stacked
pb.recurse_fused_multi launch (interpret mode) and its demultiplexing.
$BENCH_FAULT then breaks the guarantee underneath, or not:

    none       nothing broken: the rehearsal has to pass its checks
    swap_pair  the first two members of every stacked launch are handed
               each other's arrays: each answers with the count of the
               other's root. The harness must then say `correct: false`

With $BENCH_TRACE_CTL set (a traced run) it also starts serve_traced.py's
profiler watcher, as that wrapper would.
"""

import os
import sys
import threading

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def main() -> int:
    sys.path.insert(0, ROOT)
    import jax  # noqa: F401 — before any watcher thread, see serve_traced
    from dgraph_tpu.__main__ import main as dgraph_main
    from dgraph_tpu.query import recurse as recmod
    from dgraph_tpu.query.batch import DeviceBatcher

    recmod.KERNEL_MIN_EDGES = 0
    fault = os.environ["BENCH_FAULT"]
    if fault == "swap_pair":
        run_recurse = DeviceBatcher._run_recurse

        def swapped(self, entries, depth, allow_loop):
            run_recurse(self, entries, depth, allow_loop)
            a, b = entries[0], entries[1]
            a.result, b.result = b.result, a.result

        DeviceBatcher._run_recurse = swapped
    elif fault != "none":
        raise SystemExit(f"unknown BENCH_FAULT {fault!r}")
    if "BENCH_TRACE_CTL" in os.environ:
        sys.path.insert(0, os.path.dirname(HERE))
        import serve_traced

        threading.Thread(target=serve_traced.watcher,
                         args=(os.environ["BENCH_TRACE_CTL"],),
                         daemon=True).start()
    return dgraph_main(sys.argv[1:])


if __name__ == "__main__":
    sys.exit(main())
