"""`serve` with a profiler around part of the window — the traced run's
child. Only the process that holds the chip can trace it, and the program
has no profiler hook, so this wrapper runs dgraph_tpu's own entry
in-process and a watcher thread starts / stops jax.profiler on flag files
the benchmark's parent makes in $BENCH_TRACE_CTL:

    start   (parent)  -> start_trace(<ctl>/trace); writes `started`
    stop    (parent)  -> stop_trace(); writes `done`

`started` and `done` hold time.monotonic() readings (one clock for every
process of a machine) taken after start_trace returned and before
stop_trace was called: the traced interval on the parent's clock.
"""

import json
import os
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _wait_for(path: str) -> None:
    while not os.path.exists(path):
        time.sleep(0.02)


def _write(path: str, obj: dict) -> None:
    with open(path + ".tmp", "w") as f:
        json.dump(obj, f)
    os.rename(path + ".tmp", path)


def watcher(ctl: str) -> None:
    import jax          # already imported by main(): two threads importing
    #                     it at once trip over its circular imports

    _wait_for(os.path.join(ctl, "start"))
    # no Python tracer: a hook on every call of every server thread would
    # slow the host path this run exists to see; XLA's own host events
    # (level 2) stay on and name what the runtime was doing in a gap
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(os.path.join(ctl, "trace"),
                             profiler_options=opts)
    _write(os.path.join(ctl, "started"), {"t": time.monotonic()})
    _wait_for(os.path.join(ctl, "stop"))
    t = time.monotonic()
    jax.profiler.stop_trace()
    _write(os.path.join(ctl, "done"), {"t": t})


def main() -> int:
    sys.path.insert(0, ROOT)
    import jax  # noqa: F401 — before the watcher thread starts, see there
    from dgraph_tpu.__main__ import main as dgraph_main

    threading.Thread(target=watcher, args=(os.environ["BENCH_TRACE_CTL"],),
                     daemon=True).start()
    return dgraph_main(sys.argv[1:])


if __name__ == "__main__":
    sys.exit(main())
