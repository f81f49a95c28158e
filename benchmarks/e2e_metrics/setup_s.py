"""Process start to the first request of the window: generate, bulk (or the
store cache), copy, serve start, warm-up. The reference's time is not in it."""

def read(run):
    return run.setup_s
