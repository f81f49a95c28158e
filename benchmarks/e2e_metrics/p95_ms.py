"""95th percentile of the client latency of ALL requests of the window; a
failed or wrong one counts as the client's time-out."""

from harness import stats


def read(run):
    return stats.percentile(stats.latencies_ms(run.reqs), 95)
