"""95th percentile of the per-call latencies, in milliseconds, where the
traffic waits on every call; nothing otherwise."""
from bench.harness import quantile


def read(r):
    if not r.window.latencies:
        return None
    return 1e3 * quantile(r.window.latencies, 0.95)
