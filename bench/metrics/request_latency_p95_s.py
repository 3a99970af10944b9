"""95th percentile over every request due in the window of (its tokens
ready) minus (its arrival), in seconds."""

from bench.readers import latency_p95


def read(run):
    return latency_p95(run)
