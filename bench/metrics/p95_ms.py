"""95th percentile of the latency from each request's due time to its
answer, over every request due in the window: one population, not a
median of chunks (a request never answered counts as infinitely late)."""
from harness import quantile


def read(run):
    lat = run.latency_s
    return quantile(lat, 0.95) * 1e3 if lat.size else None
