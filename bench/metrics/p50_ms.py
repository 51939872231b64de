"""Median latency, due time to answer, over every request due in the
window (a request never answered counts as infinitely late)."""
from harness import quantile


def read(run):
    lat = run.latency_s
    return quantile(lat, 0.5) * 1e3 if lat.size else None
