"""Batcher: median of due time to the start of the dispatch that carries
the request (queueing plus coalescing), host clock."""
import numpy as np


def read(run):
    wait = run.start - run.due
    wait = wait[np.isfinite(wait)]
    return float(np.median(wait)) * 1e3 if wait.size else None
