"""Load generator: 95th percentile of submit time minus due time, so a
starved generator is not read as a fast server."""
import numpy as np


def read(run):
    late = run.submit - run.due
    return float(np.quantile(late, 0.95)) * 1e3 if late.size else None
