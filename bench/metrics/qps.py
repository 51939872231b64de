"""Requests answered OK inside the window, per second of the window."""
import numpy as np


def read(run):
    done = run.ok & (run.done <= run.t_close)
    return float(np.sum(done)) / run.seconds
