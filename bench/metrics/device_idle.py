"""Device: share of the traced window in which no operation ran on the
chip, 1 - (union of device-operation intervals) / window."""
import trace as tr


def read(run):
    if run.trace is None or not run.trace.device:
        return None
    return 100.0 * (1.0 - tr.busy_s(run.trace) / run.trace.window_s)
