"""Service host path: mean over the window's batches of the dispatch
callback's wall time minus the device-busy time inside it (from the
trace: the callback's span and the device operations share one clock)."""
import trace as tr


def read(run):
    t = run.trace
    if t is None or not t.device:
        return None
    spans = [s for s in t.spans if s.name == "bench.dispatch_cb"
             and t.window[0] <= s.start < t.window[1]]
    if not spans:
        return None
    host = [s.dur - tr.length(tr.busy(t, 0, s.start, s.end)) for s in spans]
    return sum(host) / len(host) * 1e-6
