"""Screen kernels (the kernels/fused_query.py pallas_calls): their share
of the chip's roofline.  For every call in the traced window the least
time the chip could take is the larger of bytes / HBM peak and MXU
operations / bf16 peak, each computed from the call's shapes by
``bench/kernels/<kernel>.py``; the share is the sum of those least times
over the sum of the calls' device times."""
import trace as tr


def _calls(run):
    """[(kernel, device seconds, bytes, MXU operations)] in the window."""
    return [(name, e.dur * 1e-9) + tuple(k.cost(e.name))
            for name, k in sorted(run.kernels.items())
            for e in tr.ops_matching(run.trace, k.PATTERN)]


def _least(run, nbytes, ops):
    return max(nbytes / run.peaks["hbm_bytes_per_s"],
               ops / run.peaks["bf16_flops_per_s"])


def read(run):
    if run.trace is None or not run.peaks:
        return None
    calls = _calls(run)
    busy = sum(c[1] for c in calls)
    if not calls or busy <= 0:
        return None
    return 100.0 * sum(_least(run, c[2], c[3]) for c in calls) / busy


def describe(run):
    """One clause per kernel: calls, device time, bytes, operations and
    which of the two bounds its least time."""
    if run.trace is None or not run.peaks:
        return ""
    calls = _calls(run)
    parts = []
    for name in sorted({c[0] for c in calls}):
        cs = [c for c in calls if c[0] == name]
        nbytes, ops = sum(c[2] for c in cs), sum(c[3] for c in cs)
        by_bytes = (nbytes / run.peaks["hbm_bytes_per_s"]
                    >= ops / run.peaks["bf16_flops_per_s"])
        parts.append(f"{name}: calls={len(cs)} "
                     f"device_s={sum(c[1] for c in cs)!r} bytes={nbytes} "
                     f"mxu_ops={ops} bound={'bytes' if by_bytes else 'ops'}")
    return "; ".join(parts)
