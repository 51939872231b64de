"""Service host path: bytes of the backend dispatch's outputs copied to
the host, per live query, counted from their shapes over the window's
batches."""


def read(run):
    live = sum(b.live for b in run.batches if b.qb)
    if not live:
        return None
    return sum(b.out_bytes for b in run.batches if b.qb) / live
