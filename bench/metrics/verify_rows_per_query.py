"""Raw-tier verify: float32 rows the tiered dispatch gathers from the host
tier, per live query.  The dispatch returns one candidate slot per
gathered row, (Q bucket) x (slots), so the count is read from its
outputs.  Nothing to read where the backend has no raw tier."""


def read(run):
    tiered = [b for b in run.batches if b.tiered and b.qb]
    live = sum(b.live for b in tiered)
    if not live:
        return None
    return sum(b.qb * b.slots for b in tiered) / live
