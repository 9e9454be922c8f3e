"""Planner: mean requests per batch served (before padding to the bucket)."""


def read(run):
    sizes = [len(b.uids) for b in run.batches if b.record is not None]
    return sum(sizes) / len(sizes) if sizes else None
