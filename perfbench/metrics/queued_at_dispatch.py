"""Planner: mean number of requests in the stage queue when a served batch
was formed, its own included (the program's ``queued`` counter on each
batch's record).  Above ``batch_size`` the planner split the queue; equal
to it, batches are small because few requests wait."""


def read(run):
    queued = [b.record.queued for b in run.batches
              if b.record is not None and hasattr(b.record, "queued")]
    return sum(queued) / len(queued) if queued else None
