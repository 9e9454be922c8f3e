"""Executor and model: mean time a served batch waited in the executor's
own queue, from ``submit()`` to the worker taking it up (the program's
``executor.queue`` span on each batch's record), in ms."""


def read(run):
    waits = [(t1 - t0) / 1e6 for b in run.batches if b.record is not None
             for name, t0, t1 in getattr(b.record, "spans", ())
             if name == "executor.queue"]
    return sum(waits) / len(waits) if waits else None
