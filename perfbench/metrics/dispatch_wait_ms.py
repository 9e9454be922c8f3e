"""Dispatch: mean wait of a served request in the control plane's stage
queue, from when it was ready to when its batch was dispatched, on the
control plane's own clock (the program's ``waits_ms`` counter on each
batch's record), in ms."""


def read(run):
    waits = [w for b in run.batches if b.record is not None
             for w in getattr(b.record, "waits_ms", ())]
    return sum(waits) / len(waits) if waits else None
