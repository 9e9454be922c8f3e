"""Gateway: mean wait of a request from when it was due to when the device
started the batch that served it (benchmark spans, host clock)."""


def read(run):
    waits = [r.batch.t_start * 1e3 - r.due_ms for r in run.requests
             if r.batch is not None]
    return sum(waits) / len(waits) if waits else None
