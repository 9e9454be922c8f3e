"""Executor and model: host time to dispatch one decode step, from the
program's ``executor.decode.enqueue.head`` spans.  Each times a pass's first
``DECODE_HEAD_STEPS`` decode steps, fewer than the runtime keeps in flight,
so none of them waits for the device.  Σ span over the steps they
dispatched, in ms a step."""


def read(run):
    head = [(t1 - t0) / 1e6 for b in run.batches if b.record is not None
            for name, t0, t1 in getattr(b.record, "spans", ())
            if name == "executor.decode.enqueue.head"]
    if not head:
        return None
    from repro.serving.executor import DECODE_HEAD_STEPS
    return sum(head) / (len(head) * min(DECODE_HEAD_STEPS,
                                        run.mix["gen_len"]))
