"""Executor and model: mean device time of one decode step (one run of the
executor's decode program), from the trace."""


def read(run):
    if run.trace is None:
        return None
    steps = [p["dur_s"] for dev in run.trace["programs"] for p in dev
             if p["kind"] == "decode"]
    return sum(steps) / len(steps) * 1e3 if steps else None
