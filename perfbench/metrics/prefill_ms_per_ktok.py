"""Executor and model: device time of the prefill program per 1,000 prompt
tokens it processed (batch rows x prompt length, from the kernel's
shapes), from the trace."""


def read(run):
    if run.trace is None:
        return None
    t = ktok = 0.0
    for dev in run.trace["programs"]:
        for p in dev:
            if p["kind"] == "prefill" and p["shape"] is not None:
                t += p["dur_s"]
                ktok += p["shape"]["b"] * p["shape"]["s"] / 1e3
    return t * 1e3 / ktok if ktok else None
