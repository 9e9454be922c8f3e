"""Kernels: the decode attention kernel's share of its roofline: the least
time its calls need on this chip (the cache read up to each step's
position, which the step's place after its batch's prefill gives) over the
time they took in the trace, in %.  Calls whose step the trace does not
show (a batch whose prefill ran before the trace began) are left out."""
from perfbench.harness import work


def read(run):
    if run.trace is None or run.peak is None:
        return None
    need = took = 0.0
    for dev in run.trace["programs"]:
        for p in dev:
            if p["kind"] != "decode" or p["step"] is None \
                    or p["prompt"] is None:
                continue
            t = p["prompt"] + p["step"]
            for k, dur in p["kernels"]:
                f, b = work.flash_decode(k["b"], k["h"], k["kv"], t, k["d"],
                                         k["itemsize"])
                need += work.roofline_s(f, b, run.peak)
                took += dur
    return need / took * 100 if took else None
