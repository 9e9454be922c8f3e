"""Kernels: the prefill attention kernel's share of its roofline: the least
time its calls need on this chip (causal work counted from their shapes)
over the time they took in the trace, in %."""
from perfbench.harness import work


def read(run):
    if run.trace is None or run.peak is None:
        return None
    need = took = 0.0
    for dev in run.trace["programs"]:
        for p in dev:
            for k, dur in p.get("kernels", []):
                if k["kernel"] == "flash_attention":
                    f, b = work.flash_attention(k["b"], k["h"], k["kv"],
                                                k["s"], k["d"], k["itemsize"])
                    need += work.roofline_s(f, b, run.peak)
                    took += dur
    return need / took * 100 if took else None
