"""Whole step: model FLOPs of the requests served over the chip time billed
for their batches (each batch's wall time around ``block_until_ready``, from
the executor's own records, times its quota) at the chip's peak, in %.
Padding rows and idle time between batches earn nothing."""
from perfbench.harness import work


def read(run):
    if run.peak is None:
        return None
    served = [b for b in run.batches if b.record is not None]
    billed = sum(b.record.wall_ms * b.quota for b in served) / 1e3
    per_req = work.request_flops(run.config, run.mix["prompt_len"],
                                 run.mix["gen_len"])
    flops = per_req * sum(len(b.uids) for b in served)
    return flops / (billed * run.peak["bf16_flops_per_s"]) * 100 \
        if billed else None
