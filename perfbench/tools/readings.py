"""Read the numbers a cell's correctness limit is set from, on the chip.

    python3 perfbench/tools/readings.py --workload internlm2_1_8b.chat_burst \
        --seconds 12 --seeds 2147520001 ... 2147520012

In one process (the set-up is long): for each seed, the benchmark's weights
and prompts of that seed, a short window at the cell's own load, then the
sample's served tokens against the float32 reference (``token_gap_sd``:
the program) and the control at the same prompts and tokens, the
reference with its weights in fp8 (``control_gap_sd``).  One JSON line a
seed.  The limit in the configuration file lies between the largest
program reading and the smallest control reading (PERF.md gives both).
"""
from __future__ import annotations

import argparse
import json
import time

from _common import setup_path


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    setup_path()
    from _common import served
    from perfbench.harness import check
    srv = served(args.workload, args.seeds[0])
    for i, seed in enumerate(args.seeds):
        if i:
            srv.reseed(seed)
        run = srv.window(seed, args.seconds)
        t = time.perf_counter()
        cmp = srv.compare(run, seed, quantize=check.fp8)
        print(json.dumps({"seed": seed, "due": len(run.requests),
                          "batches": len(run.batches),
                          "compare_s": time.perf_counter() - t, **cmp}),
              flush=True)


if __name__ == "__main__":
    main()
