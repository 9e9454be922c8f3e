"""Find a cell's knee once, on the chip: serve its mix at several fixed
rates, in one process, and print one JSON line a window.

    python3 perfbench/tools/sweep.py --workload internlm2_20b_l12.chat_burst \
        --rates 0.6 0.8 1.0 --seconds 51 25 --seeds 2147510001 2147510002

A rate is sustained where the backlog at the close does not grow with the
run's length.  The chosen rate goes into the cell's mix file by hand; the
benchmark never searches for one.
"""
from __future__ import annotations

import argparse
import json

import numpy as np

from _common import setup_path


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, nargs="+", default=[51.0])
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    setup_path()
    from _common import served
    srv = served(args.workload, args.seeds[0])
    for rate in args.rates:
        for seconds in args.seconds:
            for seed in args.seeds:
                mix = {**srv.mix, "rate_rps": rate}
                run = srv.window(seed, seconds, mix=mix)
                lat = [r.finish_ms - r.due_ms if r.finish_ms is not None
                       else seconds * 1e3 for r in run.requests]
                done = [b for b in run.batches if b.record is not None]
                print(json.dumps({
                    "rate_rps": rate, "seconds": seconds, "seed": seed,
                    "due": len(run.requests),
                    "refused": sum(r.refused for r in run.requests),
                    "p50_ms": float(np.percentile(lat, 50)),
                    "p90_ms": float(np.percentile(lat, 90)),
                    "slo_attainment": sum(
                        1 for x, r in zip(lat, run.requests)
                        if r.finish_ms is not None and x <= mix["slo_ms"])
                    / len(lat),
                    "backlog_at_close": run.backlog_at_close,
                    "drain_s": run.drain_s, "n_batches": len(done),
                    "mean_batch": float(np.mean([len(b.uids) for b in done])),
                    "chip_ms_per_req": sum(b.record.wall_ms for b in done)
                    / max(len(run.requests), 1),
                    # each request: due, answered (ms), refused; each
                    # batch: device start and end (s), requests
                    "requests": [[round(r.due_ms), r.finish_ms and
                                  round(r.finish_ms), r.refused]
                                 for r in run.requests],
                    "batches": [[round(b.t_start, 3), round(b.t_end, 3),
                                 len(b.uids)] for b in done]}), flush=True)


if __name__ == "__main__":
    main()
