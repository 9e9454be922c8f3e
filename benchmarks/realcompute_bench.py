"""Real-compute benchmark: the served path on one TPU chip, checked.

Runs ``launch.serve.serve_real`` for internlm2_1_8b at its published
widths in bf16: scenario traffic through Gateway → ESG planner →
``ClusterSim`` dispatch → the compile-cached ``RealExecutor`` → Pallas
kernels.  The planner's predicted stage latencies (from the profile
measured in the same process) are compared with the measured wall times
of the served batches.  Its times are device times, so it refuses to run
on anything but a TPU.

Guards on the fresh document:

1. **Zero recompiles after warmup** — the post-warmup compile-cache hit
   rate is exactly 1.0: batch-lattice bucketing means steady-state
   serving never sees a shape warmup didn't compile.
2. **Calibration** — mean absolute predicted-vs-measured stage-latency
   error <= 15% across the executed (batch, quota) cells.
3. **Provenance** — the planner ran against ``"measured"`` profiles.
4. **Lattice** — every executed bucket is on the measured lattice, and
   less quota is never faster.

Usage::

    python benchmarks/realcompute_bench.py [--n 48] [--out PATH]
"""
from __future__ import annotations

import argparse
import pathlib
import sys
from typing import Optional, Sequence

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "src"))

ARCH = "internlm2_1_8b"
N_REQUESTS = 48
BATCHES = (1, 2, 4, 8)
QUOTAS = (1.0, 0.5)
PROMPT_LEN = 512
GEN_LEN = 32
REPS = 5
SEED = 0

GUARDS = {
    "post_warmup_hit_rate": 1.0,     # exact: zero recompiles after warmup
    "max_mean_abs_err": 0.15,        # predicted vs measured stage latency
}


def run(n_requests: int = N_REQUESTS, out: Optional[str] = None) -> dict:
    from repro.configs.registry import get_config
    from repro.launch.serve import serve_real
    from repro.serving.executor import RealExecutor
    ex = RealExecutor(get_config(ARCH), batch_lattice=BATCHES,
                      quotas=QUOTAS, prompt_len=PROMPT_LEN, gen_len=GEN_LEN,
                      seed=SEED)
    doc = serve_real(ex, n_requests=n_requests, scenario="mmpp", seed=SEED,
                     reps=REPS, bench_out=out)
    ex.shutdown()
    return doc


def check_guards(doc: dict) -> list[str]:
    """Machine-independent checks on one benchmark document."""
    fails: list[str] = []
    ex = doc.get("executor", {})
    if ex.get("post_warmup_hit_rate") != GUARDS["post_warmup_hit_rate"]:
        fails.append(f"post-warmup compile-cache hit rate "
                     f"{ex.get('post_warmup_hit_rate')} != 1.0 "
                     f"(recompile after warmup)")
    if not ex.get("executed", 0):
        fails.append("no batches executed")
    if doc.get("mean_abs_err", 1.0) > GUARDS["max_mean_abs_err"]:
        fails.append(f"mean abs predicted-vs-measured error "
                     f"{doc.get('mean_abs_err'):.3f} > "
                     f"{GUARDS['max_mean_abs_err']}")
    prov = doc.get("telemetry", {}).get("profile_provenance", {})
    if prov.get(doc.get("arch")) != "measured":
        fails.append(f"planner profile provenance is "
                     f"{prov.get(doc.get('arch'))!r}, not 'measured'")
    lattice = set(doc.get("profile", {}).get("batch_lattice", []))
    for c in doc.get("cells", []):
        if c["batch"] not in lattice:
            fails.append(f"executed bucket {c['batch']} is off the "
                         f"measured lattice {sorted(lattice)}")
    qc = doc.get("quota_check", {})
    if qc.get("n_points") and qc.get("measured_exponent") is not None:
        # more quota is never slower: the fitted slowdown exponent of
        # the serialized-pass quota emulation must be positive
        if qc["measured_exponent"] <= 0:
            fails.append(f"measured quota exponent "
                         f"{qc['measured_exponent']:.3f} <= 0")
    return fails


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--n", type=int, default=N_REQUESTS)
    ap.add_argument("--out", default=None, metavar="PATH",
                    help="write the benchmark document here")
    args = ap.parse_args(argv)

    from repro.launch.chip import device_info, use_compile_cache
    use_compile_cache()
    dev = device_info()
    print(f"[realcompute-bench] device: {dev}")
    if dev["platform"] != "tpu":
        print("[realcompute-bench] needs a TPU: its times are device times")
        return 1
    fails = check_guards(run(n_requests=args.n, out=args.out))
    for f in fails:
        print(f"[realcompute-bench] GUARD FAIL: {f}")
    if not fails:
        print("[realcompute-bench] all guards passed")
    return 1 if fails else 0


if __name__ == "__main__":
    raise SystemExit(main())
