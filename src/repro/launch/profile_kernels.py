"""Measured-profile pipeline: time the real kernels over the lattice.

Times actual jax/Pallas execution (via ``serving.executor.RealExecutor``:
flash_attention prefill + scalar-prefetch flash_decode / WKV6 decode)
across the (batch-bucket, quota) lattice and emits a
``repro.measured_profile.v1`` JSON artifact that
``ProfileTable.from_measured`` loads in place of the zoo numbers.

Two cross-checks ride along in the artifact:

* **Roofline** — on a TPU, each quota-1.0 cell is compared against the
  analytic lower bound from ``launch/roofline.py`` (``model_flops`` /
  ``analytic_memory_bytes``) with the published peaks of the device's
  ``device_kind``.  Elsewhere there is no device to bound, and the check
  reports "not measured".
* **Quota exponent** — the fractional-quota slowdown measured from the
  serialized-pass emulation is fit to the profile model's power law and
  reported next to ``QUOTA_SLOWDOWN_EXP``.

CLI::

    PYTHONPATH=src python -m repro.launch.profile_kernels \
        --arch internlm2_1_8b --out profile.json --smoke

The model runs at its published widths in bf16.
"""
from __future__ import annotations

import argparse
import json
import math

from repro.configs.registry import ShapeSpec, get_config
from repro.core.profiles import QUOTA_SLOWDOWN_EXP
from repro.launch.chip import device_info, use_compile_cache
from repro.launch.roofline import model_flops, roofline

NOT_MEASURED = "not measured"


def roofline_check(executor, bucket: int, measured_ms: float,
                   stage: str, device: dict) -> dict:
    """Compare one measured quota-1.0 cell against the analytic roofline
    bound of ``device`` for the same config and shape."""
    if stage == "decode":                  # per decode step
        measured_ms = measured_ms / max(executor.gen_len, 1)
    out = {"stage": stage, "batch": bucket, "measured_ms": measured_ms}
    if device["platform"] != "tpu":
        return {**out, "bound_ms": NOT_MEASURED,
                "bound_fraction": NOT_MEASURED, "dominant": NOT_MEASURED}
    cfg = executor.cfg
    seq = executor.prompt_len if stage == "prefill" else 1
    shape = ShapeSpec(f"profile_{stage}", seq_len=seq,
                      global_batch=bucket, kind=stage)
    terms = roofline(cfg, shape,
                     flops_per_device=model_flops(cfg, shape),
                     bytes_hlo_upper=0.0,   # analytic memory model only
                     wire_bytes_per_device=0.0, n_chips=1,
                     device_kind=device["kind"])
    bound_ms = terms.bound_s * 1e3
    return {**out, "bound_ms": bound_ms,
            "bound_fraction": bound_ms / measured_ms if measured_ms else 0.0,
            "dominant": terms.dominant}


def quota_exponent(cells: list[dict]) -> dict:
    """Fit measured quota slowdowns to ``(1/q)^alpha`` per bucket and
    report the mean exponent next to the profile model's constant."""
    base = {c["batch"]: c["e2e_ms"] for c in cells if c["quota"] == 1.0}
    exps = []
    for c in cells:
        q = c["quota"]
        if q >= 1.0 or c["batch"] not in base or base[c["batch"]] <= 0:
            continue
        slowdown = c["e2e_ms"] / base[c["batch"]]
        if slowdown > 0:
            exps.append(math.log(slowdown) / math.log(1.0 / q))
    if not exps:
        return {"model_exponent": QUOTA_SLOWDOWN_EXP,
                "measured_exponent": None, "n_points": 0}
    mean = sum(exps) / len(exps)
    return {
        "model_exponent": QUOTA_SLOWDOWN_EXP,
        "measured_exponent": mean,
        "max_abs_dev": max(abs(e - mean) for e in exps),
        "n_points": len(exps),
    }


def build_artifact(executor, reps: int = 3, cold_ms: float = 0.0,
                   input_mb: float = 0.01, log=print) -> dict:
    """Measure every (bucket, quota) lattice cell on an already-warmed
    :class:`RealExecutor` and assemble the ``repro.measured_profile.v1``
    artifact ``ProfileTable.from_measured`` consumes."""
    if not executor._warmed:
        executor.warmup()
    device = device_info()
    cells, checks = [], []
    for bucket in executor.batch_lattice:
        for quota in executor.quotas:
            rec = executor.measure(bucket, quota, reps=reps)
            cells.append({
                "batch": bucket,
                "quota": quota,
                "prefill_ms": rec.prefill_ms,
                "decode_ms": rec.decode_ms,
                "e2e_ms": rec.wall_ms,
                "reps": reps,
            })
            log(f"  cell batch={bucket} quota={quota}: "
                f"{rec.wall_ms:.2f} ms ({rec.prefill_ms:.2f} prefill + "
                f"{rec.decode_ms:.2f} decode)")
            if quota == 1.0:
                checks.append(roofline_check(
                    executor, bucket, rec.prefill_ms, "prefill", device))
                checks.append(roofline_check(
                    executor, bucket, rec.decode_ms, "decode", device))
    return {
        "schema": "repro.measured_profile.v1",
        "arch": executor.arch,
        "reduced": executor.cfg != get_config(executor.arch),
        "backend": device["platform"],
        "device": device,
        "interpret": executor.interpret,
        "prompt_len": executor.prompt_len,
        "gen_len": executor.gen_len,
        "batch_lattice": list(executor.batch_lattice),
        "quota_lattice": list(executor.quotas),
        "cells": cells,
        "roofline": checks,
        "quota_check": quota_exponent(cells),
        "cold_ms": cold_ms,
        "input_mb": input_mb,
    }


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(
        description="Measure real kernel latencies over the batch/quota "
                    "lattice and emit a measured-profile artifact")
    ap.add_argument("--arch", default="internlm2_1_8b")
    ap.add_argument("--batches", type=int, nargs="+", default=[1, 2, 4, 8])
    ap.add_argument("--quotas", type=float, nargs="+", default=[1.0, 0.5])
    ap.add_argument("--prompt-len", type=int, default=512)
    ap.add_argument("--gen-len", type=int, default=32)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny lattice (batches 1,2; quota 1.0; 1 rep)")
    args = ap.parse_args(argv)
    if args.smoke:
        args.batches, args.quotas, args.reps = [1, 2], [1.0], 1

    from repro.serving.executor import RealExecutor

    use_compile_cache()
    ex = RealExecutor(get_config(args.arch),
                      batch_lattice=tuple(args.batches),
                      quotas=tuple(args.quotas),
                      prompt_len=args.prompt_len, gen_len=args.gen_len,
                      seed=args.seed)
    print(f"[profile] warming {args.arch} "
          f"({len(args.batches)} buckets x {len(ex.quotas)} quotas) ...")
    w = ex.warmup()
    print(f"[profile] warmup: {w['warmup_compiles']} compiles, "
          f"{w['warmup_s']:.1f}s, {w['cells']} cache cells")
    artifact = build_artifact(ex, reps=args.reps)
    ex.shutdown()
    qc = artifact["quota_check"]
    if qc["measured_exponent"] is not None:
        print(f"[profile] quota exponent: measured "
              f"{qc['measured_exponent']:.3f} vs model "
              f"{qc['model_exponent']} ({qc['n_points']} points)")
    if args.out:
        with open(args.out, "w") as f:
            json.dump(artifact, f, indent=2)
        print(f"[profile] wrote {args.out}")
    return artifact


if __name__ == "__main__":
    main()
