"""Serve internlm2_1_8b at its published widths on one TPU chip.

Drives the path a user calls, in one process: a full-width bf16
``RealExecutor`` behind ``launch.serve.serve_real``, so each request goes
Gateway -> ESG planner -> ClusterSim dispatch -> RealExecutor -> Pallas
kernels, for a few dozen requests of an ``mmpp`` scenario.  Then one
batch is served again through the same compiled executables and its
prefill and decode logits are compared with a plain reference: the same
model and weights on the jnp path (no Pallas kernels, no KV cache) in
float32.

    python chip_smoke.py                  # one TPU chip; fails elsewhere
    python chip_smoke.py --cpu-rehearsal  # the same phases on the CPU, at
                                          # the reduced config, kernels
                                          # interpreted; never "ok": true

Earlier lines report the device, warmup compile time, executor stats,
served count, SLO attainment, per-cell prefill/decode times, peak device
memory and the reference comparison.  The last line is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent
ARCH = "internlm2_1_8b"
CHECK_BUCKET = 2          # batch served again for the reference check

# bf16 against float32: each bf16 rounding costs up to 2^-9 relative; at
# the published widths with random weights the served logits sit about 1%
# of their range from the float32 ones.  Random weights also leave many
# near-ties at the top of the 92544-way vocabulary (top-1/top-2 gaps down
# to 1e-5), which that 1% flips, so the argmax floor only catches a
# scrambled model; the error bound is the tight check.
MAX_ERR_FRAC = 0.05       # max |served - ref| / max |ref|
MIN_TOP1 = 0.5            # share of (row, step) argmaxes that agree


def _shape(rehearsal: bool) -> dict:
    if rehearsal:
        return {"prompt_len": 32, "gen_len": 4, "batch_lattice": (1, 2),
                "n_requests": 8}
    return {"prompt_len": 512, "gen_len": 32, "batch_lattice": (1, 2, 4, 8),
            "n_requests": 32}


def reference_logits(cfg, params, tokens, prompt_len: int):
    """Float32 logits of the plain jnp path at every served step: one
    forward over the prompt and the generated tokens, read at positions
    ``prompt_len - 1`` onward."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models.model import RunOptions, get_model

    model = get_model(cfg, RunOptions(use_kernels=False, remat="none",
                                      param_dtype=jnp.float32,
                                      act_dtype=jnp.float32))

    def fwd(params, tokens):
        p32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        logits, _ = model.forward(p32, {"tokens": tokens})
        return logits[:, prompt_len - 1:]

    with jax.default_matmul_precision("float32"):
        return np.asarray(jax.jit(fwd)(params, jnp.asarray(tokens)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="run the same phases on the CPU at the reduced "
                         "config (kernels interpreted); never reports ok")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"[smoke] FAIL: no src/repro next to {pathlib.Path(__file__).name}"
              f"; run it from a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))

    import jax
    import numpy as np

    from repro.configs.registry import get_config, reduced
    from repro.launch.chip import device_info, use_compile_cache
    from repro.launch.serve import serve_real
    from repro.serving.executor import RealExecutor

    cache_dir = use_compile_cache()
    dev = device_info()
    print(f"[smoke] device: platform={dev['platform']} kind={dev['kind']} "
          f"count={dev['count']}")
    want = "cpu" if args.cpu_rehearsal else "tpu"
    if dev["platform"] != want:
        print(f"[smoke] FAIL: platform is {dev['platform']!r}, this run "
              f"needs {want!r}", file=sys.stderr)
        return 1
    print(f"[smoke] compile cache: {cache_dir}")

    cfg = get_config(ARCH)
    if args.cpu_rehearsal:
        cfg = reduced(cfg)
    shape = _shape(args.cpu_rehearsal)
    print(f"[smoke] model: {cfg.name} layers={cfg.n_layers} "
          f"d_model={cfg.d_model} heads={cfg.n_heads}/{cfg.n_kv_heads} "
          f"d_ff={cfg.d_ff} vocab={cfg.vocab} bf16 "
          f"({'reduced' if args.cpu_rehearsal else 'published widths'}); "
          f"prompt={shape['prompt_len']} gen={shape['gen_len']} "
          f"batches={shape['batch_lattice']}")

    ex = RealExecutor(cfg, batch_lattice=shape["batch_lattice"],
                      quotas=(1.0, 0.5), prompt_len=shape["prompt_len"],
                      gen_len=shape["gen_len"], seed=args.seed)
    bench = serve_real(ex, n_requests=shape["n_requests"], scenario="mmpp",
                       seed=args.seed,
                       log=lambda m: print(m.replace("[serve-real]",
                                                     "[smoke] serve:")))
    fails = []

    st = bench["executor"]
    print(f"[smoke] warmup: {bench['warmup']['warmup_compiles']} compiles "
          f"in {bench['warmup']['warmup_s']:.3f} s")
    print(f"[smoke] executor: compiles={st['compiles']} "
          f"executed={st['executed']} cache_hits={st['cache_hits']} "
          f"cache_misses={st['cache_misses']} "
          f"post_warmup_hit_rate={st['post_warmup_hit_rate']}")
    if st["post_warmup_hit_rate"] != 1.0:
        fails.append(f"post-warmup hit rate {st['post_warmup_hit_rate']}")
    if st["compiles"] != st["warmup_compiles"]:
        fails.append("compiled after warmup")
    tel = bench["telemetry"]
    print(f"[smoke] served: {bench['n_requests']} requests, "
          f"{st['executed']} batches, slo_attainment="
          f"{tel['slo_attainment']} shed={tel['shed']}")
    if not st["executed"]:
        fails.append("no batch executed")
    for c in bench["profile"]["cells"]:
        print(f"[smoke] cell batch={c['batch']} quota={c['quota']}: "
              f"prefill_ms={c['prefill_ms']:.3f} "
              f"decode_ms={c['decode_ms']:.3f} ({shape['gen_len']} steps)")
    mem = jax.devices()[0].memory_stats()
    print(f"[smoke] peak_bytes_in_use: "
          f"{mem['peak_bytes_in_use'] if mem else 'not measured'}")

    for (stage, bucket), kernel in sorted(ex.kernel_calls.items()):
        print(f"[smoke] executable {stage} batch={bucket}: "
              f"tpu_custom_call={kernel}")
        if not kernel and not args.cpu_rehearsal:
            fails.append(f"{stage} batch={bucket} calls no compiled kernel")

    tokens, served = ex.trace(CHECK_BUCKET)
    ex.shutdown()
    ref = reference_logits(cfg, ex.params, tokens, shape["prompt_len"])
    err = float(np.max(np.abs(served - ref)))
    scale = float(np.max(np.abs(ref)))
    top1 = float(np.mean(served.argmax(-1) == ref.argmax(-1)))
    print(f"[smoke] reference (jnp, float32) vs served (Pallas, bf16), "
          f"batch={CHECK_BUCKET}, prefill + {shape['gen_len']} decode steps: "
          f"max_abs_err={err:.6g} max_abs_ref={scale:.6g} "
          f"err_frac={err / scale:.6g} (limit {MAX_ERR_FRAC}) "
          f"top1_agreement={top1:.6g} (limit {MIN_TOP1})")
    if not err <= MAX_ERR_FRAC * scale:
        fails.append(f"logits differ from the reference by {err:.6g}")
    if not top1 >= MIN_TOP1:
        fails.append(f"top-1 agreement {top1:.6g}")

    for f in fails:
        print(f"[smoke] FAIL: {f}", file=sys.stderr)
    if fails:
        return 1
    if args.cpu_rehearsal:
        print(json.dumps({"ok": False, "rehearsal": "cpu", "device": dev}))
        return 0
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
