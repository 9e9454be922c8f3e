import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run driver (deliverable e).

For every (architecture x input shape) cell:  lower + compile the step on
the production mesh (16x16 single-pod, and 2x16x16 multi-pod), print
``memory_analysis()`` (proves it fits) and ``cost_analysis()`` (feeds
§Roofline), parse the optimized HLO for collective wire bytes, and persist
everything to ``benchmarks/results/dryrun/<cell>.json``.

The XLA_FLAGS line above MUST stay before any other import — jax locks the
device count at first init.

Usage:
  python -m repro.launch.dryrun --arch internlm2_20b --shape train_4k
  python -m repro.launch.dryrun --all [--mesh single|multi|both]
"""
import argparse
import json
import pathlib
import time
import traceback

import jax

from repro.configs.registry import (ARCH_IDS, SHAPES, cell_applicable,
                                    get_config)
from repro.launch import collectives as coll
from repro.launch import hlo_analysis
from repro.launch import roofline as rf
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import build_step
from repro.models.model import RunOptions

RESULTS_DIR = pathlib.Path(__file__).resolve().parents[3] / \
    "benchmarks" / "results" / "dryrun"


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             opts: RunOptions = RunOptions(), save: bool = True,
             tag: str = "") -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = cell_applicable(cfg, shape)
    mesh_name = "multi" if multi_pod else "single"
    cell = f"{arch}__{shape_name}__{mesh_name}" + (f"__{tag}" if tag else "")
    out: dict = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                 "tag": tag, "applicable": ok}
    if not ok:
        out["skip_reason"] = why
        if save:
            _save(cell, out)
        return out

    t0 = time.time()
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        n_chips = mesh.devices.size
        fn, in_sh, out_sh, input_specs, donate = build_step(
            cfg, shape, mesh, opts)
        with mesh:
            jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                             donate_argnums=donate)
            lowered = jitted.lower(*input_specs)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower

        ma = compiled.memory_analysis()
        ca = compiled.cost_analysis()
        if isinstance(ca, list):
            ca = ca[0]
        hlo = compiled.as_text()
        cinfo = coll.parse_collectives(hlo, n_chips)
        # trip-count-aware re-analysis: cost_analysis counts while bodies
        # (the layer scan!) once — see hlo_analysis docstring
        hinfo = hlo_analysis.analyze(hlo)
        flops = float(hinfo["flops"])
        byts = float(hinfo["bytes"])
        terms = rf.roofline(cfg, shape, flops, byts,
                            cinfo["total_wire_bytes"], n_chips,
                            device_kind=rf.V5E)
        out.update({
            "status": "ok",
            "n_chips": n_chips,
            "lower_s": t_lower,
            "compile_s": t_compile,
            "memory": {
                "argument_bytes": ma.argument_size_in_bytes,
                "output_bytes": ma.output_size_in_bytes,
                "temp_bytes": ma.temp_size_in_bytes,
                "alias_bytes": ma.alias_size_in_bytes,
                "peak_bytes_est": ma.argument_size_in_bytes
                + ma.output_size_in_bytes + ma.temp_size_in_bytes
                - ma.alias_size_in_bytes,
            },
            "cost": {"flops": flops, "bytes_hlo_upper": byts,
                     "bytes_analytic": rf.analytic_memory_bytes(
                         cfg, shape, n_chips),
                     "xla_flops_flat": float(ca.get("flops", 0.0)),
                     "xla_bytes_flat": float(ca.get("bytes accessed", 0.0))},
            "collectives": {k: v for k, v in cinfo.items() if k != "items"},
            "collective_items": cinfo["items"][:64],
            "roofline": terms.to_dict(),
        })
        fits = out["memory"]["peak_bytes_est"] <= 16e9
        out["fits_hbm16g"] = bool(fits)
    except Exception as e:  # noqa: BLE001 — a failing cell is a bug report
        out.update({"status": "error", "error": f"{type(e).__name__}: {e}",
                    "traceback": traceback.format_exc()[-4000:]})
    if save:
        _save(cell, out)
    return out


def _save(cell: str, out: dict):
    RESULTS_DIR.mkdir(parents=True, exist_ok=True)
    with open(RESULTS_DIR / f"{cell}.json", "w") as f:
        json.dump(out, f, indent=1, default=str)


def _fmt(out: dict) -> str:
    if not out.get("applicable", True):
        return f"SKIP ({out['skip_reason'][:60]})"
    if out.get("status") != "ok":
        return f"ERROR {out.get('error', '?')[:120]}"
    r = out["roofline"]
    mem_gb = out["memory"]["peak_bytes_est"] / 1e9
    return (f"ok compile={out['compile_s']:.1f}s mem={mem_gb:.2f}GB "
            f"comp={r['compute_s']*1e3:.2f}ms mem={r['memory_s']*1e3:.2f}ms "
            f"coll={r['collective_s']*1e3:.2f}ms dom={r['dominant'][:4]} "
            f"useful={r['useful_ratio']:.2f}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--remat", default="full", choices=["full", "none"])
    ap.add_argument("--attn-chunk", type=int, default=1024)
    ap.add_argument("--tag", default="")
    ap.add_argument("--causal-pair-scan", action="store_true")
    ap.add_argument("--sharding", default="auto",
                    choices=["auto", "2d", "dp_only"])
    ap.add_argument("--no-seq-shard-decode", action="store_true")
    ap.add_argument("--explicit-tp", action="store_true")
    args = ap.parse_args()

    opts = RunOptions(remat=args.remat, attn_chunk=args.attn_chunk,
                      causal_pair_scan=args.causal_pair_scan,
                      sharding_mode=args.sharding,
                      seq_shard_decode=not args.no_seq_shard_decode,
                      explicit_tp_ffn=args.explicit_tp)
    archs = ARCH_IDS if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    n_fail = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                out = run_cell(arch, shape, mp, opts, tag=args.tag)
                status = _fmt(out)
                print(f"{arch:26s} {shape:12s} "
                      f"{'multi ' if mp else 'single'} {status}", flush=True)
                if out.get("status") == "error":
                    n_fail += 1
    if n_fail:
        raise SystemExit(f"{n_fail} cells failed")


if __name__ == "__main__":
    main()
