"""Serving launcher: ESG scheduling over the model zoo.

Two modes:

  * ``--emulate`` (default): the paper's controller (ESG or a baseline)
    schedules LM-pipeline workflows onto the emulated 16-host TPU cluster,
    with per-arch latency profiles from the v5e roofline model
    (cluster/tpu_profiles).  This is the "assigned architectures as
    servable functions" configuration.

  * ``--real``: serves ``--arch`` at its published widths, in bf16,
    through the full control plane: scenario arrivals enter via the
    Gateway, ESG_1Q plans batches against a *measured* profile table
    (``launch/profile_kernels``), and every dispatched task is executed
    for real by the compile-cached ``serving.executor.RealExecutor``
    (Pallas prefill + scalar-prefetch decode).  ``--bench-out`` writes
    the predicted-vs-measured comparison.
"""
from __future__ import annotations

import argparse

import numpy as np

from repro.cluster.emulator import ClusterSim
from repro.cluster.tpu_profiles import zoo_tables
from repro.cluster.workload import generate
from repro.core.profiles import Config, ProfileTable
from repro.core.scheduler import ESGScheduler
from repro.core.workflows import Workflow

# LM pipelines over the assigned architectures (DAG stage = one model)
ZOO_APPS = {
    "draft_verify": Workflow.pipeline(
        "draft_verify", ["rwkv6_1_6b", "internlm2_20b"]),
    "vlm_caption": Workflow.pipeline(
        "vlm_caption", ["internvl2_76b", "internlm2_1_8b"]),
    "code_review": Workflow.pipeline(
        "code_review", ["starcoder2_7b", "mixtral_8x22b"]),
    "music_tagging": Workflow.pipeline(
        "music_tagging", ["musicgen_medium", "hymba_1_5b",
                          "internlm2_1_8b"]),
}


def _make_scheduler(name: str, tables):
    if name == "esg":
        return ESGScheduler(ZOO_APPS, tables, risk_sigma=0.05)
    from repro.core.baselines.aquatope import AquatopeScheduler
    from repro.core.baselines.fastgshare import FaSTGShareScheduler
    from repro.core.baselines.infless import INFlessScheduler
    from repro.core.baselines.orion import OrionScheduler
    factories = {"infless": INFlessScheduler, "fastgshare": FaSTGShareScheduler,
                 "orion": OrionScheduler, "aquatope": AquatopeScheduler}
    return factories[name](ZOO_APPS, tables)


def emulate(setting: str = "moderate-normal", n: int = 200, seed: int = 0,
            scheduler: str = "esg", scenario: str | None = None,
            autoscaler: str | None = None, slo_mult: float = 1.0,
            overlap: bool = False, prefetch: bool = False,
            trace_out: str | None = None, metrics_out: str | None = None,
            audit_out: str | None = None, calibrate: bool = False,
            health_out: str | None = None,
            log=print) -> dict:
    """Emulated serving over the model zoo.

    Legacy mode (``scenario=None``) drives the paper's uniform-interval
    ``setting`` through ``cluster.workload.generate``.  Scenario mode runs
    the online-serving stack: ``serving.traces`` arrival engine behind the
    ``serving.gateway`` admission front end, with the warm-pool policy
    named by ``autoscaler`` (ewma | finegrained | vertical | none).

    Any of ``trace_out`` / ``metrics_out`` / ``audit_out`` /
    ``health_out`` attaches the flight recorder (``repro.obs``) and
    exports the Perfetto trace / metrics time-series / planner audit
    log / health-alert stream after the run.  ``calibrate=True`` closes
    the pricing loop: an online ``ProfileCalibrator`` subscribed to the
    audit stream corrects the planner's exec estimates per (app, stage)
    as the run progresses, and ``health_out`` additionally wires the
    SLO health engine's alerts into the gateway's admission check and
    the autoscaler's congestion hooks.
    """
    from repro.serving import Gateway, get_autoscaler, get_scenario

    tables = zoo_tables()
    profiles = {a: t.fn for a, t in tables.items()}
    sched = _make_scheduler(scheduler, tables)
    scaler = get_autoscaler(autoscaler) if autoscaler else None
    recorder = None
    health = None
    if trace_out or metrics_out or audit_out or calibrate or health_out:
        from repro.obs import HealthEngine, ProfileCalibrator, Recorder
        if health_out is not None:
            health = HealthEngine()
        # calibration consumes the audit stream, so the audit log is on
        # whenever either consumer needs it
        recorder = Recorder(health=health)
        if calibrate:
            if not hasattr(sched, "calibrator"):
                raise SystemExit(f"--calibrate requires the ESG scheduler "
                                 f"(got {scheduler!r})")
            sched.calibrator = ProfileCalibrator().attach(recorder.audit)
    sim = ClusterSim(ZOO_APPS, tables, profiles, sched, seed=seed,
                     autoscaler=scaler, overlap=overlap, prefetch=prefetch,
                     recorder=recorder)
    if health is not None and scaler is not None:
        scaler.health = health

    def _export():
        if recorder is None:
            return
        written = recorder.export(trace_out, metrics_out, audit_out,
                                  health_out)
        for kind, path in written.items():
            log(f"[obs] wrote {kind} -> {path}")
        cal = getattr(sched, "calibrator", None)
        if cal is not None:
            log(f"[obs] calibration: {cal.observations} observations, "
                f"{cal.updates} published factor updates")
        if health is not None:
            hs = health.summary()
            log(f"[obs] health: {hs['alerts_total']} alert transitions, "
                f"active={hs['active'] or 'none'}")

    if scenario is None:
        generate(sim, setting, n, profiles, seed=seed + 1)
        sim.run()
        s = sim.summary()
        log(f"[serve-emulate] {s['scheduler']}: hit={s['slo_hit_rate']:.3f} "
            f"cost=${s['total_cost']:.4f} mean_lat={s['mean_latency_ms']:.0f}ms "
            f"sched_ovh={s['mean_sched_overhead_ms']:.2f}ms")
        _export()
        return s
    gw = Gateway(sim, health=health)
    sc = get_scenario(scenario, app_names=list(ZOO_APPS))
    gw.inject(sc, n, seed=seed + 1, slo_mult=slo_mult)
    tel = gw.run()
    tel.scenario = scenario
    s = tel.summary()
    log(f"[serve-scenario] {scenario}/{s['scheduler']}/{s['autoscaler']}: "
        f"slo={s['slo_attainment']:.3f} $/1k={s['cost_per_1k']:.4f} "
        f"cold={s['cold_starts']} shed={s['shed']} "
        f"p95={s['latency']['p95_ms']:.0f}ms")
    _export()
    return s


def serve_real(ex, n_requests: int = 48, scenario: str = "mmpp",
               autoscaler: str | None = None, slo_mult: float = 8.0,
               seed: int = 0, profile_path: str | None = None,
               reps: int = 2, bench_out: str | None = None,
               log=print) -> dict:
    """Real-compute serving through the full control plane.

    Every request goes through the same Gateway → autoscaler →
    ``ClusterSim`` dispatch path the emulator uses: ESG_1Q plans batches
    against a *measured* profile table, and each dispatched task is
    executed for real by ``ex``, a compile-cached
    ``serving.executor.RealExecutor`` (Pallas prefill + scalar-prefetch
    decode on the config it was built with).  The caller owns ``ex``.

    The measured table comes from ``launch/profile_kernels`` — either
    built in-process (default) or loaded from ``profile_path``.  After
    the run, the per-cell measured wall times are compared against the
    planner's predicted stage latencies; the comparison (plus compile
    cache stats and roofline cross-checks) is the returned benchmark
    document, also written to ``bench_out``.
    """
    import json

    from repro.configs.registry import get_config
    from repro.launch.chip import device_info
    from repro.launch.profile_kernels import build_artifact
    from repro.serving import Gateway, get_autoscaler, get_scenario

    arch = ex.arch
    reduced = ex.cfg != get_config(arch)
    log(f"[serve-real] warming {arch} ({'reduced' if reduced else 'full'} "
        f"width, bf16): {len(ex.batch_lattice)} buckets x "
        f"{len(ex.quotas)} quotas ...")
    w = ex.warmup()
    log(f"[serve-real] warmup: {w['warmup_compiles']} compiles in "
        f"{w['warmup_s']:.1f}s ({w['cells']} cache cells)")

    if profile_path:
        with open(profile_path) as f:
            artifact = json.load(f)
        if artifact.get("arch") != arch:
            raise SystemExit(f"profile {profile_path} is for "
                             f"{artifact.get('arch')!r}, not {arch!r}")
    else:
        artifact = build_artifact(ex, reps=reps, log=lambda *_: None)
    table = ProfileTable.from_measured(artifact)
    log(f"[serve-real] measured profile: lattice={table.batch_lattice} "
        f"t1={table.fn.t1_ms:.1f}ms provenance={table.fn.provenance}")

    apps = {arch: Workflow.pipeline(arch, [arch])}
    tables = {arch: table}
    profiles = {arch: table.fn}
    sched = ESGScheduler(apps, tables, risk_sigma=0.05)
    scaler = get_autoscaler(autoscaler) if autoscaler else None
    # one shareable-GPU host: capacity pressure is what makes the
    # planner walk the batch lattice instead of serving everything at
    # batch 1 — the point of replaying through both paths.
    # count_overhead=False keeps simulated time fully decoupled from
    # this host's wall clock: with it on, planner wall time (inflated
    # by the executor worker's GIL share) would leak into the very
    # predictions the real measurements are compared against.
    sim = ClusterSim(apps, tables, profiles, sched, n_invokers=1,
                     vcpus=8, vgpus=1, noise_sigma=0.0, seed=seed,
                     count_overhead=False, autoscaler=scaler, executor=ex)
    gw = Gateway(sim)
    # pace arrivals to the measured service time: the stock scenario
    # rates target zoo latencies, which need not match this model's
    pace = max(table.fn.t1_ms / 2.0, 1.0)
    try:
        sc = get_scenario(scenario, app_names=[arch],
                          mean_interval_ms=pace)
    except TypeError:   # uniform-family scenarios have no rate knob
        sc = get_scenario(scenario, app_names=[arch])
    gw.inject(sc, n_requests, seed=seed + 1, slo_mult=slo_mult)
    tel = gw.run()
    tel.scenario = scenario
    s = tel.summary()
    recs = ex.drain()

    # predicted (planner profile) vs measured (device wall) per cell
    by_cell: dict[tuple, list] = {}
    for r in recs:
        if r.tid >= 0:
            by_cell.setdefault((r.bucket, r.quota), []).append(r.wall_ms)
    cells, err_sum, err_n = [], 0.0, 0
    for (bucket, quota), walls in sorted(by_cell.items()):
        c = Config(bucket, 1, 1)
        predicted = table.fn.exec_ms(
            c, quota_vgpu=quota if quota < 1.0 else None)
        # floor estimator, matching the profiling side: wall noise on a
        # shared host is one-sided, so the minimum is the reproducible
        # statistic for both legs of the comparison
        measured = float(np.min(walls))
        err = abs(predicted - measured) / measured if measured else 0.0
        cells.append({"batch": bucket, "quota": quota,
                      "n_executed": len(walls), "predicted_ms": predicted,
                      "measured_ms": measured, "abs_err": err})
        err_sum += err * len(walls)
        err_n += len(walls)
    mean_abs_err = err_sum / err_n if err_n else 0.0
    stats = ex.stats()

    bench = {
        "schema": "repro.realcompute_bench.v1",
        "arch": arch,
        "reduced": reduced,
        "scenario": scenario,
        "n_requests": n_requests,
        "seed": seed,
        "slo_mult": slo_mult,
        "device": device_info(),
        "interpret": ex.interpret,
        "profile": {k: artifact[k] for k in
                    ("batch_lattice", "quota_lattice", "prompt_len",
                     "gen_len", "cells")},
        "warmup": w,
        "executor": stats,
        "cells": cells,
        "mean_abs_err": mean_abs_err,
        "roofline": artifact["roofline"],
        "quota_check": artifact["quota_check"],
        "telemetry": {
            "slo_attainment": s["slo_attainment"],
            "scheduler": s["scheduler"],
            "autoscaler": s["autoscaler"],
            "cold_starts": s["cold_starts"],
            "shed": s["shed"],
            "profile_provenance": s.get("profile_provenance", {}),
        },
    }
    log(f"[serve-real] {arch}/{scenario}: "
        f"slo={s['slo_attainment']:.3f} executed={stats['executed']} "
        f"hit_rate={stats['post_warmup_hit_rate']} "
        f"mean_abs_err={mean_abs_err:.3f}")
    if bench_out:
        with open(bench_out, "w") as f:
            json.dump(bench, f, indent=2)
        log(f"[serve-real] wrote {bench_out}")
    return bench


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--real", action="store_true")
    ap.add_argument("--arch", default="internlm2_1_8b")
    ap.add_argument("--setting", default="moderate-normal")
    ap.add_argument("--n", type=int, default=200)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--scheduler", default="esg",
                    choices=["esg", "infless", "fastgshare", "orion",
                             "aquatope"])
    from repro.serving.traces import SCENARIOS
    ap.add_argument("--scenario", default=None, choices=sorted(SCENARIOS),
                    help="serving scenario; omit for the legacy uniform "
                         "setting")
    ap.add_argument("--autoscaler", default=None,
                    choices=["ewma", "finegrained", "vertical", "none"],
                    help="warm-pool policy (default: ewma); 'vertical' "
                         "adds fractional vGPU resizing of running pools")
    ap.add_argument("--slo-mult", type=float, default=1.0)
    ap.add_argument("--overlap", action="store_true",
                    help="overlapped swap pipeline: restart penalties "
                         "become async PCIe transfer completions")
    ap.add_argument("--prefetch", action="store_true",
                    help="predictive next-stage weight prefetch "
                         "(requires --overlap)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="record per-request spans and write a "
                         "Perfetto-loadable Chrome-trace JSON here")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="record windowed metrics and write JSON "
                         "(or CSV if PATH ends in .csv) here")
    ap.add_argument("--audit-out", default=None, metavar="PATH",
                    help="record the planner decision audit log "
                         "and write JSONL here")
    ap.add_argument("--calibrate", action="store_true",
                    help="close the pricing loop: correct the planner's "
                         "exec estimates online from the audit stream's "
                         "predicted-vs-realized records (ESG only)")
    ap.add_argument("--health-out", default=None, metavar="PATH",
                    help="run the SLO burn-rate health engine (alerts "
                         "feed the gateway + autoscaler) and write its "
                         "alert stream as JSONL here")
    ap.add_argument("--batches", type=int, nargs="+", default=[1, 2, 4, 8],
                    help="(--real) measured batch lattice")
    ap.add_argument("--quotas", type=float, nargs="+", default=[1.0, 0.5],
                    help="(--real) measured fractional-quota lattice")
    ap.add_argument("--gen-len", type=int, default=32,
                    help="(--real) decode steps per request")
    ap.add_argument("--prompt-len", type=int, default=512,
                    help="(--real) prompt length")
    ap.add_argument("--reps", type=int, default=2,
                    help="(--real) profiling reps per lattice cell")
    ap.add_argument("--profile", default=None, metavar="PATH",
                    help="(--real) load a measured-profile artifact "
                         "instead of profiling in-process")
    ap.add_argument("--bench-out", default=None, metavar="PATH",
                    help="(--real) write the predicted-vs-measured "
                         "benchmark JSON here")
    args = ap.parse_args()
    if args.real:
        from repro.configs.registry import get_config
        from repro.launch.chip import use_compile_cache
        from repro.serving.executor import RealExecutor
        use_compile_cache()
        ex = RealExecutor(get_config(args.arch),
                          batch_lattice=tuple(args.batches),
                          quotas=tuple(args.quotas),
                          prompt_len=args.prompt_len, gen_len=args.gen_len,
                          seed=args.seed)
        serve_real(ex, n_requests=args.n if args.n else 48,
                   scenario=args.scenario or "mmpp",
                   autoscaler=args.autoscaler, slo_mult=args.slo_mult
                   if args.slo_mult != 1.0 else 8.0, seed=args.seed,
                   profile_path=args.profile, reps=args.reps,
                   bench_out=args.bench_out)
        ex.shutdown()
    else:
        emulate(args.setting, args.n, seed=args.seed,
                scheduler=args.scheduler, scenario=args.scenario,
                autoscaler=args.autoscaler, slo_mult=args.slo_mult,
                overlap=args.overlap, prefetch=args.prefetch,
                trace_out=args.trace_out, metrics_out=args.metrics_out,
                audit_out=args.audit_out, calibrate=args.calibrate,
                health_out=args.health_out)


if __name__ == "__main__":
    main()
