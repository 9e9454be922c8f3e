"""One run of one cell: set-up, the measured window, the check, the result.

The served path is the program's own, wired as ``launch/serve.serve_real``
wires it: a ``RealExecutor`` at the configuration's widths behind
``Gateway`` -> ``ESGScheduler`` -> ``ClusterSim`` on one shareable-chip
host, with the profile table the executor measures at set-up.  The
benchmark brings the weights and prompts (from the seed), the arrivals and
SLO (from the mix file), the wall clock (``pump.Pump``) and the check.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import queue
import shutil
import tempfile
import time
from typing import Callable, Optional

import numpy as np

from perfbench.harness import check, devtrace, traffic, work
from perfbench.harness.pump import Capture, Pump, Request, SimExecutor

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
TRACE_AT_S, TRACE_LEN_S = 20.0, 10.0      # the traced span of the window
DRAIN_S = 60.0                            # wait for answers past the close


class NoChip(SystemExit):
    pass


def load_spec(path: pathlib.Path = ROOT / "BENCHMARK.json") -> dict:
    with open(path) as f:
        return json.load(f)


def load_config(spec: dict, name: str) -> dict:
    entry = next(c for c in spec["configs"] if c["name"] == name)
    with open(ROOT / entry["file"]) as f:
        return json.load(f)


def program_config(c: dict):
    """The program's ``ModelConfig`` for a configuration file, named as the
    program's registry names the architecture (``arch``)."""
    from repro.configs.registry import ModelConfig
    keys = {f.name for f in dataclasses.fields(ModelConfig)} - {"name"}
    return ModelConfig(name=c["arch"],
                       **{k: v for k, v in c.items() if k in keys})


@dataclasses.dataclass
class Run:
    """Everything a per-layer reader may read."""
    cell: dict
    config: dict
    mix: dict
    seconds: float
    requests: list
    batches: list
    plan_s: list
    peak: Optional[dict] = None
    trace: Optional[dict] = None
    window_end_s: float = 0.0
    compiles: int = 0               # compilations inside the window
    errors: int = 0                 # batches the executor failed
    backlog_at_close: int = 0       # requests unanswered at the close
    drain_s: float = 0.0            # past the close until the last answer
    advances: int = 0


def _window_compiles() -> Callable[[], int]:
    """Count XLA compilations and persistent-cache loads from now on."""
    import jax.monitoring as mon
    n = [0]

    def on(event, *a, **k):
        if event in ("/jax/core/compile/backend_compile_duration",
                     "/jax/compilation_cache/cache_retrieval_time_sec"):
            n[0] += 1

    mon.register_event_duration_secs_listener(on)
    return lambda: n[0]


class Served:
    """The set-up of one cell: the program's served path, warmed, with the
    benchmark's weights and prompts for ``seed``.  ``window`` runs the
    measured window; ``reseed`` (tools only) swaps weights and prompts so
    that one process can read many seeds."""

    def __init__(self, cell: dict, config: dict, mix: dict, seed: int,
                 on_cpu: bool = False):
        import jax

        devs = jax.devices()
        self.device = {"platform": devs[0].platform,
                       "kind": devs[0].device_kind, "count": len(devs)}
        if not on_cpu and (self.device["platform"] != "tpu"
                           or self.device["count"] < cell["chips"]):
            raise NoChip(f"cell {cell['name']} needs {cell['chips']} TPU "
                         f"chip(s); JAX finds {self.device['count']} "
                         f"{self.device['platform']} device(s)")
        self.peak = None if on_cpu else work.peaks(self.device["kind"])

        from repro.core.profiles import ProfileTable
        from repro.launch.profile_kernels import build_artifact
        from repro.serving.executor import RealExecutor

        self.cell, self.config, self.mix = cell, config, mix
        self.ref = check.reference(config)
        self.pcfg = program_config(config)
        ex = RealExecutor(self.pcfg, batch_lattice=tuple(mix["batch_lattice"]),
                          quotas=(1.0,), prompt_len=mix["prompt_len"],
                          gen_len=mix["gen_len"], seed=seed & 0x7FFFFFFF)
        self.ref.check_layout(ex.params, self.ref.weight_shapes(config))
        self.ex = ex
        self.reseed(seed)
        self.warm = ex.warmup()
        artifact = build_artifact(ex, reps=2, log=lambda *_: None)
        self.table = ProfileTable.from_measured(artifact)
        self.t0 = 0.0           # the window's start, on perf_counter
        self.capture = Capture(ex, lambda: time.perf_counter() - self.t0)

    def reseed(self, seed: int) -> None:
        import jax.numpy as jnp
        self.ex.params = self.weights = None     # the program's own draw
        self.weights = self.ref.make_weights(self.config, seed)
        self.ex.params = self.weights
        self.prompts = traffic.prompts(self.config["vocab"],
                                       self.mix["batch_lattice"],
                                       self.mix["prompt_len"], seed)
        self.ex._tokens = {b: jnp.asarray(p) for b, p in self.prompts.items()}

    def window(self, seed: int, seconds: float, trace_dir=None,
               mix: Optional[dict] = None) -> "Run":
        """Serve the cell's traffic for ``seconds`` on the wall clock, then
        wait for every answer due (a minute past the close at most)."""
        import jax

        from repro.cluster.emulator import ClusterSim
        from repro.cluster.workload import min_config_latency
        from repro.core.scheduler import ESGScheduler
        from repro.core.workflows import Workflow
        from repro.serving import Gateway
        from repro.serving.traces import Arrival

        mix = mix or self.mix
        ex, arch = self.ex, self.pcfg.name
        apps = {arch: Workflow.pipeline(arch, [arch])}
        tables, profiles = {arch: self.table}, {arch: self.table.fn}
        sched = ESGScheduler(apps, tables, risk_sigma=0.05)
        plan_s: list[float] = []
        plan = sched.plan

        def timed_plan(*a, **k):
            t = time.perf_counter()
            with jax.profiler.TraceAnnotation("plan"):
                out = plan(*a, **k)
            plan_s.append(time.perf_counter() - t)
            return out

        sched.plan = timed_plan
        done: queue.Queue = queue.Queue()
        simex = SimExecutor(ex, self.capture, done)
        sim = ClusterSim(apps, tables, profiles, sched, n_invokers=1,
                         vcpus=8, vgpus=1, noise_sigma=0.0, seed=seed,
                         count_overhead=False, executor=simex)
        gw = Gateway(sim)
        pump = Pump(sim, done)

        slo_ms = float(mix["slo_ms"])
        slo_mult = slo_ms / min_config_latency(apps[arch], profiles)
        closed = mix["kind"] == "closed"
        window_ms = seconds * 1e3
        due = (np.zeros(mix["clients"]) if closed
               else traffic.open_arrivals(mix, seconds))
        requests = {i: Request(uid=i, due_ms=float(t))
                    for i, t in enumerate(due)}

        class Fixed:        # the arrivals, as Gateway.inject reads them
            @staticmethod
            def arrivals(app_names, n, seed_):
                return [Arrival(i, float(t), arch) for i, t in enumerate(due)]

        gw.inject(Fixed, len(due), seed=seed, slo_mult=slo_mult)
        state = {"closed_at": None, "backlog": None}

        def on_batch(batch, t_ms):
            for row, uid in enumerate(batch.uids):
                r = requests[uid]
                r.finish_ms, r.batch, r.row = t_ms, batch, row
                if closed and state["closed_at"] is None:
                    if t_ms >= window_ms:
                        state["closed_at"] = t_ms
                    else:
                        nxt = len(requests)
                        requests[nxt] = Request(uid=nxt, due_ms=t_ms)
                        sim.add_arrival(arch, t_ms, slo_ms, nxt)

        pump.on_batch = on_batch

        def resolved() -> bool:
            return sim.n_completed + sim.n_shed >= len(requests)

        compiles = _window_compiles()
        before = (ex.compiles, ex.cache_misses)
        self.t0 = pump.t0 = time.perf_counter()
        tracer = None
        if trace_dir is not None:
            tracer = devtrace.Capture(trace_dir, pump.t0,
                                      min(TRACE_AT_S, 0.4 * seconds),
                                      min(TRACE_LEN_S, 0.4 * seconds))
            tracer.start()
        if closed:
            pump.run_until(lambda: state["closed_at"] is not None,
                           window_ms + DRAIN_S * 1e3)
        else:
            pump.run_until(lambda: False, window_ms)
        backlog = sum(1 for r in requests.values() if r.finish_ms is None)
        pump.run_until(resolved, window_ms + DRAIN_S * 1e3)
        drain_s = pump.now_ms() / 1e3 - seconds
        ex.drain(timeout=DRAIN_S)
        while not done.empty():
            pump._take(done.get())
        if tracer is not None:
            tracer.join()
            if tracer.error is not None:
                raise tracer.error
        for inst in sim.shed:
            requests[inst.uid].refused = True
        return Run(cell=self.cell, config=self.config, mix=mix,
                   seconds=seconds,
                   requests=[requests[u] for u in sorted(requests)],
                   batches=simex.batches, plan_s=plan_s, peak=self.peak,
                   window_end_s=(state["closed_at"] or window_ms) / 1e3,
                   compiles=compiles() + (ex.compiles - before[0])
                   + (ex.cache_misses - before[1]),
                   errors=len(pump.errors), backlog_at_close=backlog,
                   drain_s=drain_s, advances=pump.advances)

    def compare(self, run: "Run", seed: int, quantize=None) -> dict:
        """The served tokens of a sample of ``run``'s requests against the
        reference (and, given ``quantize``, the control's picks)."""
        g_len = run.mix["gen_len"]
        rows, unserved = check.served_rows(run.batches, run.requests, g_len)
        lost = sum(1 for r in run.requests
                   if r.finish_ms is None and not r.refused)
        picked = check.sample(list(rows), seed)
        cmp = {"token_gap_sd": float("inf"), "tokens_compared": 0}
        if picked:
            seqs, of = check.sequences(picked, rows, self.prompts, g_len)
            cmp = check.compare(self.config, self.weights, seqs, of,
                                run.mix["prompt_len"], quantize)
        cmp.update(lost_requests=lost + unserved + run.errors,
                   sampled=len(picked))
        return cmp


def run_cell(cell_name: str, seed: int, seconds: float, trace: bool,
             t_process: float, spec: Optional[dict] = None,
             config_override: Optional[dict] = None,
             mix_override: Optional[dict] = None, on_cpu: bool = False,
             fault: Optional[Callable] = None, log=print) -> dict:
    """Run ``cell_name`` once and return the result line's object.

    The overrides, ``on_cpu`` (which skips the look for a chip) and
    ``fault`` (which may break the executor under the harness after
    set-up) are for the tests, which drive a run on the CPU at a small
    size."""
    import jax

    spec = spec or load_spec()
    cell = next(w for w in spec["workloads"] if w["name"] == cell_name)
    config = {**load_config(spec, cell["config"]), **(config_override or {})}
    mix = {**traffic.load_mix(cell["traffic"], cell["config"]),
           **(mix_override or {})}
    served = Served(cell, config, mix, seed, on_cpu=on_cpu)
    if fault is not None:
        fault(served.ex)
    tdir = tempfile.mkdtemp(prefix="trace-") if trace else None
    setup_s = time.perf_counter() - t_process
    run = served.window(seed, seconds, trace_dir=tdir)
    mem = jax.devices()[0].memory_stats() or {}
    memory_peak = int(mem.get("peak_bytes_in_use", 0))

    served.ex.shutdown()
    served.ex._exe.clear()
    t_ref = time.perf_counter()
    cmp = served.compare(run, seed)
    log(f"[check] reference over {cmp['sampled']} sampled requests, "
        f"{cmp['tokens_compared']} served tokens, in "
        f"{time.perf_counter() - t_ref:.3f} s")
    checks = {
        "token_gap_sd": {"value": cmp["token_gap_sd"],
                         "limit": config["check"]["token_gap_sd"],
                         "holds": "<="},
        "tokens_compared": {"value": cmp["tokens_compared"],
                            "limit": check.MIN_TOKENS, "holds": ">="},
        "lost_requests": {"value": cmp["lost_requests"], "limit": 0,
                          "holds": "<="},
        "window_compiles": {"value": run.compiles, "limit": 0,
                            "holds": "<="},
    }
    refused = sum(r.refused for r in run.requests)
    finished = sum(r.finish_ms is not None for r in run.requests)
    log(f"[result] {cell_name} seed={seed}: {len(run.requests)} due, "
        f"{finished} completed, {refused} refused, "
        f"{cmp['lost_requests']} lost; {len(run.batches)} batches; "
        f"{run.advances} advances; backlog at close {run.backlog_at_close},"
        f" drain {run.drain_s:.3f} s; setup {setup_s:.3f} s (warmup "
        f"{served.warm['warmup_s']:.3f} s, {served.warm['warmup_compiles']} "
        f"compiles); peak {memory_peak} B")

    out = {"correct": all(_holds(c) for c in checks.values()),
           "attempted": len(run.requests),
           "failed": refused + cmp["lost_requests"]}
    dev_out = {**served.device, "memory_peak_bytes": memory_peak}
    if trace:
        red = devtrace.reduce(devtrace.load(tdir))
        shutil.rmtree(tdir, ignore_errors=True)
        run.trace = red
        dev_out.update(busy_s=red["busy_s"], window_s=red["window_s"])
        out["metrics"] = per_layer(spec, run)
        out["breakdown"] = {"device_ops": red["device_ops"],
                            "idle_gaps": red["idle_gaps"]}
    else:
        out["metrics"] = end_to_end(spec, run, setup_s)
    out["device"] = dev_out
    out["checks"] = checks
    return out


def _holds(c: dict) -> bool:
    v, lim = c["value"], c["limit"]
    return v <= lim if c["holds"] == "<=" else v >= lim


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------
def _applies(metric: dict, cell: dict, spec: dict) -> bool:
    if "workloads" in metric:
        return cell["name"] in metric["workloads"]
    moved = next(m for m in spec["end_to_end"] if m["name"] == metric["moves"])
    return "workloads" not in moved or cell["name"] in moved["workloads"]


def end_to_end(spec: dict, run: Run, setup_s: float) -> dict:
    """The cell's end-to-end metrics, each taken over all the work and all
    the time of the window (host clock)."""
    reqs, cell = run.requests, run.cell
    slo = float(run.mix["slo_ms"])
    window_ms = run.seconds * 1e3
    done = [r for r in reqs if r.finish_ms is not None]
    # a request refused or never finished counts as taking the whole window
    lat = np.array([r.finish_ms - r.due_ms if r.finish_ms is not None
                    else window_ms for r in reqs])
    batches = [b for b in run.batches if b.record is not None]
    values = {
        "setup_s": setup_s,
        "e2e_p90_ms": float(np.percentile(lat, 90)),
        "slo_attainment": sum(1 for r in done
                              if r.finish_ms - r.due_ms <= slo) / len(reqs),
        "chip_ms_per_req": (sum(b.record.wall_ms * b.quota for b in batches)
                            / len(done)) if done else float("inf"),
        "tokens_per_s": sum(run.mix["gen_len"] for r in done
                            if r.finish_ms <= run.window_end_s * 1e3)
        / run.window_end_s,
    }
    return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in spec["end_to_end"]
            if cell["name"] in m.get("workloads", [cell["name"]])}


def reader(name: str):
    """The reader of a per-layer metric: ``metrics/<name>.py``, else the
    one of the quantity it splits (``metrics/<name before the dot>.py``)."""
    for stem in (name, name.split(".", 1)[0]):
        path = BENCH / "metrics" / f"{stem}.py"
        if path.exists():
            s = importlib.util.spec_from_file_location(
                f"perfbench_metric_{stem.replace('.', '_')}", path)
            mod = importlib.util.module_from_spec(s)
            s.loader.exec_module(mod)
            return mod.read
    raise SystemExit(f"no reader for per-layer metric {name!r}")


def per_layer(spec: dict, run: Run) -> dict:
    out = {}
    for m in spec["per_layer"]:
        if not _applies(m, run.cell, spec):
            continue
        v = reader(m["name"])(run)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out
