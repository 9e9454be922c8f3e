"""Real-compute bridge: ops-level kernel parity, measured profiles,
compile-cache invariants, and emulator bit-identity with the executor
attached.

Kernel tests run the *ops-layer* wrappers (the exact entry points the
serving executor and the model use, jit + layout adapters + interpret
mode on the CPU included) against the jnp references — the
kernel-layer parity lives in tests/test_kernels.py.
"""
from __future__ import annotations

import json
import os
import pathlib
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]
ATOL = 2e-5          # float32 interpret mode: numerically tight
RTOL = 2e-5
WKV_TOL = 5e-3       # chunked scan reassociates the state recurrence


def _rand(key, shape, dtype=jnp.float32):
    return jax.random.normal(key, shape, dtype)


# ---- ops-level parity: flash_attention ------------------------------------

@pytest.mark.parametrize("kw", [
    {"causal": True},
    {"causal": True, "window": 16},
    {"causal": True, "local_block": 8},
])
def test_flash_attention_ops_parity(kw):
    from repro.kernels.flash_attention.ops import (flash_attention,
                                                   flash_attention_oracle)
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    b, s, h, kvh, d = 2, 64, 4, 2, 16
    q = _rand(ks[0], (b, s, h, d))
    k = _rand(ks[1], (b, s, kvh, d))
    v = _rand(ks[2], (b, s, kvh, d))
    out = flash_attention(q, k, v, **kw)
    ref = flash_attention_oracle(q, k, v, **kw)
    np.testing.assert_allclose(out, ref, atol=ATOL, rtol=RTOL)


# ---- ops-level parity: flash_decode (dynamic t) ---------------------------

@pytest.mark.parametrize("kw,t", [
    ({}, 17),                                  # linear cache, mid-fill
    ({}, 63),                                  # linear cache, last slot
    ({"window": 16}, 40),                      # sliding-window ring
    ({"local_block": 8}, 29),                  # chunked-local ring
])
def test_flash_decode_at_ops_parity(kw, t):
    from repro.kernels.flash_decode.ops import flash_decode_at
    from repro.kernels.flash_decode.ref import decode_ref
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    b, s, h, kvh, d = 2, 64, 4, 2, 16
    q = _rand(ks[0], (b, h, d))
    kc = _rand(ks[1], (b, s, kvh, d))
    vc = _rand(ks[2], (b, s, kvh, d))
    out = flash_decode_at(q, kc, vc, t, **kw)
    ref = decode_ref(q, kc, vc, t=t, **kw)
    np.testing.assert_allclose(out, ref, atol=ATOL, rtol=RTOL)


def test_flash_decode_at_one_executable_for_all_t():
    """The point of scalar prefetch: every position t reuses ONE jit
    cache entry — a static t would compile per token and break the
    executor's zero-recompile invariant."""
    from repro.kernels.flash_decode.ops import flash_decode_at
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    b, s, h, kvh, d = 1, 32, 2, 1, 8
    q = _rand(ks[0], (b, h, d))
    kc = _rand(ks[1], (b, s, kvh, d))
    vc = _rand(ks[2], (b, s, kvh, d))
    flash_decode_at(q, kc, vc, 0)              # prime the jit cache
    before = flash_decode_at._cache_size()
    for t in (1, 7, 31):
        flash_decode_at(q, kc, vc, t)
    assert flash_decode_at._cache_size() == before


def _stacked_cache(key, n_layers, b, s=64, kvh=2, d=16):
    """Every layer's K and V stacked as the decode scan carries them:
    (L·B, S, KV, D), layer ``l`` at rows ``l·B .. l·B + B - 1``."""
    ks = jax.random.split(key, 2)
    return (_rand(ks[0], (n_layers * b, s, kvh, d)),
            _rand(ks[1], (n_layers * b, s, kvh, d)))


@pytest.mark.parametrize("b", [1, 4])
@pytest.mark.parametrize("layer", [0, 2, 4])       # first, middle, last
def test_flash_decode_at_layer_reads_its_rows_in_place(b, layer):
    """With ``layer`` the kernel reads that layer's rows of the stacked
    cache: the same numbers as the call on the layer's own slice."""
    from repro.kernels.flash_decode.ops import flash_decode_at
    n_layers, h, d = 5, 4, 16
    q = _rand(jax.random.PRNGKey(3), (b, h, d))
    kc, vc = _stacked_cache(jax.random.PRNGKey(4), n_layers, b)
    rows = slice(layer * b, (layer + 1) * b)
    for t in (0, 17, 63):
        out = flash_decode_at(q, kc, vc, t, layer)
        per_layer = flash_decode_at(q, kc[rows], vc[rows], t)
        np.testing.assert_array_equal(out, per_layer)


def test_flash_decode_at_one_executable_for_all_t_and_layers():
    """The layer offset is scalar-prefetched like ``t``: one jit cache
    entry serves every (t, layer) of the decode loop."""
    from repro.kernels.flash_decode.ops import flash_decode_at
    n_layers, b, h, d = 3, 2, 2, 8
    q = _rand(jax.random.PRNGKey(5), (b, h, d))
    kc, vc = _stacked_cache(jax.random.PRNGKey(6), n_layers, b, s=32,
                            kvh=1, d=d)
    layer = jnp.asarray(0, jnp.int32)
    flash_decode_at(q, kc, vc, 0, layer)       # prime the jit cache
    before = flash_decode_at._cache_size()
    for t in (1, 7, 31):
        for layer in range(n_layers):
            flash_decode_at(q, kc, vc, t, jnp.asarray(layer, jnp.int32))
    assert flash_decode_at._cache_size() == before


# ---- ops-level parity: rwkv6 wkv6 -----------------------------------------

def test_wkv6_ops_parity():
    from repro.kernels.rwkv6.ops import wkv6
    from repro.kernels.rwkv6.ref import wkv6_ref
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    b, t, h, k = 2, 48, 2, 8                   # t=48: exercises padding
    r = _rand(ks[0], (b, t, h, k))
    kk = _rand(ks[1], (b, t, h, k))
    v = _rand(ks[2], (b, t, h, k))
    lw = -jnp.exp(_rand(ks[3], (b, t, h, k)))  # log-decay < 0
    u = _rand(ks[4], (h, k))
    s0 = jnp.zeros((b, h, k, k), jnp.float32)
    y, s_fin = wkv6(r, kk, v, lw, u, s0)
    yr, sr = wkv6_ref(jnp.moveaxis(r, 1, 2), jnp.moveaxis(kk, 1, 2),
                      jnp.moveaxis(v, 1, 2), jnp.moveaxis(lw, 1, 2),
                      u, s0)
    np.testing.assert_allclose(y, jnp.moveaxis(yr, 1, 2),
                               atol=WKV_TOL, rtol=WKV_TOL)
    np.testing.assert_allclose(s_fin, sr, atol=WKV_TOL, rtol=WKV_TOL)


# ---- measured profiles ----------------------------------------------------

def _artifact():
    return {
        "schema": "repro.measured_profile.v1",
        "arch": "toy",
        "backend": "cpu", "interpret": True,
        "prompt_len": 8, "gen_len": 2,
        "batch_lattice": [1, 2, 4], "quota_lattice": [1.0, 0.5],
        "cells": [
            {"batch": 1, "quota": 1.0, "e2e_ms": 10.0,
             "prefill_ms": 4.0, "decode_ms": 6.0, "reps": 3},
            {"batch": 2, "quota": 1.0, "e2e_ms": 14.0,
             "prefill_ms": 6.0, "decode_ms": 8.0, "reps": 3},
            {"batch": 4, "quota": 1.0, "e2e_ms": 22.0,
             "prefill_ms": 10.0, "decode_ms": 12.0, "reps": 3},
            {"batch": 2, "quota": 0.5, "e2e_ms": 26.0,
             "prefill_ms": 11.0, "decode_ms": 15.0, "reps": 3},
        ],
        "cold_ms": 100.0, "input_mb": 0.02,
    }


def test_measured_profile_lattice_lookup():
    from repro.core.profiles import Config, ProfileTable
    t = ProfileTable.from_measured(_artifact())
    assert t.fn.provenance == "measured"
    assert t.batch_lattice == (1, 2, 4)
    assert t.fn.cold_ms == 100.0
    # exact lattice cells
    assert t.fn.exec_ms(Config(1, 1, 1)) == 10.0
    assert t.fn.exec_ms(Config(4, 1, 1)) == 22.0
    # off-lattice batch rounds UP to the covering bucket
    assert t.fn.exec_ms(Config(3, 1, 1)) == 22.0
    # beyond the lattice: waves of the largest bucket
    assert t.fn.exec_ms(Config(8, 1, 1)) == 44.0
    # measured fractional-quota cell wins over the power-law model
    assert t.fn.exec_ms(Config(2, 1, 1), quota_vgpu=0.5) == 26.0
    # unmeasured quota falls back to the power law on the bucket base
    model = 10.0 * t.fn.quota_factor(Config(1, 1, 1), 0.5)
    assert t.fn.exec_ms(Config(1, 1, 1), quota_vgpu=0.5) == \
        pytest.approx(model)


def test_measured_profile_requires_full_quota_cells():
    from repro.core.profiles import ProfileTable
    art = _artifact()
    art["cells"] = [c for c in art["cells"] if c["quota"] != 1.0]
    with pytest.raises(ValueError):
        ProfileTable.from_measured(art)


def test_zoo_profiles_report_zoo_provenance():
    from repro.cluster.tpu_profiles import zoo_tables
    t = next(iter(zoo_tables().values()))
    assert getattr(t.fn, "provenance", "zoo") == "zoo"


# ---- executor compile cache ----------------------------------------------

@pytest.fixture(scope="module")
def executor():
    from repro.configs.registry import get_config, reduced
    from repro.serving.executor import RealExecutor
    ex = RealExecutor(reduced(get_config("internlm2_1_8b")),
                      batch_lattice=(1, 2),
                      quotas=(1.0, 0.5), prompt_len=8, gen_len=2, seed=0)
    ex.warmup()
    yield ex
    ex.shutdown()


class _FakeTask:
    """A dispatched task as ``ClusterSim._dispatch`` hands it over: jobs
    made ready 10, 20, ... ms before the dispatch at 100 ms, two more
    jobs left behind in the stage queue."""
    _next = iter(range(10_000))

    def __init__(self, n_jobs, slices=4):
        from repro.cluster.emulator import AppInstance, Job
        from repro.core.profiles import Config
        self.tid = next(self._next)
        self.func = "internlm2_1_8b"
        self.stage = "0:internlm2_1_8b"
        self.dispatch_ms = 100.0
        self.jobs = [Job(AppInstance(None, 1000 * self.tid + i, 0.0, 1e3),
                         self.stage, self.dispatch_ms - 10.0 * (i + 1))
                     for i in range(n_jobs)]
        self.queued = n_jobs + 2
        self.config = Config(n_jobs, 1, 1)
        self.quota_slices = slices


def test_executor_zero_recompiles_after_warmup(executor):
    compiles_before = executor.compiles
    for n, slices in [(1, 4), (2, 4), (2, 2), (1, 2), (2, 4), (1, 4)]:
        executor.submit(_FakeTask(n, slices))
    executor.drain()
    assert executor.compiles == compiles_before      # zero new XLA compiles
    assert executor.cache_misses == 0
    assert executor.stats()["post_warmup_hit_rate"] == 1.0


PHASES = ("executor.prefill.enqueue", "executor.prefill.sync",
          "executor.decode.enqueue", "executor.decode.sync")
HEAD = "executor.decode.enqueue.head"


@pytest.mark.parametrize("n,slices", [(2, 4), (1, 2)])
def test_executor_record_holds_spans_and_dispatch_counters(executor, n,
                                                           slices):
    task = _FakeTask(n, slices)
    rec = executor.submit(task).result()
    passes = 1 if slices == 4 else 2
    names = [s[0] for s in rec.spans]
    # spans are kept in the order they end: the head inside decode.enqueue
    assert names == ["executor.queue", "executor.run"] + [
        PHASES[0], PHASES[1], HEAD, PHASES[2], PHASES[3]] * passes
    (_, q0, q1), (_, r0, r1) = rec.spans[:2]
    children = [s for s in rec.spans[2:] if s[0] in PHASES]
    assert q0 <= q1 <= r0                      # queued, then taken up
    # the children lie inside the run, in order, without overlap
    edges = [t for _, t0, t1 in children for t in (t0, t1)]
    assert edges == sorted(edges) and r0 <= edges[0] and edges[-1] <= r1
    # the head opens its pass's decode.enqueue and lies inside it
    enq = [s for s in rec.spans if s[0] == PHASES[2]]
    heads = [s for s in rec.spans if s[0] == HEAD]
    for (_, e0, e1), (_, h0, h1) in zip(enq, heads, strict=True):
        assert e0 <= h0 <= h1 <= e1
    # wall_ms is timed from continuous points (a pass's prefill dispatch,
    # its prefill sync's end, its decode sync's end); the phases tile it,
    # so their sums agree with it up to the spans' own enter and exit
    ms = lambda pre: sum(t1 - t0 for n_, t0, t1 in children
                         if n_.startswith(pre)) / 1e6
    assert ms("executor.") == pytest.approx(rec.wall_ms, abs=1.0)
    assert ms("executor.prefill.") == pytest.approx(rec.prefill_ms, abs=1.0)
    assert ms("executor.decode.") == pytest.approx(rec.decode_ms, abs=1.0)
    assert rec.wall_ms == pytest.approx(rec.prefill_ms + rec.decode_ms)
    pre0 = [t0 for n_, t0, _ in children if n_ == PHASES[0]]
    pre1 = [t1 for n_, _, t1 in children if n_ == PHASES[1]]
    dec1 = [t1 for n_, _, t1 in children if n_ == PHASES[3]]
    assert rec.prefill_ms == pytest.approx(
        sum(b - a for a, b in zip(pre0, pre1)) / 1e6)
    assert rec.decode_ms == pytest.approx(
        sum(b - a for a, b in zip(pre1, dec1)) / 1e6)
    assert rec.tid == task.tid
    assert rec.uids == tuple(j.inst.uid for j in task.jobs)
    assert rec.dispatch_ms == task.dispatch_ms == 100.0
    assert rec.waits_ms == tuple(10.0 * (i + 1) for i in range(n))
    assert rec.queued == task.queued == n + 2


def test_executor_bucketing_and_quota_snap(executor):
    assert executor.bucket_of(1) == 1
    assert executor.bucket_of(2) == 2
    assert executor.bucket_of(3) == 2                # clamps to max bucket
    assert executor.quota_of(_FakeTask(1, slices=4)) == 1.0
    assert executor.quota_of(_FakeTask(1, slices=2)) == 0.5
    assert executor.quota_of(_FakeTask(1, slices=3)) == 1.0  # nearest


def test_executor_quota_is_serialized_passes(executor):
    full = executor.measure(1, 1.0, reps=3)
    half = executor.measure(1, 0.5, reps=3)
    # the half-quota cell runs 2 serialized passes: strictly slower,
    # loosely ~2x (wall-clock noise precludes a tight bound)
    assert half.wall_ms > full.wall_ms * 1.2


# ---- emulator coupling ----------------------------------------------------

def test_sim_digest_unchanged_by_attached_executor(executor):
    """Attaching the real executor must not perturb simulated time: the
    digest with the bridge on equals the digest with it off (defaults-
    off paths replay bit-identically)."""
    import json

    from repro.cluster.emulator import ClusterSim
    from repro.core.profiles import ProfileTable
    from repro.core.scheduler import ESGScheduler
    from repro.core.workflows import Workflow
    from repro.launch.profile_kernels import build_artifact
    from repro.serving import Gateway, get_scenario

    art = build_artifact(executor, reps=1, log=lambda *_: None)
    assert art["schema"] == "repro.measured_profile.v1"
    json.dumps(art)                                  # JSON-serializable

    arch = executor.arch
    digests = []
    for ex in (None, executor):
        table = ProfileTable.from_measured(art)
        apps = {arch: Workflow.pipeline(arch, [arch])}
        sched = ESGScheduler(apps, {arch: table}, risk_sigma=0.05)
        # count_overhead=False: with it on, wall-clock planning time
        # enters simulated time and no two runs digest identically
        sim = ClusterSim(apps, {arch: table}, {arch: table.fn}, sched,
                         n_invokers=1, vcpus=8, vgpus=1,
                         noise_sigma=0.0, seed=0, count_overhead=False,
                         track_digest=True, executor=ex)
        gw = Gateway(sim)
        gw.inject(get_scenario("mmpp", app_names=[arch]), 6, seed=1,
                  slo_mult=8.0)
        tel = gw.run()
        digests.append(sim.run_digest())
        assert tel.summary()["profile_provenance"] == {arch: "measured"}
    executor.drain()
    assert digests[0] == digests[1]


# ---- honest artifacts: device, interpret mode, peaks ----------------------

def test_artifact_reads_interpret_and_reduced_off_the_run(executor):
    from repro.launch.profile_kernels import NOT_MEASURED, build_artifact
    art = build_artifact(executor, reps=1, log=lambda *_: None)
    assert art["device"] == {"platform": "cpu", "kind": "cpu", "count": 1}
    assert art["reduced"] is True                  # reduced() config
    assert art["interpret"] is True                # no tpu_custom_call
    assert set(executor.kernel_calls.values()) == {False}
    # no published peaks for a CPU: the roofline bound is not measured
    assert art["roofline"] and all(c["bound_ms"] == NOT_MEASURED
                                   for c in art["roofline"])


def test_kernels_interpret_only_on_the_cpu(monkeypatch):
    from repro.kernels import interpret_mode
    assert interpret_mode() is True
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert interpret_mode() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="'gpu'"):
        interpret_mode()


def test_peaks_are_keyed_by_device_kind():
    from repro.configs.registry import ShapeSpec, get_config
    from repro.launch.roofline import V5E, model_flops, peaks, roofline
    pk = peaks(V5E)
    assert (pk.flops, pk.hbm_bw, pk.ici_bw) == (197e12, 819e9, 200e9)
    with pytest.raises(KeyError, match="cpu"):
        peaks("cpu")
    cfg = get_config("internlm2_1_8b")
    shape = ShapeSpec("decode", seq_len=1, global_batch=8, kind="decode")
    terms = roofline(cfg, shape, model_flops(cfg, shape), 0.0, 2e9, 1,
                     device_kind=V5E)
    assert terms.collective_s == pytest.approx(2e9 / 200e9)
    with pytest.raises(KeyError):
        roofline(cfg, shape, 1.0, 0.0, 0.0, 1, device_kind="TPU v4")


# ---- chip smoke and compile cache -----------------------------------------

def _run_smoke(script, *args, env=None, cwd=None):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **(env or {})}
    return subprocess.run([sys.executable, str(script), *args], env=env,
                          cwd=cwd, capture_output=True, text=True,
                          timeout=600)


def test_chip_smoke_refuses_a_non_tpu_platform(tmp_path):
    r = _run_smoke(REPO / "chip_smoke.py",
                   env={"JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout
    assert "needs 'tpu'" in r.stderr


def test_chip_smoke_refuses_to_run_outside_the_repository(tmp_path):
    script = tmp_path / "chip_smoke.py"
    shutil.copy(REPO / "chip_smoke.py", script)
    r = _run_smoke(script, cwd=tmp_path)
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout


def test_chip_smoke_cpu_rehearsal_runs_the_reduced_config(tmp_path):
    r = _run_smoke(REPO / "chip_smoke.py", "--cpu-rehearsal",
                   env={"JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
    assert r.returncode == 0, r.stderr[-3000:]
    last = json.loads(r.stdout.strip().splitlines()[-1])
    assert last == {"ok": False, "rehearsal": "cpu",
                    "device": {"platform": "cpu", "kind": "cpu",
                               "count": 1}}
    assert "layers=2 d_model=64" in r.stdout          # reduced()
    assert "post_warmup_hit_rate=1.0" in r.stdout
    assert "top1_agreement=" in r.stdout


def test_compile_cache_leaves_an_external_dir_to_jax(monkeypatch, tmp_path):
    from repro.launch.chip import use_compile_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_a_fixed_ignored_checkout_path(
        monkeypatch):
    from repro.launch.chip import use_compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = use_compile_cache()
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert path == str(REPO / ".jax_cache")
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()
