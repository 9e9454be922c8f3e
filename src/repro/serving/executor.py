"""Real-compute execution bridge: compile-cached batched Pallas serving.

``RealExecutor`` runs the *actual* jax/Pallas kernels (flash_attention
prefill, scalar-prefetch flash_decode, WKV6 for SSM archs — all via
``models/model.py``) behind the emulator's Gateway → autoscaler →
``ClusterSim`` dispatch path.  The emulator stays the timing/placement
model; every dispatched task is additionally *executed for real* here,
and the measured wall times validate the emulator's predictions
(``launch/serve.serve_real``).

The executor serves whatever config its caller hands it, in bf16
parameters and activations: the published widths on a chip, a
``reduced()`` config only in CPU tests and the CPU rehearsal.

Fast-path design, in order of importance:

* **Batch-lattice bucketing** — a dispatched batch of n jobs pads up to
  the nearest ``batch_lattice`` bucket, so the set of shapes the device
  ever sees is the profile lattice itself.  Each (arch, stage,
  batch-bucket, quota) cell compiles exactly once.
* **Persistent compile cache** — stage step functions are AOT-compiled
  (``jit(...).lower(...).compile()``) into ``self._exe`` keyed on that
  tuple, with hit/miss counters; after ``warmup()`` the steady-state
  hit rate is exactly 1.0 (asserted in CI).  Fractional-quota variants
  of a bucket share the bucket's executables (quota is a run-count, see
  below), so a quota change can never trigger a recompile either.
* **Donated decode buffers** — the decode step donates the KV cache
  (``donate_argnums``), and the model's layer scan carries it, writing
  each layer's one new row in place: no layer is sliced out or written
  back, and no cache-sized buffer is allocated or copied per token.
  ``decode_cache_copies`` counts, per bucket, the compiled decode
  program's instructions that would move a cache-sized array anyway
  (:func:`cache_copies`); it is 0.
* **Async dispatch** — ``submit()`` enqueues onto a single-worker
  executor and returns a future immediately; the gateway/emulator
  thread never blocks on device completion.  ``drain()`` collects the
  measured records at end of run.

Fractional compute quota q < 1 is emulated on a time-sliced sharing
model: the cell runs ``round(1/q)`` serialized passes, so the measured
latency is what a container throttled to a 1/q device share observes.
This is the measured counterpart of the profile model's
``QUOTA_SLOWDOWN_EXP`` (cross-checked by ``launch/profile_kernels.py``).

Each served batch's :class:`ExecRecord` carries its wall-clock spans
(``repro.obs.host_span``; ``(name, t0_ns, t1_ns)`` on
``time.perf_counter_ns``), parented by the task id, with the task's
request ids in ``uids``:

* ``executor.queue`` — ``submit()`` to the worker taking the batch up:
  time spent behind earlier batches in the single-worker FIFO.  It
  crosses threads, so it is kept on the record only.
* ``executor.run`` — the worker's whole batch, every pass; on the
  profiler's host plane with ``tid``, ``n_jobs`` and ``bucket``.
* Per pass, four children that tile the timed part of ``executor.run``:
  ``executor.prefill.enqueue`` (prefill and the first argmax,
  dispatched), ``executor.prefill.sync`` (waiting for them),
  ``executor.decode.enqueue`` (the host loop that dispatches the
  ``gen_len`` steps) and ``executor.decode.sync`` (waiting for the last).
  ``prefill_ms`` runs from a pass's prefill dispatch to the end of its
  prefill sync, ``decode_ms`` from there to the end of its decode sync.
* ``executor.decode.enqueue.head``, inside ``executor.decode.enqueue``:
  the dispatch of a pass's first ``DECODE_HEAD_STEPS`` steps.  The
  runtime bounds the programs in flight, so past about ten steps a
  device-bound loop waits in each dispatch and ``decode.enqueue`` reads
  the device's pace; the head starts on an idle device and stays under
  that bound, so it reads the host's own cost of dispatching a step.

The record also copies the dispatch counters of the task: its
``dispatch_ms`` on the control plane's clock, each job's wait in the
stage queue up to it (``waits_ms``), and how many jobs that queue held
when the batch was formed (``queued``; more than ``n_jobs`` means the
planner split the queue).
"""
from __future__ import annotations

import dataclasses
import re
import time
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import ModelConfig
from repro.gpu import SLICES_PER_VGPU
from repro.models.model import RunOptions, get_model
from repro.obs import host_span

DEFAULT_BATCH_LATTICE = (1, 2, 4, 8)
DEFAULT_QUOTAS = (1.0, 0.5, 0.25)
# decode steps a pass dispatches under ``executor.decode.enqueue.head``:
# fewer than the runtime keeps in flight, so none of them waits for the
# device (see the module docstring)
DECODE_HEAD_STEPS = 8


@dataclasses.dataclass
class ExecRecord:
    """One real execution of a dispatched task (or a profiling rep)."""
    tid: int                    # emulator task id (-1 for profiling runs)
    func: str
    stage: str                  # emulator stage name ("" for profiling)
    n_jobs: int                 # real jobs in the batch (before padding)
    bucket: int                 # padded batch bucket actually executed
    quota: float                # fractional compute quota emulated
    wall_ms: float              # measured end-to-end (prefill + decode)
    prefill_ms: float           # prefill component
    decode_ms: float            # decode-loop component (gen_len steps)
    cache_hit: bool             # compile cache hit at submit time
    # --- observability (empty for profiling runs) ---
    uids: tuple = ()            # request ids of the task's jobs
    # (name, t0_ns, t1_ns) on perf_counter_ns; see the module docstring
    spans: list = dataclasses.field(default_factory=list)
    dispatch_ms: float = 0.0    # control-plane time the task was dispatched
    waits_ms: tuple = ()        # per job: dispatch_ms - its ready time
    queued: int = 0             # jobs in the stage queue at dispatch


# one HLO instruction: its name, result shape and opcode
_HLO_OP = re.compile(r"^\s*(?:ROOT\s+)?%?(?P<name>[^\s=]+)\s*=\s*\w+"
                     r"\[(?P<dims>[\d,]*)\]\S*\s+(?P<op>[\w-]+)\(")


def cache_copies(hlo_text: str, cache) -> int:
    """Instructions of a compiled program that make a cache-sized array
    (last three dims those of a cache stack: S, KV, D) by a copy, a
    dynamic slice (or a fusion named for one) or a fresh buffer
    (``AllocateBuffer``): the per-layer slices, write-back stacks and
    whole-cache copies that an in-place decode step does without."""
    tails = {tuple(a.shape[-3:]) for a in jax.tree.leaves(cache)
             if len(a.shape) >= 4}
    n = 0
    for line in hlo_text.splitlines():
        m = _HLO_OP.match(line)
        if m is None:
            continue
        dims = tuple(int(x) for x in m["dims"].split(",") if x)
        if dims[-3:] not in tails:
            continue
        n += (m["op"] == "copy" or "dynamic-slice" in m["name"]
              or m["op"] == "dynamic-slice"
              or 'custom_call_target="AllocateBuffer"' in line)
    return n


def phase_ms(spans: list) -> tuple[float, float]:
    """(prefill_ms, decode_ms) of a batch from its passes' phase spans:
    each pass's prefill from its dispatch to the end of its sync, and its
    decode from there to the end of the decode sync."""
    pre = dec = 0
    for name, t0, t1 in spans:
        if name == "executor.prefill.enqueue":
            start = t0
        elif name == "executor.prefill.sync":
            pre, mid = pre + t1 - start, t1
        elif name == "executor.decode.sync":
            dec += t1 - mid
    return pre / 1e6, dec / 1e6


class RealExecutor:
    """Compile-cached batched real execution for one model config."""

    def __init__(self, cfg: ModelConfig,
                 batch_lattice: tuple = DEFAULT_BATCH_LATTICE,
                 quotas: tuple = DEFAULT_QUOTAS,
                 prompt_len: int = 512, gen_len: int = 32,
                 seed: int = 0):
        self.arch = cfg.name
        self.cfg = cfg
        self.opts = RunOptions(use_kernels=True, remat="none",
                               param_dtype=jnp.bfloat16,
                               act_dtype=jnp.bfloat16)
        self.model = get_model(self.cfg, self.opts)
        # built under jit: the bf16 weights are made on the device from
        # the seed, with no float32 copy of the whole model
        self.params = jax.jit(self.model.init)(jax.random.PRNGKey(seed))
        self.batch_lattice = tuple(sorted(batch_lattice))
        self.quotas = tuple(sorted(quotas, reverse=True))
        if 1.0 not in self.quotas:
            self.quotas = (1.0,) + self.quotas
        self.prompt_len = prompt_len
        self.gen_len = gen_len
        self.max_len = prompt_len + gen_len
        rng = np.random.default_rng(seed)
        # deterministic per-bucket token batches: padding a real batch
        # reuses the bucket's prefix so shapes — and therefore compiled
        # executables — are a pure function of the bucket
        self._tokens = {
            b: jnp.asarray(rng.integers(0, self.cfg.vocab,
                                        (b, prompt_len)), jnp.int32)
            for b in self.batch_lattice
        }
        # compile cache: (arch, stage, bucket, quota) -> executable.
        # Quota variants alias the bucket's two stage executables (quota
        # is a serialized-pass count, not a shape), so they can never
        # force a recompile; they still get their own cache entries so
        # the hit/miss accounting covers the full dispatch key.
        self._exe: dict[tuple, Any] = {}
        # (stage, bucket) -> whether the compiled program calls a
        # compiled Pallas kernel (False: interpreted on the CPU)
        self.kernel_calls: dict[tuple, bool] = {}
        # bucket -> cache-sized slices, copies and buffers the compiled
        # decode program makes (see cache_copies); 0 when it runs in place
        self.decode_cache_copies: dict[int, int] = {}
        self.compiles = 0            # actual XLA compilations performed
        self.warmup_compiles = 0     # ... of which during warmup()
        self.cache_hits = 0          # submit()-time cache hits
        self.cache_misses = 0        # submit()-time compile-cache misses
        self._warmed = False
        self._pool = ThreadPoolExecutor(max_workers=1)
        self._futures: dict[int, Future] = {}
        self.records: list[ExecRecord] = []

    # ---- compile cache ----------------------------------------------------
    def _compile_bucket(self, bucket: int) -> tuple:
        """AOT-compile the prefill and donated-cache decode executables
        for one batch bucket (the expensive path — once per bucket)."""
        toks = self._tokens[bucket]
        max_len = self.max_len

        def prefill_fn(params, tokens):
            return self.model.prefill(params, {"tokens": tokens},
                                      max_len=max_len)

        def decode_fn(params, cache, tokens):
            return self.model.decode(params, cache, tokens)

        prefill = jax.jit(prefill_fn).lower(self.params, toks).compile()
        self.compiles += 1
        _, cache = jax.eval_shape(prefill_fn, self.params, toks)
        nxt = jax.ShapeDtypeStruct((bucket, 1), jnp.int32)
        # donate the KV cache: the decode hot loop rewrites it in place
        decode = jax.jit(decode_fn, donate_argnums=(1,)).lower(
            self.params, cache, nxt).compile()
        self.compiles += 1
        for stage, exe in (("prefill", prefill), ("decode", decode)):
            self.kernel_calls[(stage, bucket)] = \
                "tpu_custom_call" in exe.as_text()
        self.decode_cache_copies[bucket] = cache_copies(decode.as_text(),
                                                        cache)
        return prefill, decode

    @property
    def interpret(self) -> bool:
        """Whether the kernels ran interpreted: no compiled program
        calls a compiled Pallas kernel."""
        return not any(self.kernel_calls.values())

    def _cell(self, stage: str, bucket: int, quota: float):
        """Cache lookup for one (arch, stage, bucket, quota) cell;
        compiles on miss.  Returns (executable, hit)."""
        key = (self.arch, stage, bucket, quota)
        exe = self._exe.get(key)
        if exe is not None:
            return exe, True
        base_p = (self.arch, "prefill", bucket, 1.0)
        base_d = (self.arch, "decode", bucket, 1.0)
        if base_p not in self._exe:
            prefill, decode = self._compile_bucket(bucket)
            self._exe[base_p] = prefill
            self._exe[base_d] = decode
        # quota aliases: same executables, distinct cache identity
        self._exe[(self.arch, "prefill", bucket, quota)] = self._exe[base_p]
        self._exe[(self.arch, "decode", bucket, quota)] = self._exe[base_d]
        return self._exe[key], False

    def warmup(self) -> dict:
        """Compile every (stage, bucket, quota) lattice cell and run one
        pass per bucket, so steady-state serving never compiles again
        (post-warmup hit rate == 1.0, the CI-asserted invariant)."""
        t0 = time.perf_counter()
        before = self.compiles
        for bucket in self.batch_lattice:
            for quota in self.quotas:
                self._cell("prefill", bucket, quota)
                self._cell("decode", bucket, quota)
            self._run(bucket, 1.0)     # execute once: warm allocators
        self.warmup_compiles = self.compiles - before
        self._warmed = True
        # warmup fills are not serving traffic: reset serving counters
        self.cache_hits = self.cache_misses = 0
        return {"warmup_compiles": self.warmup_compiles,
                "warmup_s": time.perf_counter() - t0,
                "cells": len(self._exe)}

    # ---- execution --------------------------------------------------------
    def _run(self, bucket: int, quota: float) -> list:
        """One real serve of a bucket at a quota: prefill + gen_len
        greedy decode steps, ``round(1/q)`` serialized passes.  Returns
        the passes' phase spans, in the order they end (see the module
        docstring); :func:`phase_ms` turns them into wall times."""
        prefill, _ = self._cell("prefill", bucket, quota)
        decode, _ = self._cell("decode", bucket, quota)
        passes = max(int(round(1.0 / quota)), 1)
        toks = self._tokens[bucket]
        head = min(DECODE_HEAD_STEPS, self.gen_len)
        spans: list = []
        for _ in range(passes):
            with host_span(spans, "executor.prefill.enqueue"):
                logits, cache = prefill(self.params, toks)
                nxt = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
            with host_span(spans, "executor.prefill.sync"):
                jax.block_until_ready(nxt)
            with host_span(spans, "executor.decode.enqueue"):
                with host_span(spans, "executor.decode.enqueue.head"):
                    for _ in range(head):
                        logits, cache = decode(self.params, cache, nxt)
                        nxt = jnp.argmax(logits, -1)[:, None].astype(
                            jnp.int32)
                for _ in range(self.gen_len - head):
                    logits, cache = decode(self.params, cache, nxt)
                    nxt = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
            with host_span(spans, "executor.decode.sync"):
                jax.block_until_ready(nxt)
        return spans

    def trace(self, bucket: int) -> tuple[np.ndarray, np.ndarray]:
        """Serve one bucket at full quota through the cached executables
        and keep what a greedy client sees: the tokens fed (prompt, then
        the generated tokens) and the float32 logits of every step,
        prefill first.  Returns (tokens (B, prompt_len + gen_len),
        logits (B, gen_len + 1, V))."""
        prefill, _ = self._cell("prefill", bucket, 1.0)
        decode, _ = self._cell("decode", bucket, 1.0)
        toks = self._tokens[bucket]
        logits, cache = prefill(self.params, toks)
        fed, steps = [toks], [logits]
        for _ in range(self.gen_len):
            nxt = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
            logits, cache = decode(self.params, cache, nxt)
            fed.append(nxt)
            steps.append(logits)
        return (np.asarray(jnp.concatenate(fed, axis=1)),
                np.asarray(jnp.stack(steps, axis=1), np.float32))

    def bucket_of(self, n: int) -> int:
        for b in self.batch_lattice:
            if n <= b:
                return b
        return self.batch_lattice[-1]

    def quota_of(self, task) -> float:
        """Snap a task's delivered slice quota to the measured lattice."""
        cfg = task.config
        q = task.quota_slices / max(cfg.vgpu * SLICES_PER_VGPU, 1)
        return min(self.quotas, key=lambda x: abs(x - q))

    # ---- emulator hook ----------------------------------------------------
    def submit(self, task) -> Future:
        """ClusterSim._dispatch hook: execute the dispatched task for
        real, asynchronously.  Never blocks the emulator thread."""
        t_submit = time.perf_counter_ns()
        n_jobs = len(task.jobs)
        bucket = self.bucket_of(max(n_jobs, 1))
        quota = self.quota_of(task)
        # cache accounting happens on the caller thread so the hit/miss
        # ordering matches dispatch order deterministically
        _, hit_p = self._cell("prefill", bucket, quota)
        _, hit_d = self._cell("decode", bucket, quota)
        hit = hit_p and hit_d
        if hit:
            self.cache_hits += 1
        else:
            self.cache_misses += 1
        tid, func, stage = task.tid, task.func, task.stage
        # copied now: the emulator may reuse the task and its jobs
        dispatch_ms, queued = task.dispatch_ms, task.queued
        uids = tuple(j.inst.uid for j in task.jobs)
        waits = tuple(dispatch_ms - j.ready_ms for j in task.jobs)

        def work() -> ExecRecord:
            spans = [("executor.queue", t_submit, time.perf_counter_ns())]
            with host_span(spans, "executor.run", tid=tid, n_jobs=n_jobs,
                           bucket=bucket):
                phases = self._run(bucket, quota)
            spans += phases
            pre, dec = phase_ms(phases)
            rec = ExecRecord(tid=tid, func=func, stage=stage,
                             n_jobs=n_jobs, bucket=bucket, quota=quota,
                             wall_ms=pre + dec, prefill_ms=pre,
                             decode_ms=dec, cache_hit=hit, uids=uids,
                             spans=spans, dispatch_ms=dispatch_ms,
                             waits_ms=waits, queued=queued)
            self.records.append(rec)
            return rec

        fut = self._pool.submit(work)
        self._futures[tid] = fut
        return fut

    def measure(self, bucket: int, quota: float, reps: int = 3,
                ) -> ExecRecord:
        """Synchronous timed run for profiling: floor of ``reps``.

        Wall-clock noise on a shared host is one-sided (runs only ever
        get slower), so the minimum is the reproducible statistic — a
        median of few reps swings ~10% run to run at ms-scale cells."""
        runs = [phase_ms(self._run(bucket, quota)) for _ in range(reps)]
        pre = float(np.min([r[0] for r in runs]))
        dec = float(np.min([r[1] for r in runs]))
        return ExecRecord(tid=-1, func=self.arch, stage="", n_jobs=bucket,
                          bucket=bucket, quota=quota, wall_ms=pre + dec,
                          prefill_ms=pre, decode_ms=dec, cache_hit=True)

    # ---- teardown / stats -------------------------------------------------
    def drain(self, timeout: Optional[float] = None) -> list[ExecRecord]:
        """Wait for all in-flight work; returns the full record list."""
        for fut in list(self._futures.values()):
            fut.result(timeout=timeout)
        return list(self.records)

    def shutdown(self) -> None:
        self._pool.shutdown(wait=True)

    def stats(self) -> dict:
        served = self.cache_hits + self.cache_misses
        return {
            "arch": self.arch,
            "batch_lattice": list(self.batch_lattice),
            "quotas": list(self.quotas),
            "prompt_len": self.prompt_len,
            "gen_len": self.gen_len,
            "interpret": self.interpret,
            "decode_cache_copies": dict(self.decode_cache_copies),
            "compiles": self.compiles,
            "warmup_compiles": self.warmup_compiles,
            "cache_hits": self.cache_hits,
            "cache_misses": self.cache_misses,
            "post_warmup_hit_rate": (self.cache_hits / served) if served
            else None,
            "executed": len(self.records),
        }
