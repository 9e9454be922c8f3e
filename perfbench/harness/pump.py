"""Wall-clock driver of the served path.

The program's ``ClusterSim`` is an event simulator: left alone it runs a
trace to quiescence in simulated time, and the ``RealExecutor`` behind it
only replays the dispatched batches on the device.  The pump makes it a
server: simulated time is the wall clock of the measured window.

* Arrivals are injected through ``Gateway.inject`` as the program would,
  and held until they fall due.
* ``sim.push_event`` is replaced on the instance so that no event is run
  before its time, and so that the completion the simulator predicts from
  its profile is dropped: a batch completes when the device has finished
  it, and that completion is pushed at the moment the executor reports it.
  Capacity, queueing, admission and the planner's choices therefore all
  follow the chip.
* Between events the pump sleeps on the executor's completion queue.

Each served batch leaves a :class:`Batch`: which requests it held, when the
device started and ended it (around ``block_until_ready``, from the
executor's own ``_run``), and the tokens its decode loop was fed.  The
requests' rows are the batch's first rows, in the order of the task's jobs.
"""
from __future__ import annotations

import dataclasses
import heapq
import math
import queue
import time
from typing import Callable, Optional

import jax

NEVER = math.inf


@dataclasses.dataclass
class Batch:
    bucket: int
    quota: float
    uids: list[int]
    t_start: float = 0.0          # s, window clock
    t_end: float = 0.0
    tokens: list = dataclasses.field(default_factory=list)   # per step (B,1)
    record: object = None          # the executor's ExecRecord


@dataclasses.dataclass
class Request:
    uid: int
    due_ms: float
    finish_ms: Optional[float] = None
    batch: Optional[Batch] = None
    row: int = -1
    refused: bool = False


class Capture:
    """Wraps a warmed ``RealExecutor``: its ``_run`` (to time each batch
    and label it in the profiler's trace) and its cached prefill/decode
    executables (to keep what the decode loop is fed).  Nothing is added
    to the device's work."""

    def __init__(self, ex, clock: Callable[[], float]):
        self.ex = ex
        self.clock = clock
        self.pending: list[Batch] = []       # submitted, not yet run (FIFO)
        self.current: Optional[Batch] = None
        self._pass = 0
        run = ex._run

        def timed_run(bucket, quota):
            b = self.pending.pop(0)
            if (b.bucket, b.quota) != (bucket, quota):
                raise RuntimeError(f"the executor ran ({bucket}, {quota}) "
                                   f"where ({b.bucket}, {b.quota}) was "
                                   f"submitted next")
            self.current, self._pass = b, 0
            b.t_start = self.clock()
            try:
                with jax.profiler.TraceAnnotation(
                        f"executor.batch b={bucket}"):
                    return run(bucket, quota)
            finally:
                b.t_end = self.clock()
                self.current = None

        ex._run = timed_run
        for key, exe in list(ex._exe.items()):
            stage = key[1]
            ex._exe[key] = (self._wrap_prefill(exe) if stage == "prefill"
                            else self._wrap_decode(exe))

    def _wrap_prefill(self, exe):
        def prefill(params, tokens):
            self._pass += 1
            return exe(params, tokens)
        return prefill

    def _wrap_decode(self, exe):
        def decode(params, cache, nxt):
            b = self.current
            if b is not None and self._pass == 1:
                b.tokens.append(nxt)
            return exe(params, cache, nxt)
        return decode


class SimExecutor:
    """What the ``ClusterSim`` holds as its executor: submits to the real
    one and reports each finished batch on a queue."""

    def __init__(self, ex, capture: Capture, done: queue.Queue):
        self.ex, self.capture, self.done = ex, capture, done
        self.batches: list[Batch] = []

    def submit(self, task):
        bucket = self.ex.bucket_of(max(len(task.jobs), 1))
        b = Batch(bucket=bucket, quota=self.ex.quota_of(task),
                  uids=[j.inst.uid for j in task.jobs])
        self.batches.append(b)
        self.capture.pending.append(b)
        fut = self.ex.submit(task)

        def finished(f, task=task, b=b):
            b.record = f.result() if f.exception() is None else None
            self.done.put((task, b, f.exception()))

        fut.add_done_callback(finished)
        return fut


class Pump:
    """Run ``sim`` in lockstep with the wall clock.  ``t0`` is the window's
    start on ``time.perf_counter``."""

    def __init__(self, sim, done: queue.Queue):
        self.sim = sim
        self.done = done
        self.held: list[tuple] = []
        self.horizon = -NEVER
        self.t0 = 0.0
        self.advances = 0
        self.errors: list[BaseException] = []
        self.on_batch: Optional[Callable] = None   # (batch, t_ms) hook

        def push_event(t, kind, payload):
            if kind == "complete":
                return            # the device reports completions itself
            ev = (t, next(sim._seq), kind, payload)
            heapq.heappush(sim._events if t <= self.horizon else self.held,
                           ev)

        sim.push_event = push_event

    def now_ms(self) -> float:
        return (time.perf_counter() - self.t0) * 1e3

    def advance(self, t_ms: float) -> None:
        """Run every event due by ``t_ms``."""
        self.horizon = t_ms
        while self.held and self.held[0][0] <= t_ms:
            heapq.heappush(self.sim._events, heapq.heappop(self.held))
        with jax.profiler.TraceAnnotation("pump.advance"):
            self.sim.run()
        self.advances += 1

    def _take(self, item) -> None:
        task, batch, exc = item
        if exc is not None:
            self.errors.append(exc)
        # the batch is done when the device finished it, as the executor's
        # own clock saw it
        t_ms = batch.t_end * 1e3
        heapq.heappush(self.held, (t_ms, next(self.sim._seq), "complete",
                                   (task, task.gen)))
        if self.on_batch is not None:
            self.on_batch(batch, t_ms)

    def run_until(self, stop: Callable[[], bool], deadline_ms: float) -> None:
        """Pump until ``stop()`` holds or the wall clock passes
        ``deadline_ms``."""
        while True:
            while True:
                try:
                    self._take(self.done.get_nowait())
                except queue.Empty:
                    break
            t = self.now_ms()
            if self.held and self.held[0][0] <= t:
                self.advance(t)
                continue
            if stop() or t >= deadline_ms:
                return
            nxt = min(self.held[0][0] if self.held else NEVER, deadline_ms)
            wait = max(nxt - t, 0.0) / 1e3
            with jax.profiler.TraceAnnotation("pump.wait"):
                try:
                    item = self.done.get(timeout=wait)
                except queue.Empty:
                    continue
            self._take(item)
