"""The profiler's trace: capture a fixed span of the window, and reduce it.

Capture runs on a helper thread, so the pump keeps serving while the
profiler starts and writes: it starts the trace at ``at_s`` into the
window, marks ``len_s`` seconds with the host annotation ``bench.window``
and stops.  The traced window is that annotation's span.

The reduction reads only three things of the trace: the device's XLA
module events (one per executed program: ``jit_prefill_fn(..)`` and
``jit_decode_fn(..)`` are the executor's), its XLA op events (the kernels
``flash_attention`` and ``flash_decode_at`` among them, each named with its
HLO text and so with its shapes), and the host annotations the benchmark
itself writes (``pump.wait``, ``pump.advance``, ``plan``,
``executor.batch``).
"""
from __future__ import annotations

import bisect
import glob
import os
import re
import threading
import time

import jax

KERNELS = ("flash_attention", "flash_decode_at")
PREFILL, DECODE = "jit_prefill_fn", "jit_decode_fn"
HOST_SPANS = ("pump.", "plan", "executor.", "bench.window")
SHORT_GAP_NS = 100_000      # idle gaps shorter than this are between ops


class Capture(threading.Thread):
    def __init__(self, log_dir: str, t0: float, at_s: float, len_s: float):
        super().__init__(daemon=True)
        self.log_dir, self.t0, self.at_s, self.len_s = log_dir, t0, at_s, len_s
        self.error = None

    def run(self):
        try:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            delay = self.t0 + self.at_s - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            jax.profiler.start_trace(self.log_dir, profiler_options=opts)
            try:
                with jax.profiler.TraceAnnotation("bench.window"):
                    time.sleep(self.len_s)
            finally:
                jax.profiler.stop_trace()
        except Exception as e:      # reported with the run's result
            self.error = e


def _short(name: str) -> str:
    return name.split(" ", 1)[0].lstrip("%")


def load(log_dir: str) -> dict:
    """The events the reduction reads, from the newest ``.xplane.pb`` under
    ``log_dir``: per device, its modules and ops as (name, start_ns,
    dur_ns); kernel ops keep their HLO text; and the benchmark's host
    spans."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no trace under {log_dir}")
    pd = ProfileData.from_file(paths[-1])
    out = {"devices": [], "host": []}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = {"name": plane.name, "modules": [], "ops": []}
            for line in plane.lines:
                if line.name == "XLA Modules":
                    dev["modules"] = [(e.name, e.start_ns, e.duration_ns)
                                      for e in line.events]
                elif line.name == "XLA Ops":
                    for e in line.events:
                        short = _short(e.name)
                        keep = e.name if short.startswith(KERNELS) else short
                        dev["ops"].append((keep, e.start_ns, e.duration_ns))
            out["devices"].append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"].extend(
                    (e.name, e.start_ns, e.duration_ns) for e in line.events
                    if e.name.startswith(HOST_SPANS))
    return out


# ---------------------------------------------------------------------------
# reduction
# ---------------------------------------------------------------------------
def window(events: dict) -> tuple[float, float]:
    marks = [(s, s + d) for n, s, d in events["host"] if n == "bench.window"]
    if marks:
        return marks[0]
    ev = [(s, s + d) for dev in events["devices"]
          for _, s, d in dev["modules"]]
    return min(a for a, _ in ev), max(b for _, b in ev)


def _clip(iv, w0, w1):
    return [(max(a, w0), min(b, w1)) for a, b in iv if b > w0 and a < w1]


def _union(iv):
    out = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def busy(dev: dict, w0: float, w1: float) -> list:
    """Merged intervals in which the device ran a program, in the window."""
    iv = [(s, s + d) for _, s, d in (dev["modules"] or dev["ops"])]
    return _union(_clip(iv, w0, w1))


def leaf_ops(ops: list) -> list:
    """Ops that contain no other op (a ``while`` holds its body's ops)."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    container = set()
    stack: list[int] = []
    for i in order:
        s, e = ops[i][1], ops[i][1] + ops[i][2]
        while stack and ops[stack[-1]][1] + ops[stack[-1]][2] <= s:
            stack.pop()
        if stack and e <= ops[stack[-1]][1] + ops[stack[-1]][2]:
            container.add(stack[-1])
        stack.append(i)
    return [ops[i] for i in range(len(ops)) if i not in container]


# what an idle gap is put down to, when several host spans hold it: a
# batch the executor is inside of, then the planner, then the pump
GAP_ORDER = ("executor.batch", "plan", "pump.advance", "pump.wait")


def _label_gaps(gaps, host) -> dict:
    spans = sorted((s, s + d, n.split(" ", 1)[0]) for n, s, d in host
                   if n != "bench.window")
    starts = [s for s, _, _ in spans]
    longest = max((e - s for s, e, _ in spans), default=0)
    out: dict[str, float] = {}
    for a, b in gaps:
        label = "between_ops" if b - a < SHORT_GAP_NS else "other"
        if label == "other":
            mid = (a + b) / 2
            lo = bisect.bisect_left(starts, mid - longest)
            held = {n for s, e, n in spans[lo:bisect.bisect_right(starts, mid)]
                    if e >= mid}
            label = next((n for n in GAP_ORDER if n in held), "other")
        out[label] = out.get(label, 0.0) + (b - a) / 1e9
    return out


def reduce(events: dict) -> dict:
    """Device busy and idle, the top device ops and idle gaps, and the
    program structure the per-layer readers need: per device, the
    prefill/decode modules in the window with their kernel ops."""
    w0, w1 = window(events)
    span = (w1 - w0) / 1e9
    busy_s, ops_time, gaps_by = [], {}, {}
    structure = []
    for dev in events["devices"]:
        iv = busy(dev, w0, w1)
        busy_s.append(sum(b - a for a, b in iv) / 1e9)
        inside = [o for o in dev["ops"] if w0 <= o[1] and o[1] + o[2] <= w1]
        for name, _, d in leaf_ops(inside):
            key = _short(name)
            ops_time[key] = ops_time.get(key, 0.0) + d / 1e9
        gaps = [(a[1], b[0]) for a, b in zip(iv, iv[1:])]
        if iv:
            gaps = [(w0, iv[0][0])] + gaps + [(iv[-1][1], w1)]
        for k, v in _label_gaps([g for g in gaps if g[1] > g[0]],
                                events["host"]).items():
            gaps_by[k] = gaps_by.get(k, 0.0) + v / len(events["devices"])
        structure.append(programs(dev, w0, w1))
    n = max(len(busy_s), 1)
    top = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:10]
    return {"window_s": span, "busy_s": sum(busy_s) / n,
            "device_ops": [list(x) for x in top(ops_time)],
            "idle_gaps": [list(x) for x in top(gaps_by)],
            "programs": structure}


def programs(dev: dict, w0: float, w1: float) -> list[dict]:
    """The executor's prefill and decode runs in the window, in order, each
    with its kernel ops and (decode) its step since the batch's prefill
    and that prefill's prompt length, where the trace holds them."""
    by_start = lambda e: e[1]
    mods = sorted((m for m in dev["modules"]
                   if m[0].startswith((PREFILL, DECODE))), key=by_start)
    kern = sorted((o for o in dev["ops"]
                   if _short(o[0]).startswith(KERNELS)), key=by_start)
    kstarts = [o[1] for o in kern]
    out, step, prompt = [], None, None
    for name, s, d in mods:
        ks = kern[bisect.bisect_left(kstarts, s):
                  bisect.bisect_right(kstarts, s + d)]
        if name.startswith(PREFILL):
            step = 0
            fa = [parse(o[0]) for o in ks
                  if _short(o[0]).startswith("flash_attention")]
            prompt = fa[0]["s"] if fa else None
            rec = {"kind": "prefill", "shape": fa[0] if fa else None}
        else:
            rec = {"kind": "decode", "step": step, "prompt": prompt}
            step = None if step is None else step + 1
        if w0 <= s and s + d <= w1:
            rec.update(dur_s=d / 1e9, kernels=[(parse(o[0]), o[2] / 1e9)
                                               for o in ks])
            out.append(rec)
    return out


_SHAPE = re.compile(r"([a-z]+\d*)\[([\d,]*)\]")
ITEMSIZE = {"bf16": 2, "f16": 2, "f32": 4, "s8": 1, "f8e4m3fn": 1}


def parse(text: str) -> dict:
    """A kernel op's shapes, from its HLO text: ``flash_attention`` gives
    q (B, H, S, D) and k (B, KV, S, D); ``flash_decode_at`` gives q (B, H, D)
    and the cache (B, L, KV, D)."""
    name = _short(text)
    operands = text.split("custom-call(", 1)[1]
    shapes = [(t, [int(x) for x in dims.split(",") if x])
              for t, dims in _SHAPE.findall(operands)]
    if name.startswith("flash_attention"):
        (dt, q), (_, k) = shapes[0], shapes[1]
        return {"kernel": "flash_attention", "b": q[0], "h": q[1], "s": q[2],
                "d": q[3], "kv": k[1], "itemsize": ITEMSIZE[dt]}
    dt, q = next((t, s) for t, s in shapes if len(s) == 3)
    cache = next(s for t, s in shapes if len(s) == 4)
    return {"kernel": "flash_decode_at", "b": q[0], "h": q[1], "d": q[2],
            "l": cache[1], "kv": cache[2], "itemsize": ITEMSIZE[dt]}
