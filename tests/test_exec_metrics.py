"""The executor's spans and the dispatch counters, as the chip benchmark's
per-layer readers (``perfbench/metrics/``) read them off each served
batch's ``ExecRecord``: on hand-built runs, and on one small served window
on the CPU."""
import pathlib
import sys
import types

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from perfbench.harness import cell as pb_cell, traffic  # noqa: E402
from perfbench.harness.pump import Batch  # noqa: E402
from repro.serving.executor import ExecRecord  # noqa: E402

CHAT = "internlm2_1_8b.chat_burst"
BATCH = "internlm2_1_8b.batch_c64"
NEW = ("exec_queue_ms.lat", "dispatch_wait_ms.lat",
       "queued_at_dispatch.cost", "decode_host_ms.lat",
       "decode_host_ms.tput")
SEED = 2**31 + 4242
SMALL = dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, d_head=16,
             d_ff=128, vocab=512)
MIXES = {CHAT: dict(prompt_len=16, gen_len=32, rate_rps=4.0,
                    slo_ms=60_000.0),
         BATCH: dict(prompt_len=16, gen_len=32, batch_lattice=[1, 2, 4, 8],
                     clients=8)}
MS = 1_000_000      # ns


def _record(queue_ms, head_ms, waits, queued):
    spans = [("executor.queue", 0, int(queue_ms * MS)),
             ("executor.run", 10 * MS, 90 * MS)]
    t = 10 * MS
    for h in head_ms:        # one pass per decode.enqueue.head span
        spans += [("executor.prefill.enqueue", t, t + MS),
                  ("executor.prefill.sync", t + MS, t + 2 * MS),
                  ("executor.decode.enqueue.head", t + 2 * MS,
                   t + 2 * MS + int(h * MS)),
                  # the whole loop: read by no metric (it waits on the
                  # device once the runtime's bound on programs is reached)
                  ("executor.decode.enqueue", t + 2 * MS, t + 20 * MS),
                  ("executor.decode.sync", t + 20 * MS, t + 21 * MS)]
        t += 21 * MS
    return ExecRecord(tid=0, func="f", stage="s", n_jobs=len(waits),
                      bucket=len(waits), quota=1.0, wall_ms=1.0,
                      prefill_ms=0.5, decode_ms=0.5, cache_hit=True,
                      uids=tuple(range(len(waits))), spans=spans,
                      dispatch_ms=100.0, waits_ms=tuple(waits),
                      queued=queued)


def _run(batches, gen_len=16):
    return pb_cell.Run(cell={"name": CHAT}, config={},
                       mix={"gen_len": gen_len}, seconds=1.0, requests=[],
                       batches=batches, plan_s=[])


def _batch(record):
    return Batch(bucket=1, quota=1.0, uids=[0], record=record)


SERVED = [_batch(_record(2.0, [1.0, 1.0], [10.0, 30.0], 5)),
          _batch(_record(4.0, [2.0], [20.0], 1)),
          _batch(None)]                 # failed: read by no metric


# the head times DECODE_HEAD_STEPS (8) steps a pass, or gen_len if fewer
@pytest.mark.parametrize("name,gen_len,want", [
    ("exec_queue_ms.lat", 16, (2.0 + 4.0) / 2),
    ("dispatch_wait_ms.lat", 16, (10.0 + 30.0 + 20.0) / 3),
    ("queued_at_dispatch.cost", 16, (5 + 1) / 2),
    ("decode_host_ms.lat", 16, (1.0 + 1.0 + 2.0) / (3 * 8)),
    ("decode_host_ms.tput", 16, (1.0 + 1.0 + 2.0) / (3 * 8)),
    ("decode_host_ms.lat", 4, (1.0 + 1.0 + 2.0) / (3 * 4)),
])
def test_reader_reads_the_records(name, gen_len, want):
    read = pb_cell.reader(name)
    assert read(_run(SERVED, gen_len)) == pytest.approx(want)
    # nothing served, or records that carry no spans or counters (a
    # program without them): nothing to read, and no error
    assert read(_run([])) is None
    assert read(_run([_batch(None)])) is None
    bare = types.SimpleNamespace(wall_ms=1.0, n_jobs=1)
    assert read(_run([_batch(bare)])) is None


@pytest.mark.parametrize("cell_name", [CHAT, BATCH])
def test_cpu_window_reports_every_new_metric(cell_name):
    spec = pb_cell.load_spec()
    cell = next(w for w in spec["workloads"] if w["name"] == cell_name)
    config = {**pb_cell.load_config(spec, cell["config"]), **SMALL}
    mix = {**traffic.load_mix(cell["traffic"], cell["config"]),
           **MIXES[cell_name]}
    served = pb_cell.Served(cell, config, mix, SEED, on_cpu=True)
    try:
        run = served.window(SEED, 2.0)
    finally:
        served.ex.shutdown()
    got = pb_cell.per_layer(spec, run)
    want = {m["name"] for m in spec["per_layer"]
            if m["name"] in NEW and cell_name in m["workloads"]}
    assert want and want <= set(got), got
    batches = [b for b in run.batches if b.record is not None]
    assert batches
    for b in batches:
        rec = b.record
        assert list(rec.uids) == b.uids
        assert rec.queued >= len(b.uids) and len(rec.waits_ms) == len(b.uids)
        assert min(rec.waits_ms) >= 0.0
    values = {name: got[name]["value"] for name in want}
    assert min(values.values()) >= 0.0
    assert all(v > 0.0 for n, v in values.items() if n.startswith("decode"))
    if cell_name == CHAT:
        # the waits nest: the control plane's wait, then the host's delay
        # in acting on it, then the executor's queue make up the request's
        # whole wait from due to its batch's start, none of them negative
        for r in run.requests:
            if r.batch is None or r.batch.record is None:
                continue
            rec = r.batch.record
            (_, q0, q1), = [s for s in rec.spans if s[0] == "executor.queue"]
            inner = rec.waits_ms[r.row] + (q1 - q0) / MS
            assert inner <= r.batch.t_start * 1e3 - r.due_ms + 1e-3
