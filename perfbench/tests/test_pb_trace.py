"""The reduction from a profiler trace to device metrics, on a small trace
recorded on a TPU v5 lite: one internlm2_1_8b batch of one request (its
prefill and first three decode steps) and the idle time before it, with
the benchmark's host spans."""
import json
import pathlib

import pytest

from perfbench.harness import cell, devtrace, work

FIXTURE = pathlib.Path(__file__).parent / "fixtures" / "trace_1_8b_chat.json"
PEAK = work.peaks("TPU v5 lite")


@pytest.fixture(scope="module")
def events():
    with open(FIXTURE) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def reduced(events):
    return devtrace.reduce(events)


def _run(reduced):
    return cell.Run(cell={"name": "t"}, config={}, mix={}, seconds=0,
                    requests=[], batches=[], plan_s=[], peak=PEAK,
                    trace=reduced)


def test_window_is_the_benchmarks_annotation(events, reduced):
    (s, d), = [(s, d) for n, s, d in events["host"] if n == "bench.window"]
    assert reduced["window_s"] == pytest.approx(d / 1e9)


def test_busy_is_the_union_of_module_intervals(events, reduced):
    w0, w1 = devtrace.window(events)
    ticks = set()
    for _, s, d in events["devices"][0]["modules"]:
        lo, hi = max(s, w0), min(s + d, w1)
        ticks.update(range(int(lo / 1000), int(hi / 1000)))   # 1 us grid
    assert reduced["busy_s"] == pytest.approx(len(ticks) * 1e-6, rel=1e-3)
    idle = sum(v for _, v in reduced["idle_gaps"])
    assert idle == pytest.approx(reduced["window_s"] - reduced["busy_s"],
                                 rel=1e-6)
    # the recorded slice starts with the pump waiting for the next request
    assert reduced["idle_gaps"][0][0] == "pump.wait"


def test_programs_are_numbered_from_their_prefill(reduced):
    progs = reduced["programs"][0]
    assert [p["kind"] for p in progs] == ["prefill"] + ["decode"] * 3
    assert progs[0]["shape"]["s"] == 512 and progs[0]["shape"]["b"] == 1
    assert [p["step"] for p in progs[1:]] == [0, 1, 2]
    assert all(p["prompt"] == 512 for p in progs[1:])
    # one attention kernel per layer in every program
    assert all(len(p["kernels"]) == 24 for p in progs)


def test_readers_on_the_recorded_trace(reduced):
    run = _run(reduced)
    progs = reduced["programs"][0]
    dec = [p["dur_s"] for p in progs if p["kind"] == "decode"]
    assert cell.reader("decode_step_ms.lat")(run) == pytest.approx(
        sum(dec) / 3 * 1e3)
    pre = progs[0]
    assert cell.reader("prefill_ms_per_ktok.lat")(run) == pytest.approx(
        pre["dur_s"] * 1e3 / 0.512)
    need = took = 0.0
    for p in progs[1:]:
        for k, dur in p["kernels"]:
            f, b = work.flash_decode(1, 16, 8, 512 + p["step"], 128)
            need += max(f / PEAK["bf16_flops_per_s"],
                        b / PEAK["hbm_bytes_per_s"])
            took += dur
    fd = cell.reader("flash_decode_roofline.lat")(run)
    assert fd == pytest.approx(need / took * 100)
    fa = cell.reader("flash_attention_roofline.lat")(run)
    assert 0 < fa < 100 and 0 < fd < 100
    idle = cell.reader("device_idle.lat")(run)
    assert idle == pytest.approx(1 - reduced["busy_s"] / reduced["window_s"])


def test_readers_stay_silent_without_a_trace():
    run = cell.Run(cell={"name": "t"}, config={}, mix={}, seconds=0,
                   requests=[], batches=[], plan_s=[], peak=PEAK)
    for name in ("decode_step_ms", "prefill_ms_per_ktok", "device_idle",
                 "flash_decode_roofline", "flash_attention_roofline"):
        assert cell.reader(name)(run) is None


def test_leaf_ops_leave_out_containers():
    ops = [("while.1", 0, 100), ("fusion.1", 10, 20), ("fusion.2", 40, 30),
           ("copy.1", 120, 5)]
    assert [o[0] for o in devtrace.leaf_ops(ops)] == [
        "fusion.1", "fusion.2", "copy.1"]


def test_kernel_shapes_from_hlo_text():
    fa = ("%flash_attention.3 = bf16[2,16,512,128]{3,2,1,0} custom-call("
          "bf16[2,16,512,128]{3,2,1,0} %a, bf16[2,8,512,128]{3,2,1,0} %b, "
          "bf16[2,8,512,128]{3,2,1,0} %c), custom_call_target=\"x\"")
    assert devtrace.parse(fa) == {"kernel": "flash_attention", "b": 2,
                                  "h": 16, "s": 512, "d": 128, "kv": 8,
                                  "itemsize": 2}
    fd = ("%flash_decode_at.5 = bf16[2,16,128]{2,1,0} custom-call("
          "s32[1]{0} %t, bf16[2,16,128]{2,1,0} %q, bf16[2,576,8,128]{3,2,1,0}"
          " %k, bf16[2,576,8,128]{3,2,1,0} %v)")
    assert devtrace.parse(fd) == {"kernel": "flash_decode_at", "b": 2,
                                  "h": 16, "d": 128, "l": 576, "kv": 8,
                                  "itemsize": 2}
