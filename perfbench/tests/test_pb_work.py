"""Shape-derived work counts, the peaks table and the end-to-end arithmetic
of the chip benchmark, on hand-made inputs."""
import itertools
import json

import numpy as np
import pytest

from perfbench.harness import cell, work
from perfbench.harness.pump import Batch, Request

PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_flash_attention_counts_the_causal_half_square():
    b, h, kv, s, d = 2, 4, 2, 7, 8
    pairs = sum(1 for i, j in itertools.product(range(s), repeat=2) if j <= i)
    flops, nbytes = work.flash_attention(b, h, kv, s, d)
    assert flops == 4 * b * h * d * pairs           # QK^T and PV, 2 flops/MAC
    assert nbytes == 2 * (2 * b * h * s * d + 2 * b * kv * s * d)


def test_flash_decode_reads_the_cache_up_to_its_position():
    flops, nbytes = work.flash_decode(b=2, h=16, kv=8, t=511, d=128)
    assert flops == 4 * 2 * 16 * 128 * 512
    assert nbytes == 2 * (2 * 2 * 512 * 8 * 128 + 2 * 2 * 16 * 128)


def test_roofline_is_the_larger_bound():
    assert work.roofline_s(197e12, 1.0, PEAK) == pytest.approx(1.0)
    assert work.roofline_s(1.0, 819e9, PEAK) == pytest.approx(1.0)


def test_request_flops_match_a_token_by_token_count():
    c = {"n_layers": 2, "d_model": 8, "n_heads": 2, "n_kv_heads": 1,
         "d_ff": 16, "vocab": 32}
    p, g = 5, 3
    dh = 4
    per_tok = 2 * 2 * (8 * 2 * dh * 2 + 8 * 1 * dh * 2 + 3 * 8 * 16)
    total = 0.0
    for pos in range(p + g - 1):               # prefill, then g-1 decode steps
        total += per_tok + 4 * 2 * 2 * dh * (pos + 1)
    total += g * 2 * 32 * 8                     # the head, once per token
    assert work.request_flops(c, p, g) == pytest.approx(total)


def test_peaks_are_keyed_by_device_kind():
    assert work.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(SystemExit):
        work.peaks("TPU v9 imaginary")


class _Rec:
    def __init__(self, wall_ms):
        self.wall_ms = wall_ms


def _run(mix, seconds=10.0, window_end_s=10.0):
    b1 = Batch(bucket=2, quota=1.0, uids=[0, 1], t_start=0.1, t_end=0.5,
               record=_Rec(400.0))
    b2 = Batch(bucket=1, quota=0.5, uids=[2], t_start=0.6, t_end=1.4,
               record=_Rec(800.0))
    reqs = [Request(0, 0.0, 500.0, b1, 0), Request(1, 50.0, 500.0, b1, 1),
            Request(2, 100.0, 1400.0, b2, 0),
            Request(3, 9000.0, refused=True)]
    return cell.Run(cell={"name": "c"}, config={}, mix=mix, seconds=seconds,
                    requests=reqs, batches=[b1, b2], plan_s=[],
                    window_end_s=window_end_s)


def test_end_to_end_arithmetic_on_a_hand_made_record_list():
    spec = {"end_to_end": [
        {"name": "e2e_p90_ms", "unit": "ms"},
        {"name": "slo_attainment", "unit": "frac"},
        {"name": "chip_ms_per_req", "unit": "ms"},
        {"name": "setup_s", "unit": "s"}]}
    out = cell.end_to_end(spec, _run({"slo_ms": 600.0, "gen_len": 4}), 3.5)
    # billed chip time: 400 ms x 1.0 + 800 ms x 0.5, over 3 answered
    assert out["chip_ms_per_req"]["value"] == pytest.approx(800.0 / 3)
    # latencies 500, 450, 1300 and the refused one at the whole window
    assert out["e2e_p90_ms"]["value"] == pytest.approx(
        np.percentile([500, 450, 1300, 10000], 90))
    assert out["slo_attainment"]["value"] == pytest.approx(2 / 4)
    assert out["setup_s"]["value"] == 3.5


def test_tokens_per_s_counts_answers_inside_the_window():
    spec = {"end_to_end": [{"name": "tokens_per_s", "unit": "tokens/s"}]}
    out = cell.end_to_end(spec, _run({"slo_ms": 1.0, "gen_len": 4},
                                     window_end_s=1.0), 0.0)
    assert out["tokens_per_s"]["value"] == pytest.approx(2 * 4 / 1.0)


def test_every_metric_has_a_reader_and_every_cell_its_files():
    spec = cell.load_spec()
    for m in spec["per_layer"]:
        assert callable(cell.reader(m["name"]))
    for w in spec["workloads"]:
        c = cell.load_config(spec, w["config"])
        assert c["check"]["token_gap_sd"] > 0
        from perfbench.harness import traffic
        mix = traffic.load_mix(w["traffic"], w["config"])
        assert mix["slo_ms"] > 0
    json.dumps(spec)
