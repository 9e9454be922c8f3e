"""The traffic generator: fixed work, drawn from the mix alone."""
import numpy as np

from perfbench.harness import traffic


def test_open_arrivals_hold_the_offered_rate_in_the_window():
    mix = traffic.load_mix("chat_burst", "internlm2_1_8b")
    due = traffic.open_arrivals(mix, 51.0)
    assert len(due) == round(mix["rate_rps"] * 51.0)
    assert due[0] == 0.0 and due[-1] < 51e3
    assert np.all(np.diff(due) > 0)
    gaps = np.diff(due)
    assert gaps.std() / gaps.mean() > 1.1       # bursty: Poisson gives 1


def test_open_arrivals_are_the_same_every_run():
    mix = traffic.load_mix("chat_burst", "internlm2_20b_l12")
    assert np.array_equal(traffic.open_arrivals(mix, 51.0),
                          traffic.open_arrivals(mix, 51.0))


def test_prompts_come_from_the_seed():
    big = 2**31 + 12345
    a = traffic.prompts(92544, [1, 2], 512, big)
    b = traffic.prompts(92544, [1, 2], 512, big)
    c = traffic.prompts(92544, [1, 2], 512, big + 1)
    assert a[2].shape == (2, 512) and a[2].max() < 92544
    assert np.array_equal(a[2], b[2]) and not np.array_equal(a[2], c[2])


def test_mix_files_carry_fixed_rates_and_slos():
    for traffic_name, config in [("chat_burst", "internlm2_1_8b"),
                                 ("chat_burst", "internlm2_20b_l12"),
                                 ("batch_c64", "internlm2_1_8b")]:
        mix = traffic.load_mix(traffic_name, config)
        assert isinstance(mix["slo_ms"], float)
        assert mix["kind"] in ("open_mmpp", "closed")
