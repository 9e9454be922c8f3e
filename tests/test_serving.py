"""Serving-layer integration: zoo profiles, ESG over LM pipelines, and the
real-compute single-host serve loop."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cluster.tpu_profiles import ServingSpec, TPUFunctionProfile, zoo_tables
from repro.configs.registry import get_config
from repro.core.profiles import Config


def test_tpu_profile_monotonicity():
    fp = TPUFunctionProfile(get_config("internlm2_20b"), overhead=1.5)
    t1 = fp.exec_ms(Config(1, 1, 1))
    t_more_chips = fp.exec_ms(Config(1, 1, 8))
    t_more_batch = fp.exec_ms(Config(8, 1, 1))
    assert t_more_chips < t1          # chips speed a single inference up
    assert t_more_batch > t1          # batches take longer in total
    # ... but less per job:
    assert t_more_batch / 8 < t1


def test_zoo_tables_all_archs():
    tables = zoo_tables()
    assert len(tables) == 10
    for name, t in tables.items():
        assert t.min_time > 0
        assert np.all(np.diff(t.times) >= 0)       # sorted by latency


def test_emulated_zoo_serving_esg_hits():
    from repro.launch.serve import emulate
    s = emulate(setting="relaxed-heavy", n=60, log=lambda *_: None)
    assert s["completed"] == 60
    assert s["slo_hit_rate"] > 0.5


def test_real_serving_loop_smoke():
    from repro.configs.registry import reduced
    from repro.launch.serve import serve_real
    from repro.serving.executor import RealExecutor
    ex = RealExecutor(reduced(get_config("internlm2_1_8b")),
                      batch_lattice=(1, 2), quotas=(1.0,), gen_len=2,
                      prompt_len=16)
    out = serve_real(ex, n_requests=6, reps=1, log=lambda *_: None)
    ex.shutdown()
    assert out["n_requests"] == 6
    assert out["reduced"] and out["interpret"]
    assert out["executor"]["executed"] > 0
    # the CI-asserted invariant: zero recompiles after warmup
    assert out["executor"]["post_warmup_hit_rate"] == 1.0
    assert out["telemetry"]["profile_provenance"] == {
        "internlm2_1_8b": "measured"}
