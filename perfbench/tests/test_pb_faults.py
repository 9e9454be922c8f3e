"""A whole run of the harness on the CPU at a small size, past its look for
a chip: sound, it comes out correct; with the timed path broken underneath
in each way a served cell can break, ``correct`` comes out false."""
import time

import jax
import jax.numpy as jnp
import pytest

from perfbench.harness.cell import run_cell

SMALL = dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, d_head=16,
             d_ff=128, vocab=512)
MIX = dict(prompt_len=16, gen_len=32, batch_lattice=[1, 2, 4, 8], clients=8)


def _decode_keys(ex):
    return [k for k in ex._exe if k[1] == "decode"]


def state_unchanged(ex):
    """Each decode step hands back the cache it was given."""
    for k in _decode_keys(ex):
        exe = ex._exe[k]

        def step(params, cache, nxt, exe=exe):
            kept = jax.tree.map(jnp.copy, cache)
            logits, _ = exe(params, cache, nxt)
            return logits, kept
        ex._exe[k] = step


def half_the_batch(ex):
    """A batch of n requests is served as a batch of n // 2."""
    bucket_of = ex.bucket_of
    ex.bucket_of = lambda n: bucket_of(max(n // 2, 1))


def token_altered(ex):
    """The third decode step of every batch puts another token first in
    its first row."""
    for k in _decode_keys(ex):
        exe = ex._exe[k]
        seen = {"n": 0}

        def step(params, cache, nxt, exe=exe, seen=seen):
            logits, cache = exe(params, cache, nxt)
            seen["n"] += 1
            if seen["n"] % ex.gen_len == 3:
                row = logits[0]
                alt = (jnp.argmax(row) + 1) % row.shape[0]
                logits = logits.at[0, alt].set(row.max() + 1.0)
            return logits, cache
        ex._exe[k] = step


@pytest.mark.parametrize("fault,correct", [
    (None, True), (state_unchanged, False), (half_the_batch, False),
    (token_altered, False)])
def test_run_comes_out_as_the_timed_path_is(fault, correct):
    out = run_cell("internlm2_1_8b.batch_c64", 2**31 + 4242, 2.0, False,
                   time.perf_counter(), config_override=SMALL,
                   mix_override=MIX, on_cpu=True, fault=fault,
                   log=lambda *_: None)
    assert out["correct"] is correct, out["checks"]
    assert list(out)[-1] == "checks"
    if fault is None:
        assert out["checks"]["window_compiles"]["value"] == 0
        assert out["checks"]["tokens_compared"]["value"] >= 256
    elif fault is not half_the_batch:
        # the faults that change tokens fail the comparison itself
        checks = out["checks"]
        assert checks["token_gap_sd"]["value"] > \
            checks["token_gap_sd"]["limit"]
