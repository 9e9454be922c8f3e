"""The plain reference against the program, and the control against the
limit, on the CPU at small sizes."""

import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.harness import cell, check
from perfbench.reference import internlm2 as ref

SMALL = dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, d_head=16,
             d_ff=128, vocab=512)


def _config(**kw):
    spec = cell.load_spec()
    return {**cell.load_config(spec, "internlm2_1_8b"), **SMALL, **kw}


def test_reference_matches_the_programs_float32_path():
    """Same weights, float32 everywhere, no kernels and no cache: the
    program's forward and the reference give the same logits, so the two
    read one model (rotary form, head grouping, MLP roles, norms)."""
    from repro.models.model import RunOptions, get_model
    c = _config()
    w = ref.make_weights(c, 2**31 + 7)
    model = get_model(cell.program_config(c),
                      RunOptions(use_kernels=False, remat="none",
                                 param_dtype=jnp.float32,
                                 act_dtype=jnp.float32))
    toks = np.random.default_rng(3).integers(0, c["vocab"], (2, 24))
    p32 = {k: (v.astype(jnp.float32) if k != "layers" else
               {n: a.astype(jnp.float32) for n, a in v.items()})
           for k, v in w.items()}
    got, _ = model.forward(p32, {"tokens": jnp.asarray(toks)})
    want = ref.logits(c, w, toks, 0)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-4)


def test_weights_come_from_the_seed_in_the_served_type():
    c = _config()
    a, b = ref.make_weights(c, 5), ref.make_weights(c, 5)
    other = ref.make_weights(c, 5 + 2**32)
    assert a["layers"]["wq"].dtype == jnp.bfloat16
    assert np.array_equal(np.asarray(a["embed"]), np.asarray(b["embed"]))
    assert not np.array_equal(np.asarray(a["embed"]),
                              np.asarray(other["embed"]))


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_in_fp8_is_not_correct(seed):
    """The reference with its weights in fp8, put in the program's place,
    picks tokens whose widest gap breaks the configuration's limit (at the
    cell's own size on the chip it reads 0.37 and more; PERF.md)."""
    c = _config(n_layers=8, d_model=256, n_heads=2, n_kv_heads=1,
                d_head=128, d_ff=512, vocab=32000)
    w = ref.make_weights(c, seed)
    toks = np.random.default_rng(seed).integers(0, c["vocab"], (8, 128))
    full = ref.logits(c, w, toks, 64)
    low = ref.logits(c, w, toks, 64, check.fp8)
    gap = check.gaps_sd(full, low.argmax(-1)).max()
    assert gap > c["check"]["token_gap_sd"]
    # the reference's own picks read no gap at all
    assert check.gaps_sd(full, full.argmax(-1)).max() == 0.0
