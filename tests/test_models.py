"""Per-arch smoke tests (reduced configs, CPU): one forward/train step,
shape + finiteness asserts; prefill->decode == full forward consistency."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs.registry import ARCH_IDS, ShapeSpec, get_config, reduced
from repro.models.model import RunOptions, get_model

OPTS = RunOptions(attn_chunk=16, remat="none",
                  param_dtype=jnp.float32, act_dtype=jnp.float32)


@pytest.fixture(scope="module")
def key():
    return jax.random.PRNGKey(0)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_train_step(arch, key):
    cfg = reduced(get_config(arch))
    m = get_model(cfg, OPTS)
    params = m.init(key)
    batch = m.dummy_inputs(ShapeSpec("t", 64, 2, "train"), key)
    loss, grads = jax.value_and_grad(m.loss)(params, batch)
    assert jnp.isfinite(loss)
    assert loss.shape == ()
    gn = sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads))
    assert jnp.isfinite(gn) and gn > 0


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_forward_shapes(arch, key):
    cfg = reduced(get_config(arch))
    m = get_model(cfg, OPTS)
    params = m.init(key)
    batch = m.dummy_inputs(ShapeSpec("t", 64, 2, "prefill"), key)
    logits, cache = m.prefill(params, batch, max_len=80)
    assert logits.shape == (2, cfg.vocab)
    assert jnp.isfinite(logits).all()


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_prefill_decode_matches_forward(arch, key):
    cfg = reduced(get_config(arch))
    m = get_model(cfg, OPTS)
    params = m.init(key)
    S, extra = 48, 3
    tokens = jax.random.randint(key, (2, S + extra), 0, cfg.vocab, jnp.int32)
    batch = {"tokens": tokens}
    if cfg.frontend == "vit" and cfg.n_prefix:
        batch["prefix_embeds"] = jax.random.normal(
            key, (2, cfg.n_prefix, cfg.d_model), jnp.float32)
    full, _ = m.forward(params, batch)
    off = cfg.n_prefix if (cfg.frontend == "vit" and cfg.n_prefix) else 0
    pb = dict(batch)
    pb["tokens"] = tokens[:, :S]
    lg, cache = m.prefill(params, pb, max_len=S + off + extra + 1)
    scale = float(jnp.abs(full).max())
    assert jnp.abs(lg - full[:, off + S - 1]).max() < 1e-3 * scale
    for i in range(extra):
        lg, cache = m.decode(params, cache, tokens[:, S + i:S + i + 1])
        assert jnp.abs(lg - full[:, off + S + i]).max() < 1e-3 * scale


@pytest.mark.parametrize("use_kernels", [True, False])
def test_decode_writes_one_row_per_layer_in_place(use_kernels, key):
    """After n decode steps the cache is the prefill cache of the longer
    prompt: rows of the prompt untouched, each step's row holding its K/V,
    rows past the last step still empty."""
    import dataclasses
    import numpy as np
    cfg = reduced(get_config("internlm2_1_8b"))
    m = get_model(cfg, dataclasses.replace(OPTS, use_kernels=use_kernels))
    params = m.init(key)
    S, n, max_len = 24, 4, 32
    tokens = jax.random.randint(key, (2, S + n), 0, cfg.vocab, jnp.int32)
    _, start = m.prefill(params, {"tokens": tokens[:, :S]}, max_len=max_len)
    _, longer = m.prefill(params, {"tokens": tokens[:, :S + n]},
                          max_len=max_len)
    cache = jax.tree.map(jnp.copy, start)
    for i in range(n):
        _, cache = m.decode(params, cache, tokens[:, S + i:S + i + 1])
    assert int(cache["t"]) == S + n
    for name in ("k", "v"):
        got = np.asarray(cache[name])
        np.testing.assert_array_equal(got[..., :S, :, :],
                                      np.asarray(start[name])[..., :S, :, :])
        want = np.asarray(longer[name])[..., S:S + n, :, :]
        np.testing.assert_allclose(got[..., S:S + n, :, :], want,
                                   rtol=1e-4, atol=1e-4 * np.abs(want).max())
        assert not got[..., S + n:, :, :].any()


def test_moe_routing_flop_exact():
    """Capacity+gather MoE computes at most cf x active-expert slots."""
    from repro.models import moe
    cfg = reduced(get_config("mixtral_8x22b"))
    key = jax.random.PRNGKey(1)
    r, t, d, e, f = 2, 32, cfg.d_model, cfg.n_experts, cfg.d_ff
    x = jax.random.normal(key, (r, t, d), jnp.float32)
    router = jax.random.normal(key, (d, e), jnp.float32) * 0.1
    w1 = jax.random.normal(key, (e, d, f), jnp.float32) * 0.05
    w2 = jax.random.normal(key, (e, d, f), jnp.float32) * 0.05
    w3 = jax.random.normal(key, (e, f, d), jnp.float32) * 0.05
    out, aux = moe.moe_ffn(x, router, w1, w2, w3, n_experts=e,
                           top_k=2, capacity_factor=4.0)
    assert out.shape == x.shape
    assert jnp.isfinite(out).all()
    assert jnp.isfinite(aux)
    cap = moe.capacity(t, e, 2, 4.0)
    assert cap <= t * 2


def test_moe_matches_dense_mixture():
    """With capacity ample, gather-MoE == explicit dense top-k mixture."""
    from repro.models import moe
    key = jax.random.PRNGKey(2)
    r, t, d, e, f, k = 1, 16, 8, 4, 12, 2
    x = jax.random.normal(key, (r, t, d), jnp.float32)
    ks = jax.random.split(key, 4)
    router = jax.random.normal(ks[0], (d, e), jnp.float32)
    w1 = jax.random.normal(ks[1], (e, d, f), jnp.float32) * 0.3
    w2 = jax.random.normal(ks[2], (e, d, f), jnp.float32) * 0.3
    w3 = jax.random.normal(ks[3], (e, f, d), jnp.float32) * 0.3
    out, _ = moe.moe_ffn(x, router, w1, w2, w3, n_experts=e, top_k=k,
                         capacity_factor=e * 2.0)
    # dense reference
    probs = jax.nn.softmax(x @ router, axis=-1)
    gv, gi = jax.lax.top_k(probs, k)
    gv = gv / gv.sum(-1, keepdims=True)
    h = jnp.einsum("rtd,edf->rtef", x, w1) * jax.nn.silu(
        jnp.einsum("rtd,edf->rtef", x, w2))
    ye = jnp.einsum("rtef,efd->rted", h, w3)
    mask = jax.nn.one_hot(gi, e).sum(-2) * 0  # build combine weights
    comb = jnp.zeros((r, t, e))
    for j in range(k):
        comb = comb + jax.nn.one_hot(gi[..., j], e) * gv[..., j:j + 1]
    ref = jnp.einsum("rted,rte->rtd", ye, comb)
    assert jnp.abs(out - ref).max() < 1e-4 * float(jnp.abs(ref).max() + 1)
