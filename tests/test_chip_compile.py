"""Compile the served path's Pallas kernels for a described TPU v5e.

The TPU compiler is installed even where no chip is attached: lowering
against a described ``v5e:2x2`` topology raises exactly what the chip's
compiler would (unaligned blocks, unsupported dot layouts, VMEM
overflow), which interpret-mode parity tests cannot see.  Widths are the
published ones the server runs: internlm2_1_8b attention (16 query
heads, 8 kv heads, d_head 128) at batch 8, prompt 512 + 32 generated
tokens, and rwkv6_1_6b's WKV (32 heads of 64).

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and every test worker
imports this file.
"""
from __future__ import annotations

import dataclasses
import os
import pathlib
import re
import sys

import jax
import jax.numpy as jnp
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

B, H, KV, D = 8, 16, 8, 128
PROMPT, MAX_LEN = 512, 544


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *specs) -> str:
    return jax.jit(fn).lower(*specs).compile().as_text()


def test_flash_attention_prefill_compiles_for_v5e(one_chip):
    from repro.kernels.flash_attention.flash_attention import \
        flash_attention_fwd
    q = _spec((B, H, PROMPT, D), jnp.bfloat16, one_chip)
    kv = _spec((B, KV, PROMPT, D), jnp.bfloat16, one_chip)
    text = _compiled_text(
        lambda q, k, v: flash_attention_fwd(q, k, v, causal=True), q, kv, kv)
    assert "tpu_custom_call" in text


def test_flash_decode_at_compiles_for_v5e(one_chip):
    from repro.kernels.flash_decode.flash_decode import flash_decode_dynamic
    q = _spec((B, H, D), jnp.bfloat16, one_chip)
    cache = _spec((B, MAX_LEN, KV, D), jnp.bfloat16, one_chip)
    t = _spec((), jnp.int32, one_chip)
    text = _compiled_text(flash_decode_dynamic, q, cache, cache, t)
    assert "tpu_custom_call" in text


def test_flash_decode_static_compiles_for_v5e(one_chip):
    from repro.kernels.flash_decode.flash_decode import flash_decode
    q = _spec((B, H, D), jnp.bfloat16, one_chip)
    cache = _spec((B, MAX_LEN, KV, D), jnp.bfloat16, one_chip)
    text = _compiled_text(
        lambda q, k, v: flash_decode(q, k, v, t=PROMPT), q, cache, cache)
    assert "tpu_custom_call" in text


def test_wkv6_compiles_for_v5e(one_chip):
    from repro.kernels.rwkv6.rwkv6 import wkv6_pallas
    h, k = 32, 64                                   # rwkv6_1_6b WKV heads
    x = _spec((B, h, PROMPT, k), jnp.bfloat16, one_chip)
    u = _spec((h, k), jnp.bfloat16, one_chip)
    s0 = _spec((B, h, k, k), jnp.float32, one_chip)
    text = _compiled_text(wkv6_pallas, x, x, x, x, u, s0)
    assert "tpu_custom_call" in text


def test_served_decode_program_keeps_the_cache_in_place_on_v5e(
        one_chip, monkeypatch):
    """The served decode program (internlm2_1_8b at its published widths,
    two layers, batch 32, prompt 512 + 64) writes each layer's new row into
    the donated cache and nothing else: no cache-sized slice, copy or
    buffer, the cache aliased from input to output, and the kernel op
    still read by the benchmark's trace parser as one layer's call."""
    from perfbench.harness.devtrace import parse
    from repro.configs.registry import get_config
    from repro.kernels.flash_decode import ops as fd_ops
    from repro.models.model import RunOptions, get_model
    from repro.serving.executor import cache_copies
    monkeypatch.setattr(fd_ops, "interpret_mode", lambda: False)
    bucket, max_len = 32, 576
    cfg = dataclasses.replace(get_config("internlm2_1_8b"), n_layers=2)
    model = get_model(cfg, RunOptions(use_kernels=True, remat="none",
                                      param_dtype=jnp.bfloat16,
                                      act_dtype=jnp.bfloat16))

    def on_chip(tree):
        return jax.tree.map(
            lambda a: _spec(a.shape, a.dtype, one_chip), tree)

    params = on_chip(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    cache = on_chip(model.cache_specs(bucket, max_len))
    nxt = _spec((bucket, 1), jnp.int32, one_chip)
    text = jax.jit(model.decode, donate_argnums=(1,)).lower(
        params, cache, nxt).compile().as_text()

    assert cache_copies(text, cache) == 0
    alias = text.split("input_output_alias=", 1)[1].split("\n", 1)[0]
    aliased = {int(i) for i in re.findall(r"\((\d+), \{\}, may-alias\)",
                                          alias.split("entry_computation")[0])}
    n_params = len(jax.tree.leaves(params))
    keys = sorted(cache)                        # flattened in key order
    assert {n_params + keys.index("k"), n_params + keys.index("v")} <= aliased
    op = next(line.strip() for line in text.splitlines()
              if line.strip().startswith("%flash_decode_at"))
    shapes = parse(op)
    assert (shapes["b"], shapes["h"], shapes["d"], shapes["l"],
            shapes["kv"]) == (bucket, H, D, max_len, KV)
