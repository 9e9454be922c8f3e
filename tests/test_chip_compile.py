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

import os

import jax
import jax.numpy as jnp
import pytest

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
