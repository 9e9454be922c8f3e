"""Jitted wrapper: model-layout adapter for the flash attention kernel.

The model passes (B, S, H, D) activations; the kernel wants heads-major.
On the CPU the kernel body runs under ``interpret=True`` (Python
emulation — correctness only); see ``repro.kernels.interpret_mode``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.flash_attention.flash_attention import flash_attention_fwd
from repro.kernels.flash_attention.ref import attention_ref


@functools.partial(jax.jit, static_argnames=("causal", "window",
                                             "local_block", "q_offset"))
def flash_attention(q, k, v, *, causal=True, window=None, local_block=None,
                    q_offset=0):
    """q: (B, S, H, D); k/v: (B, S, KV, D) -> (B, S, H, D)."""
    qT = jnp.swapaxes(q, 1, 2)
    kT = jnp.swapaxes(k, 1, 2)
    vT = jnp.swapaxes(v, 1, 2)
    out = flash_attention_fwd(qT, kT, vT, causal=causal, window=window,
                              local_block=local_block, q_offset=q_offset,
                              interpret=interpret_mode())
    return jnp.swapaxes(out, 1, 2)


def flash_attention_oracle(q, k, v, **kw):
    qT = jnp.swapaxes(q, 1, 2)
    kT = jnp.swapaxes(k, 1, 2)
    vT = jnp.swapaxes(v, 1, 2)
    return jnp.swapaxes(attention_ref(qT, kT, vT, **kw), 1, 2)
