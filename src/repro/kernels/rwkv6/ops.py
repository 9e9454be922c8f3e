"""Jitted wrapper for the WKV6 kernel, in the model's (B, T, H, K) layout."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.rwkv6.rwkv6 import wkv6_pallas
from repro.kernels.rwkv6.ref import wkv6_ref


@jax.jit
def wkv6(r, k, v, lw, u, s0):
    """r/k/v/lw: (B, T, H, K); u: (H, K); s0: (B, H, K, V) f32.

    Returns (y (B, T, H, V), s_fin)."""
    t = r.shape[1]
    chunk = 32
    pad = (-t) % chunk
    args = [jnp.moveaxis(a, 1, 2) for a in (r, k, v)]
    lwT = jnp.moveaxis(lw, 1, 2)
    if pad:
        args = [jnp.pad(a, ((0, 0), (0, 0), (0, pad), (0, 0))) for a in args]
        # pad decays with 0 (= decay 1.0) so the padded steps keep S intact;
        # padded k rows are zero so they add nothing
        lwT = jnp.pad(lwT, ((0, 0), (0, 0), (0, pad), (0, 0)))
    y, s_fin = wkv6_pallas(*args, lwT, u, s0, chunk=chunk,
                           interpret=interpret_mode())
    y = y[:, :, :t]
    return jnp.moveaxis(y, 1, 2), s_fin
