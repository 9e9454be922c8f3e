"""KV-cache structures.

Three cache layouts, chosen per architecture:
  * full ring-less cache   (B, S_max, KV, DH) per layer — full causal attention.
  * ring cache             (B, W, KV, DH) — sliding-window (mixtral, hymba) and
    chunked-local (llama4 local layers).  ``positions`` (B, W) records absolute
    positions so masks can be recovered after wrap-around.
  * SSM state              (B, H, K, V) + token-shift states — RWKV6 / hymba.

All per-layer caches are stacked on a leading layer axis so the layer loop is a
single ``lax.scan``.  In decode each stack rides the scan's carry, not its
xs/ys: a layer writes its one new row into the stack in place and reads its
own rows where they lie, so no layer's cache is sliced out, written back or
copied whole each step.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

Array = jax.Array


def cache_len(cfg, shape_kind_max_len: int, kind: str) -> int:
    """Physical cache length for a layer kind given logical max context."""
    if kind == "local" and cfg.chunk_attn:
        return min(cfg.chunk_attn, shape_kind_max_len)
    if cfg.window is not None:
        return min(cfg.window, shape_kind_max_len)
    return shape_kind_max_len


def ring_slots(pos0: Array | int, n: int, width: int) -> Array:
    """Physical slots for logical positions pos0..pos0+n-1 in a ring of width."""
    return (pos0 + jnp.arange(n)) % width


def ring_write(k_cache: Array, v_cache: Array, k_new: Array, v_new: Array,
               pos0: Array | int) -> tuple[Array, Array]:
    """Write S_new entries at logical positions pos0.. into ring caches.

    k_cache: (B, W, KV, DH); k_new: (B, S_new, KV, DH).  If S_new >= W only the
    last W entries survive (handled by the modular scatter: later writes win —
    we pre-truncate to the last W entries to keep scatter deterministic).
    """
    w = k_cache.shape[1]
    s_new = k_new.shape[1]
    if s_new >= w:
        # keep only last W entries
        start = s_new - w
        k_new = jax.lax.dynamic_slice_in_dim(k_new, start, w, axis=1)
        v_new = jax.lax.dynamic_slice_in_dim(v_new, start, w, axis=1)
        pos0 = pos0 + start
        s_new = w
    slots = ring_slots(pos0, s_new, w)  # (S_new,)
    k_cache = k_cache.at[:, slots].set(k_new)
    v_cache = v_cache.at[:, slots].set(v_new)
    return k_cache, v_cache


def ring_positions(pos_array: Array, pos0: Array | int, n: int) -> Array:
    """Update the shared (B-agnostic) position map (W,) int32."""
    w = pos_array.shape[0]
    if n >= w:
        start = n - w
        pos0 = pos0 + start
        n = w
    slots = ring_slots(pos0, n, w)
    return pos_array.at[slots].set(pos0 + jnp.arange(n))
