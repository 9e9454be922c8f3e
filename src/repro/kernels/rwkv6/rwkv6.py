"""WKV6 (RWKV-6 "Finch" time-mix) chunked-recurrence Pallas TPU kernel.

    S_t = diag(w_t) S_{t-1} + k_t v_t^T
    y_t = r_t (S_{t-1} + diag(u) k_t v_t^T)

Grid: (batch, heads, T // C) — the time dim iterates innermost, so the
running state S (K x V fp32) persists in VMEM scratch across chunks; it is
(re)loaded from ``s0`` at chunk 0 and written out after the last chunk.

Within a chunk (C = 32) the recurrence is evaluated in parallel exactly as
the jnp oracle does: cumulative log-decays, an inter-chunk matmul against
S, a (C, C, K) pairwise-decay intra-chunk term kept in log space (so no
exp overflow — decays ratios are always <= 1), and a rank-C state update.
VMEM: the pair tensor C*C*K*4B = 256 KiB at C=32, K=64 — the budget driver.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _wkv_kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, s0_ref,
                y_ref, sfin_ref, state_ref, *, chunk: int):
    cb = pl.program_id(2)
    n_c = pl.num_programs(2)

    @pl.when(cb == 0)
    def _load():
        state_ref[...] = s0_ref[0, 0].astype(jnp.float32)

    rc = r_ref[0, 0].astype(jnp.float32)        # (C, K)
    kc = k_ref[0, 0].astype(jnp.float32)
    vc = v_ref[0, 0].astype(jnp.float32)
    lwc = lw_ref[0, 0].astype(jnp.float32)      # (C, K) log-decay <= 0
    u = u_ref[0].astype(jnp.float32)            # (1, K)
    s = state_ref[...]                          # (K, V)

    # inclusive prefix sum over time as a lower-triangular matmul (the
    # TPU kernel compiler has no cumsum)
    tri = (jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0) >=
           jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1))
    cum = jax.lax.dot_general(tri.astype(jnp.float32), lwc,
                              (((1,), (0,)), ((), ())),
                              precision=jax.lax.Precision.HIGHEST,
                              preferred_element_type=jnp.float32)
    cum_prev = cum - lwc
    # inter-chunk: y += (r * exp(cum_prev)) @ S
    r_dec = rc * jnp.exp(cum_prev)
    y = jax.lax.dot_general(r_dec, s, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (C, V)
    # intra-chunk pairwise term (log-space decay ratios)
    ddiff = cum_prev[:, None, :] - cum[None, :, :]        # (C, C, K)
    att = jnp.sum(rc[:, None, :] * kc[None, :, :] *
                  jnp.exp(jnp.clip(ddiff, -60.0, 0.0)), axis=-1)  # (C, C)
    mask = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0) > \
        jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    att = jnp.where(mask, att, 0.0)
    y += jax.lax.dot_general(att, vc, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    # diagonal bonus: r_t (u . k_t) v_t
    diag = jnp.sum(rc * u * kc, axis=-1, keepdims=True)   # (C, 1)
    y += diag * vc
    y_ref[0, 0] = y.astype(y_ref.dtype)
    # state update: S' = diag(exp(cum_C)) S + sum_s exp(cum_C - cum_s) k_s v_s
    tail = cum[-1:, :] - cum                               # (C, K) <= 0
    k_dec = kc * jnp.exp(tail)
    # whole-chunk decay as a (K, 1) column: lw^T @ 1
    cum_col = jax.lax.dot_general(lwc, jnp.ones((chunk, 1), jnp.float32),
                                  (((0,), (0,)), ((), ())),
                                  precision=jax.lax.Precision.HIGHEST,
                                  preferred_element_type=jnp.float32)
    state_ref[...] = s * jnp.exp(cum_col) + jax.lax.dot_general(
        k_dec, vc, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(cb == n_c - 1)
    def _store():
        sfin_ref[0, 0] = state_ref[...]


def wkv6_pallas(r, k, v, lw, u, s0, *, chunk: int = 32, interpret=False):
    """r/k/v/lw: (B, H, T, K); u: (H, K); s0: (B, H, K, V) f32.

    Returns (y (B, H, T, K_v), s_fin (B, H, K, V) f32).  T % chunk == 0."""
    b, h, t, kd = r.shape
    vd = s0.shape[-1]
    assert t % chunk == 0, "pad T to a chunk multiple"
    nc = t // chunk

    kernel = functools.partial(_wkv_kernel, chunk=chunk)
    y, s_fin = pl.pallas_call(
        kernel,
        grid=(b, h, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, kd), lambda b_, h_, c: (b_, h_, c, 0)),
            pl.BlockSpec((1, 1, chunk, kd), lambda b_, h_, c: (b_, h_, c, 0)),
            pl.BlockSpec((1, 1, chunk, vd), lambda b_, h_, c: (b_, h_, c, 0)),
            pl.BlockSpec((1, 1, chunk, kd), lambda b_, h_, c: (b_, h_, c, 0)),
            # u as (H, 1, K): a block's last two dims must equal the
            # array's (or be (8, 128)-aligned) on the TPU
            pl.BlockSpec((1, 1, kd), lambda b_, h_, c: (h_, 0, 0)),
            pl.BlockSpec((1, 1, kd, vd), lambda b_, h_, c: (b_, h_, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, vd), lambda b_, h_, c: (b_, h_, c, 0)),
            pl.BlockSpec((1, 1, kd, vd), lambda b_, h_, c: (b_, h_, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, t, vd), r.dtype),
            jax.ShapeDtypeStruct((b, h, kd, vd), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((kd, vd), jnp.float32)],
        interpret=interpret,
    )(r, k, v, lw, u.reshape(h, 1, kd), s0)
    return y, s_fin
