"""Flash-decoding Pallas TPU kernel: one query token vs a long KV cache.

Grid: (batch, num_kv_blocks) — the kv dim iterates innermost (split-K over
the context); all query heads are processed together per block (decode is
HBM-bandwidth-bound: each cache byte is read exactly once).  Online-softmax
state (m, l, acc) sits in VMEM scratch, sized (H, D) — e.g. 64 heads x 128
x 4 B = 32 KiB.

Ring-cache masking (sliding-window / chunked-local) is supported via the
absolute-position reconstruction  p_i = t - ((t - i) mod W)  used by the
jnp path (`layers.decode_ring_attention`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _fd_step(t, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
             scale: float, window, local_block, block_k: int, kv_len: int,
             n_rep: int):
    """One kv block of the online softmax, for position ``t``.

    GQA runs as one 2-D contraction per kv-head group: query rows
    ``g*n_rep .. (g+1)*n_rep`` against kv head ``g``.  A batched einsum
    over a head-repeated cache is refused by the TPU compiler (Mosaic has
    no batched dot with this dimension layout)."""
    kb = pl.program_id(1)
    n_kv = pl.num_programs(1)
    kvh = k_ref.shape[2]

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    slots = kb * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_k), 1)
    if window is None and local_block is None:
        kv_pos = slots                                  # linear cache
        valid = kv_pos <= t
    else:
        w = kv_len
        kv_pos = t - ((t - slots) % w)                  # ring cache
        valid = kv_pos >= 0
        if window is not None:
            valid &= (t - kv_pos) < window
        if local_block is not None:
            valid &= kv_pos >= (t // local_block) * local_block
    valid &= slots < kv_len                             # (1, bk)
    v_valid = (kb * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_k, 1), 0)) < kv_len           # (bk, 1)

    for g in range(kvh):
        rows = slice(g * n_rep, (g + 1) * n_rep)
        q = q_ref[0, rows, :].astype(jnp.float32) * scale    # (n_rep, D)
        k = k_ref[0, :, g, :].astype(jnp.float32)            # (bk, D)
        v = v_ref[0, :, g, :].astype(jnp.float32)
        sc = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        sc = jnp.where(valid, sc, NEG_INF)                   # (n_rep, bk)
        m_prev = m_ref[rows, :]
        m_new = jnp.maximum(m_prev, sc.max(axis=1, keepdims=True))
        p = jnp.exp(sc - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[rows, :] = l_ref[rows, :] * alpha + p.sum(axis=1, keepdims=True)
        # zero the tail padding: 0 x garbage = NaN otherwise
        v = jnp.where(v_valid, v, 0.0)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[rows, :] = acc_ref[rows, :] * alpha + pv
        m_ref[rows, :] = m_new

    @pl.when(kb == n_kv - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _fd_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *, t,
               **kw):
    _fd_step(t, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, **kw)


def _fd_dyn_kernel(t_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
                   **kw):
    """Dynamic-position variant: ``t`` arrives as a scalar-prefetch ref
    (SMEM) instead of a Python int baked into the trace, so one compiled
    executable serves every decode step — the per-token recompile the
    static kernel would force is exactly what the serving executor's
    compile cache must never see."""
    _fd_step(t_ref[0], q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
             **kw)


def _scratch(h: int, d: int) -> list:
    # m and l are kept as (H, 1) columns so every per-group row slice is
    # a 2-D ref access
    return [pltpu.VMEM((h, d), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32)]


def flash_decode_dynamic(q, k_cache, v_cache, t, *, window=None,
                         local_block=None, block_k=512, interpret=False):
    """Like :func:`flash_decode`, but ``t`` is a traced int32 scalar
    delivered via scalar prefetch — jit once, decode every position.

    q: (B, H, D); caches: (B, S, KV, D); t: int32 array (any 0/1-d shape).
    Returns (B, H, D)."""
    b, h, d = q.shape
    s, kvh = k_cache.shape[1], k_cache.shape[2]
    n_rep = h // kvh
    block_k = min(block_k, s)
    nk = pl.cdiv(s, block_k)
    scale = 1.0 / np.sqrt(d)

    kernel = functools.partial(
        _fd_dyn_kernel, scale=scale, window=window, local_block=local_block,
        block_k=block_k, kv_len=s, n_rep=n_rep)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, nk),
        in_specs=[
            pl.BlockSpec((1, h, d), lambda b_, j, t_: (b_, 0, 0)),
            pl.BlockSpec((1, block_k, kvh, d), lambda b_, j, t_: (b_, j, 0, 0)),
            pl.BlockSpec((1, block_k, kvh, d), lambda b_, j, t_: (b_, j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, h, d), lambda b_, j, t_: (b_, 0, 0)),
        scratch_shapes=_scratch(h, d),
    )
    t_arr = jnp.reshape(jnp.asarray(t, jnp.int32), (1,))
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        interpret=interpret,
    )(t_arr, q, k_cache, v_cache)


def flash_decode(q, k_cache, v_cache, *, t, window=None, local_block=None,
                 block_k=512, interpret=False):
    """q: (B, H, D); caches: (B, S, KV, D); t: python int (current position).

    Returns (B, H, D)."""
    b, h, d = q.shape
    s, kvh = k_cache.shape[1], k_cache.shape[2]
    n_rep = h // kvh
    block_k = min(block_k, s)
    nk = pl.cdiv(s, block_k)
    scale = 1.0 / np.sqrt(d)

    kernel = functools.partial(
        _fd_kernel, scale=scale, t=t, window=window, local_block=local_block,
        block_k=block_k, kv_len=s, n_rep=n_rep)

    return pl.pallas_call(
        kernel,
        grid=(b, nk),
        in_specs=[
            pl.BlockSpec((1, h, d), lambda b_, j: (b_, 0, 0)),
            pl.BlockSpec((1, block_k, kvh, d), lambda b_, j: (b_, j, 0, 0)),
            pl.BlockSpec((1, block_k, kvh, d), lambda b_, j: (b_, j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, h, d), lambda b_, j: (b_, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        scratch_shapes=_scratch(h, d),
        interpret=interpret,
    )(q, k_cache, v_cache)
