"""Shared neural-net layers for the model zoo.

Everything is pure-functional JAX (params passed explicitly) so that models
compose under ``jax.lax.scan`` over stacked layer weights and lower cleanly
under pjit on arbitrary meshes.

Attention comes in three flavours:
  * ``chunked_attention``  — flash-style blockwise causal attention (the jnp
    oracle of the Pallas kernel) used for train/prefill shapes.  Memory is
    O(S * chunk) instead of O(S^2).
  * ``decode_attention``   — single-token attention against a (possibly
    sequence-sharded) KV cache.
  * sliding-window / chunked-local variants via ``window`` masking on a ring
    cache (sub-quadratic decode).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array
NEG_INF = -1e30


def constrain(x: Array, opts, pattern: tuple) -> Array:
    """with_sharding_constraint helper.  pattern entries: 'B' (batch/dp axes),
    'M' (model/TP axis), None.  No-op unless opts.shard_constraints."""
    if opts is None or not getattr(opts, "shard_constraints", False) \
            or opts.dp_spec is None:
        return x
    # dp_only mode: 'model' carries batch; 'M' entries collapse to None
    tp = opts.tp_name if opts.tp_name not in tuple(opts.dp_spec) else None
    spec = jax.sharding.PartitionSpec(
        *[tuple(opts.dp_spec) if e == "B" else
          (tp if e == "M" else None) for e in pattern])
    return jax.lax.with_sharding_constraint(x, spec)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------
def rms_norm(x: Array, scale: Array, eps: float = 1e-5) -> Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    out = x * jax.lax.rsqrt(var + eps)
    return (out * (1.0 + 0.0) * scale.astype(jnp.float32)).astype(dtype)


def layer_norm(x: Array, scale: Array, bias: Array, eps: float = 1e-5) -> Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    out = (x - mean) * jax.lax.rsqrt(var + eps)
    return (out * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dtype)


def apply_norm(x: Array, norm_params: dict[str, Array], kind: str) -> Array:
    if kind == "rmsnorm":
        return rms_norm(x, norm_params["scale"])
    return layer_norm(x, norm_params["scale"], norm_params["bias"])


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------
def rope_frequencies(d_head: int, theta: float) -> Array:
    return 1.0 / (theta ** (np.arange(0, d_head, 2, dtype=np.float32) / d_head))


def apply_rope(x: Array, positions: Array, theta: float) -> Array:
    """x: (..., S, H, D) ; positions: broadcastable to (..., S)."""
    d_head = x.shape[-1]
    freqs = rope_frequencies(d_head, theta)  # (D/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, D/2)
    cos = jnp.cos(angles)[..., None, :]  # (..., S, 1, D/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention (flash-style chunked oracle; also the ref for the Pallas kernel)
# ---------------------------------------------------------------------------
def _expand_kv(k: Array, n_rep: int) -> Array:
    """(B, S, KV, D) -> (B, S, KV * n_rep, D) by head repetition (GQA)."""
    if n_rep == 1:
        return k
    b, s, kv, d = k.shape
    k = jnp.broadcast_to(k[:, :, :, None, :], (b, s, kv, n_rep, d))
    return k.reshape(b, s, kv * n_rep, d)


def chunked_attention(
    q: Array,
    k: Array,
    v: Array,
    *,
    causal: bool = True,
    window: int | None = None,
    local_block: int | None = None,
    chunk: int = 1024,
    q_offset: int = 0,
) -> Array:
    """Blockwise (flash-style) attention.

    q: (B, Sq, H, D); k/v: (B, Skv, KV, D) with H % KV == 0.
    ``window``: sliding-window size (None = full causal).
    Memory: O(Sq * chunk) per head.  Computes all (q-chunk, kv-chunk) pairs;
    masked pairs cost FLOPs but no memory (see EXPERIMENTS §Perf for the
    triangular-pair optimisation).
    """
    b, sq, h, d = q.shape
    skv = k.shape[1]
    n_rep = h // k.shape[2]
    k = _expand_kv(k, n_rep)
    v = _expand_kv(v, n_rep)

    scale = 1.0 / np.sqrt(d)
    chunk = min(chunk, skv)
    n_chunks = skv // chunk
    rem = skv - n_chunks * chunk

    q_pos = q_offset + jnp.arange(sq)

    qf = (q * scale).astype(q.dtype)

    def attend_block(carry, inputs):
        acc, m_run, l_run = carry
        k_blk, v_blk, kv_start = inputs
        # scores: (B, H, Sq, C)
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, k_blk,
                       preferred_element_type=jnp.float32)
        kv_pos = kv_start + jnp.arange(k_blk.shape[1])
        mask = jnp.ones((sq, k_blk.shape[1]), bool)
        if causal:
            mask &= q_pos[:, None] >= kv_pos[None, :]
        if window is not None:
            mask &= q_pos[:, None] - kv_pos[None, :] < window
        if local_block is not None:
            mask &= (q_pos[:, None] // local_block) == (kv_pos[None, :] // local_block)
        s = jnp.where(mask[None, None], s, NEG_INF)
        m_new = jnp.maximum(m_run, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m_run - m_new)
        l_new = l_run * alpha + p.sum(axis=-1)
        pv = jnp.einsum("bhqk,bkhd->bhqd", p.astype(v_blk.dtype), v_blk,
                        preferred_element_type=jnp.float32)
        acc = acc * alpha[..., None] + pv
        return (acc, m_new, l_new), None

    acc0 = jnp.zeros((b, h, sq, d), jnp.float32)
    m0 = jnp.full((b, h, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)

    if n_chunks > 0:
        ks = k[:, : n_chunks * chunk].reshape(b, n_chunks, chunk, h, d)
        vs = v[:, : n_chunks * chunk].reshape(b, n_chunks, chunk, h, d)
        ks = jnp.moveaxis(ks, 1, 0)
        vs = jnp.moveaxis(vs, 1, 0)
        starts = jnp.arange(n_chunks) * chunk
        (acc0, m0, l0), _ = jax.lax.scan(
            attend_block, (acc0, m0, l0), (ks, vs, starts))
    if rem:
        (acc0, m0, l0), _ = attend_block(
            (acc0, m0, l0),
            (k[:, n_chunks * chunk:], v[:, n_chunks * chunk:],
             jnp.asarray(n_chunks * chunk)),
        )

    out = acc0 / jnp.maximum(l0[..., None], 1e-30)
    return jnp.moveaxis(out, 1, 2).astype(q.dtype)  # (B, Sq, H, D)


def decode_attention(
    q: Array,
    k_cache: Array,
    v_cache: Array,
    *,
    length: Array | int,
) -> Array:
    """One-token attention.  q: (B, 1, H, D); caches: (B, S, KV, D).

    ``length`` — number of valid cache entries.  The cache sequence dim may be
    sharded (long-context decode); softmax reductions then lower to
    all-reduces under GSPMD (flash-decode-style combine).
    """
    b, _, h, d = q.shape
    s = k_cache.shape[1]
    n_rep = h // k_cache.shape[2]
    k = _expand_kv(k_cache, n_rep)
    v = _expand_kv(v_cache, n_rep)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32)
    scores *= 1.0 / np.sqrt(d)
    valid = jnp.arange(s)[None, None, None, :] < jnp.asarray(length).reshape(-1, 1, 1, 1)
    scores = jnp.where(valid, scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


def decode_ring_attention(
    q: Array,
    k_cache: Array,
    v_cache: Array,
    *,
    t: Array,
    window: int | None = None,
    local_block: int | None = None,
) -> Array:
    """One-token attention over a ring cache.

    q: (B, 1, H, D); caches: (B, W, KV, D).  ``t`` = current position (the new
    token's position; cache holds positions <= t).  Ring slot i holds absolute
    position  p_i = t - ((t - i) mod W)  (-ve => not yet written).
    """
    b, _, h, d = q.shape
    w = k_cache.shape[1]
    n_rep = h // k_cache.shape[2]
    k = _expand_kv(k_cache, n_rep)
    v = _expand_kv(v_cache, n_rep)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32)
    scores *= 1.0 / np.sqrt(d)
    i = jnp.arange(w)
    kv_pos = t - ((t - i) % w)                 # (W,) absolute positions
    mask = kv_pos >= 0
    if window is not None:
        mask &= (t - kv_pos) < window
    if local_block is not None:
        mask &= kv_pos >= (t // local_block) * local_block
    scores = jnp.where(mask[None, None, None, :], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# MLP blocks
# ---------------------------------------------------------------------------
def swiglu_mlp(x: Array, w1: Array, w2: Array, w3: Array) -> Array:
    """LLaMA-style gated MLP.  w1/w2: (D, F); w3: (F, D).  The row-parallel
    w3 dot emits the activation dtype directly so the TP partial-sum
    all-reduce runs in bf16, not the f32 accumulator (EXPERIMENTS §Perf)."""
    h = jnp.einsum("bsd,df->bsf", x, w1) * jax.nn.silu(
        jnp.einsum("bsd,df->bsf", x, w2))
    return jnp.einsum("bsf,fd->bsd", h, w3, preferred_element_type=x.dtype)


def explicit_tp_swiglu(x: Array, w1: Array, w2: Array, w3: Array,
                       opts) -> Array:
    """SwiGLU with *explicit* TP collectives via shard_map (§Perf P5).

    GSPMD reduces the row-parallel partial sums on the dot's f32
    excess-precision accumulator (P0: dtype hints refuted) and re-gathers
    the FSDP weight shards in whatever dtype it meets.  Here the FFN runs
    per TP shard: weights are all-gathered over 'data' in bf16, the local
    dot output stays bf16 into an explicit psum over 'model' — halving
    both collective families.  Differentiable (shard_map AD:
    psum <-> identity, all_gather <-> psum_scatter)."""
    mesh = opts.mesh
    tp = opts.tp_name
    fsdp = "data"

    def local_fn(x, w1, w2, w3):
        # weight blocks arrive (D/|data|, F/|model|): un-FSDP in bf16
        w1 = jax.lax.all_gather(w1, fsdp, axis=0, tiled=True)
        w2 = jax.lax.all_gather(w2, fsdp, axis=0, tiled=True)
        w3 = jax.lax.all_gather(w3, fsdp, axis=1, tiled=True)
        h = jnp.einsum("bsd,df->bsf", x, w1) * jax.nn.silu(
            jnp.einsum("bsd,df->bsf", x, w2))
        y = jnp.einsum("bsf,fd->bsd", h, w3,
                       preferred_element_type=x.dtype)
        return jax.lax.psum(y, tp)

    P = jax.sharding.PartitionSpec
    b = tuple(opts.dp_spec) if opts.dp_spec else None
    fn = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(b, None, None), P(fsdp, tp), P(fsdp, tp), P(tp, fsdp)),
        out_specs=P(b, None, None), check_vma=False)
    return fn(x, w1, w2, w3)


def explicit_tp_matmul(x: Array, w: Array, opts, *, row: bool) -> Array:
    """Column-/row-parallel projection with explicit bf16 collectives.

    Shards the *flattened* feature dim (always divisible by |model|, unlike
    head counts), all-gathers the FSDP weight shard in bf16, and row mode
    psums the bf16 partial outputs (GSPMD would reduce the f32
    excess-precision accumulator — §Perf P0/P5).  AD: dw reduces via
    psum_scatter over 'data' (bf16 ZeRO-grad), dx stays local (row) /
    psums bf16 (col)."""
    mesh, tp, fsdp = opts.mesh, opts.tp_name, "data"
    P = jax.sharding.PartitionSpec
    b = tuple(opts.dp_spec) if opts.dp_spec else None
    if row:   # x: (B,S,K) K sharded over tp; w: (K,N) P(tp, fsdp)
        def f(x, w):
            w = jax.lax.all_gather(w, fsdp, axis=1, tiled=True)
            y = jnp.einsum("bsk,kn->bsn", x, w,
                           preferred_element_type=x.dtype)
            return jax.lax.psum(y, tp)
        return jax.shard_map(f, mesh=mesh,
                             in_specs=(P(b, None, tp), P(tp, fsdp)),
                             out_specs=P(b, None, None),
                             check_vma=False)(x, w)
    # column: x replicated over tp; w: (K,N) P(fsdp, tp) -> out tp-sharded
    def f(x, w):
        w = jax.lax.all_gather(w, fsdp, axis=0, tiled=True)
        return jnp.einsum("bsk,kn->bsn", x, w,
                          preferred_element_type=x.dtype)
    return jax.shard_map(f, mesh=mesh,
                         in_specs=(P(b, None, None), P(fsdp, tp)),
                         out_specs=P(b, None, tp), check_vma=False)(x, w)


def gelu_mlp(x: Array, w1: Array, b1: Array, w3: Array, b3: Array) -> Array:
    h = jax.nn.gelu(jnp.einsum("bsd,df->bsf", x, w1) + b1)
    return jnp.einsum("bsf,fd->bsd", h, w3,
                      preferred_element_type=x.dtype) + b3


# ---------------------------------------------------------------------------
# Parameter init helpers
# ---------------------------------------------------------------------------
def dense_init(key: Array, shape: tuple[int, ...], dtype=jnp.bfloat16,
               scale: float | None = None) -> Array:
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    scale = scale if scale is not None else 1.0 / np.sqrt(fan_in)
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def split_keys(key: Array, n: int) -> list[Array]:
    return list(jax.random.split(key, n))
