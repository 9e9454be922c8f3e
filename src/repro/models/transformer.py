"""Decoder-only transformer covering the dense / moe / vlm / audio families.

Layer stacking uses ``lax.scan`` over *macro blocks* so that HLO size is
depth-independent even for heterogeneous stacks: an arch with
``global_every = N`` (llama4: 3 chunked-local layers then 1 global layer)
scans over L/N macro blocks whose bodies unroll the N sub-layers, each with
its own attention kind and its own KV-cache geometry.

Modes:
  * train   — full-sequence logits + LM loss (no cache).
  * prefill — forward over the prompt, KV caches written, last-token logits.
  * decode  — one token against the cache.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import ModelConfig
from repro.models import layers as L
from repro.models import kvcache, moe

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class RunOptions:
    """Runtime (not architecture) options — the perf knobs of §Perf."""
    attn_chunk: int = 1024
    remat: str = "full"            # full | none
    param_dtype: Any = jnp.bfloat16
    act_dtype: Any = jnp.bfloat16
    use_kernels: bool = False      # Pallas kernels (TPU) vs jnp oracle
    causal_pair_scan: bool = False # triangular chunk-pair attention (§Perf)
    logit_chunk: int = 512         # CE loss seq-chunking (memory control)
    explicit_tp_ffn: bool = False  # shard_map FFN w/ bf16 collectives (P5)
    shard_constraints: bool = False  # emit with_sharding_constraint (pjit runs)
    dp_spec: Any = ("data",)       # mesh axes carrying the batch
    tp_name: str = "model"
    sharding_mode: str = "auto"    # auto | 2d | dp_only (see shardings.py)
    seq_shard_decode: bool = True  # shard_map flash-decoding (§Perf)
    mesh: Any = None               # concrete mesh for shard_map paths


constrain = L.constrain


def chunked_lm_loss(x: Array, head: Array, labels: Array,
                    opts: RunOptions) -> Array:
    """Cross-entropy without materialising full-sequence logits.

    Scans over sequence chunks; per chunk the (B, C, V) logits are built,
    reduced and discarded.  Under pjit the vocab dim is constrained to the
    'model' axis so GSPMD never all-gathers the unembedding (the naive form
    emitted a full-vocab (B,S,V) all-reduce — 24 GB/device at train_4k)."""
    b, s, d = x.shape
    c = min(opts.logit_chunk, s)
    nc = s // c
    tm = nc * c

    def chunk_loss(xc, lc):
        logits = jnp.einsum("bsd,vd->bsv", xc, head,
                            preferred_element_type=jnp.float32)
        logits = constrain(logits, opts, ("B", None, "M"))
        logz = jax.nn.logsumexp(logits, axis=-1)
        iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 2)
        ll = jnp.sum(jnp.where(iota == lc[..., None], logits, 0.0), axis=-1)
        return jnp.sum(logz - ll)

    def body(acc, inp):
        xc, lc = inp
        return acc + chunk_loss(xc, lc), None

    xs = jnp.moveaxis(x[:, :tm].reshape(b, nc, c, d), 1, 0)
    ls = jnp.moveaxis(labels[:, :tm].reshape(b, nc, c), 1, 0)
    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (xs, ls))
    if tm < s:
        total = total + chunk_loss(x[:, tm:], labels[:, tm:])
    return total / (b * s)


# ---------------------------------------------------------------------------
# Layer geometry
# ---------------------------------------------------------------------------
def macro_shape(cfg: ModelConfig) -> tuple[int, int, list[str]]:
    """(n_macro, macro_size, kinds) — kinds[j] in {full, window, local, global}."""
    if cfg.global_every:
        m = cfg.global_every
        kinds = ["local"] * (m - 1) + ["global"]
        return cfg.n_layers // m, m, kinds
    kind = "window" if cfg.window else "full"
    return cfg.n_layers, 1, [kind]


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------
def _layer_param_shapes(cfg: ModelConfig) -> dict[str, tuple]:
    d, f = cfg.d_model, cfg.d_ff
    h, kv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    shapes: dict[str, tuple] = {
        "wq": (d, h * dh), "wk": (d, kv * dh), "wv": (d, kv * dh),
        "wo": (h * dh, d),
        "ln1_scale": (d,), "ln2_scale": (d,),
    }
    if cfg.qkv_bias:
        shapes.update({"bq": (h * dh,), "bk": (kv * dh,), "bv": (kv * dh,)})
    if cfg.norm == "layernorm":
        shapes.update({"ln1_bias": (d,), "ln2_bias": (d,)})
    if cfg.n_experts:
        e = cfg.n_experts
        shapes.update({
            "router": (d, e),
            "moe_w1": (e, d, f), "moe_w2": (e, d, f), "moe_w3": (e, f, d),
        })
    elif cfg.mlp == "swiglu":
        shapes.update({"w1": (d, f), "w2": (d, f), "w3": (f, d)})
    else:
        shapes.update({"w1": (d, f), "b1": (f,), "w3": (f, d), "b3": (d,)})
    return shapes


def param_specs(cfg: ModelConfig, opts: RunOptions = RunOptions()) -> dict:
    n_macro, m, _ = macro_shape(cfg)
    pd = opts.param_dtype
    lp = {k: jax.ShapeDtypeStruct((n_macro, m) + s, pd)
          for k, s in _layer_param_shapes(cfg).items()}
    top = {
        "embed": jax.ShapeDtypeStruct((cfg.vocab, cfg.d_model), pd),
        "final_norm_scale": jax.ShapeDtypeStruct((cfg.d_model,), pd),
    }
    if cfg.norm == "layernorm":
        top["final_norm_bias"] = jax.ShapeDtypeStruct((cfg.d_model,), pd)
    if not cfg.tie_embeddings:
        top["lm_head"] = jax.ShapeDtypeStruct((cfg.vocab, cfg.d_model), pd)
    return {"layers": lp, **top}


def init_params(cfg: ModelConfig, key: Array,
                opts: RunOptions = RunOptions()) -> dict:
    specs = param_specs(cfg, opts)
    flat, treedef = jax.tree_util.tree_flatten_with_path(specs)
    keys = jax.random.split(key, len(flat))
    out = []
    for (path, spec), k in zip(flat, keys):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        if "norm" in name or name.startswith("ln"):
            arr = (jnp.ones if "scale" in name else jnp.zeros)(spec.shape, spec.dtype)
        elif name.startswith("b"):
            arr = jnp.zeros(spec.shape, spec.dtype)
        else:
            arr = L.dense_init(k, spec.shape, spec.dtype)
        out.append(arr)
    return jax.tree.unflatten(jax.tree.structure(specs), out)


# ---------------------------------------------------------------------------
# Cache
# ---------------------------------------------------------------------------
def cache_specs(cfg: ModelConfig, batch: int, max_len: int,
                opts: RunOptions = RunOptions()) -> dict:
    n_macro, m, kinds = macro_shape(cfg)
    kvh, dh = cfg.n_kv_heads, cfg.d_head
    ad = opts.act_dtype
    specs: dict[str, Any] = {"t": jax.ShapeDtypeStruct((), jnp.int32)}
    if cfg.global_every:
        wl = kvcache.cache_len(cfg, max_len, "local")
        specs["k_local"] = jax.ShapeDtypeStruct(
            (n_macro, m - 1, batch, wl, kvh, dh), ad)
        specs["v_local"] = specs["k_local"]
        specs["k_global"] = jax.ShapeDtypeStruct(
            (n_macro, 1, batch, max_len, kvh, dh), ad)
        specs["v_global"] = specs["k_global"]
    else:
        w = kvcache.cache_len(cfg, max_len, kinds[0])
        specs["k"] = jax.ShapeDtypeStruct((n_macro, m, batch, w, kvh, dh), ad)
        specs["v"] = specs["k"]
    return specs


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               opts: RunOptions = RunOptions()) -> dict:
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                        cache_specs(cfg, batch, max_len, opts))


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------
def _norm(cfg, w, prefix, x):
    if cfg.norm == "rmsnorm":
        return L.rms_norm(x, w[f"{prefix}_scale"])
    return L.layer_norm(x, w[f"{prefix}_scale"], w[f"{prefix}_bias"])


def _use_explicit_tp(opts, mode="full_seq"):
    return (opts is not None and getattr(opts, "explicit_tp_ffn", False)
            and opts.mesh is not None and mode != "decode"
            and opts.tp_name not in tuple(opts.dp_spec or ()))


def _qkv(cfg, w, x, positions, opts=None, mode="full_seq"):
    b, s, _ = x.shape
    h, kv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    if _use_explicit_tp(opts, mode):
        q = L.explicit_tp_matmul(x, w["wq"], opts, row=False)
        k = L.explicit_tp_matmul(x, w["wk"], opts, row=False)
        v = L.explicit_tp_matmul(x, w["wv"], opts, row=False)
    else:
        q = jnp.einsum("bsd,dh->bsh", x, w["wq"])
        k = jnp.einsum("bsd,dh->bsh", x, w["wk"])
        v = jnp.einsum("bsd,dh->bsh", x, w["wv"])
    if cfg.qkv_bias:
        q, k, v = q + w["bq"], k + w["bk"], v + w["bv"]
    q = q.reshape(b, s, h, dh)
    k = k.reshape(b, s, kv, dh)
    v = v.reshape(b, s, kv, dh)
    if opts is not None:
        q = constrain(q, opts, ("B", None, "M", None))
        k = constrain(k, opts, ("B", None, "M", None))
        v = constrain(v, opts, ("B", None, "M", None))
    q = L.apply_rope(q, positions, cfg.rope_theta)
    k = L.apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _mlp(cfg, w, x, opts=None):
    """Returns (out, aux_loss)."""
    if cfg.n_experts:
        b, s, d = x.shape
        # decode (S == 1): route the flattened batch as one row so capacity
        # tracks the true token count instead of E-per-token waste.
        xr = x.reshape(1, b, d) if s == 1 else x
        out, aux = moe.moe_ffn(
            xr, w["router"], w["moe_w1"], w["moe_w2"], w["moe_w3"],
            n_experts=cfg.n_experts, top_k=cfg.top_k,
            capacity_factor=cfg.capacity_factor,
            opts=None if s == 1 else opts)
        return out.reshape(b, s, d), aux
    if cfg.mlp == "swiglu":
        if opts is not None and getattr(opts, "explicit_tp_ffn", False) \
                and opts.mesh is not None \
                and opts.tp_name not in tuple(opts.dp_spec or ()):
            return L.explicit_tp_swiglu(x, w["w1"], w["w2"], w["w3"],
                                        opts), 0.0
        return L.swiglu_mlp(x, w["w1"], w["w2"], w["w3"]), 0.0
    return L.gelu_mlp(x, w["w1"], w["b1"], w["w3"], w["b3"]), 0.0


def _attn_full_seq(cfg, w, x, kind, opts, q_offset=0):
    """Attention over a full sequence (train / prefill). Returns (out, k, v)."""
    b, s, _ = x.shape
    positions = q_offset + jnp.arange(s)
    q, k, v = _qkv(cfg, w, x, positions, opts)
    window = cfg.window if kind == "window" else None
    local = cfg.chunk_attn if kind == "local" else None
    if opts.use_kernels:
        from repro.kernels.flash_attention import ops as fa_ops
        o = fa_ops.flash_attention(q, k, v, causal=True, window=window,
                                   local_block=local, q_offset=q_offset)
    else:
        o = L.chunked_attention(q, k, v, causal=True, window=window,
                                local_block=local, chunk=opts.attn_chunk,
                                q_offset=q_offset)
    o = o.reshape(b, s, cfg.n_heads * cfg.d_head)
    if _use_explicit_tp(opts):
        out = L.explicit_tp_matmul(o, w["wo"], opts, row=True)
    else:
        out = jnp.einsum("bsh,hd->bsd", o, w["wo"],
                         preferred_element_type=o.dtype)
    return out, k, v


def _seq_shard_decode(cfg, opts, q, k_new, v_new, k_cache, v_cache, t, kind):
    """Flash-decoding over the sequence-sharded cache via shard_map.

    Baseline GSPMD turns the one-token cache write (dynamic-update-slice on
    the 'model'-sharded seq dim) into a full cache all-gather per layer --
    1 GB x n_layers at decode_32k (EXPERIMENTS §Perf).  Here each seq shard:
      * writes the new token only if it owns slot t (masked local DUS),
      * computes partial attention over its slice (all heads local),
      * combines via a logsumexp pmax/psum -- KBs on the wire per layer.
    """
    from jax.sharding import PartitionSpec as P
    axis = opts.tp_name
    mesh = opts.mesh
    n_shards = dict(zip(mesh.axis_names, mesh.devices.shape))[axis]
    bspec = tuple(opts.dp_spec) if opts.dp_spec else None
    n_rep = cfg.n_heads // cfg.n_kv_heads
    window = cfg.window if kind == "window" else None
    local_block = cfg.chunk_attn if kind == "local" else None
    scale = 1.0 / np.sqrt(cfg.d_head)

    def local_fn(q, kn, vn, kc, vc, t):
        idx = jax.lax.axis_index(axis)
        s_loc = kc.shape[1]
        w_total = s_loc * n_shards
        slot = t if kind in ("full", "global") else t % w_total
        lo = idx * s_loc
        in_range = jnp.logical_and(slot >= lo, slot < lo + s_loc)
        loc = jnp.clip(slot - lo, 0, s_loc - 1)
        cur_k = jax.lax.dynamic_slice_in_dim(kc, loc, 1, 1)
        cur_v = jax.lax.dynamic_slice_in_dim(vc, loc, 1, 1)
        kc = jax.lax.dynamic_update_slice_in_dim(
            kc, jnp.where(in_range, kn, cur_k), loc, 1)
        vc = jax.lax.dynamic_update_slice_in_dim(
            vc, jnp.where(in_range, vn, cur_v), loc, 1)

        slots = lo + jnp.arange(s_loc)
        if kind in ("full", "global"):
            pos = slots
            valid = pos <= t
        else:
            pos = t - ((t - slots) % w_total)
            valid = pos >= 0
            if window is not None:
                valid &= (t - pos) < window
            if local_block is not None:
                valid &= pos >= (t // local_block) * local_block

        k = L._expand_kv(kc, n_rep)
        v = L._expand_kv(vc, n_rep)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(valid[None, None, None, :], s, L.NEG_INF)
        m_loc = s.max(axis=-1)                           # (B, H, 1)
        p = jnp.exp(s - m_loc[..., None])
        l_loc = p.sum(axis=-1)
        acc = jnp.einsum("bhqk,bkhd->bhqd", p.astype(v.dtype), v,
                         preferred_element_type=jnp.float32)
        m_g = jax.lax.pmax(m_loc, axis)
        corr = jnp.exp(m_loc - m_g)
        l_g = jax.lax.psum(l_loc * corr, axis)
        acc_g = jax.lax.psum(acc * corr[..., None], axis)
        out = acc_g / jnp.maximum(l_g[..., None], 1e-30)
        out = jnp.moveaxis(out, 1, 2).astype(q.dtype)    # (B, 1, H, D)
        return out, kc, vc

    cspec = P(bspec, axis, None, None)
    fn = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(bspec), P(bspec), P(bspec), cspec, cspec, P()),
        out_specs=(P(bspec), cspec, cspec), check_vma=False)
    return fn(q, k_new, v_new, k_cache, v_cache, t)


def _attn_decode(cfg, w, x, k_cache, v_cache, t, kind, opts):
    """One-token attention. x: (B,1,D). Returns (out, k_cache', v_cache')."""
    b = x.shape[0]
    q, k_new, v_new = _qkv(cfg, w, x, t[None] if t.ndim == 0 else t, opts,
                           mode="decode")
    if opts.seq_shard_decode and opts.mesh is not None:
        o, k_cache, v_cache = _seq_shard_decode(
            cfg, opts, q, k_new, v_new, k_cache, v_cache, t, kind)
        o = o.reshape(b, 1, cfg.n_heads * cfg.d_head)
        return jnp.einsum("bsh,hd->bsd", o, w["wo"]), k_cache, v_cache
    wsize = k_cache.shape[1]
    if kind in ("full", "global"):
        k_cache = jax.lax.dynamic_update_slice_in_dim(k_cache, k_new, t, axis=1)
        v_cache = jax.lax.dynamic_update_slice_in_dim(v_cache, v_new, t, axis=1)
        if opts.use_kernels:
            # flash-decoding kernel with the position delivered via
            # scalar prefetch: the same compiled executable serves every
            # decode step (a static t would recompile per token, which
            # the serving executor's compile cache must never see)
            from repro.kernels.flash_decode import ops as fd_ops
            o = fd_ops.flash_decode_at(q[:, 0], k_cache, v_cache, t)[:, None]
        else:
            o = L.decode_attention(q, k_cache, v_cache, length=t + 1)
    else:
        slot = t % wsize
        k_cache = jax.lax.dynamic_update_slice_in_dim(k_cache, k_new, slot, axis=1)
        v_cache = jax.lax.dynamic_update_slice_in_dim(v_cache, v_new, slot, axis=1)
        window = cfg.window if kind == "window" else None
        local = cfg.chunk_attn if kind == "local" else None
        o = L.decode_ring_attention(q, k_cache, v_cache, t=t,
                                    window=window, local_block=local)
    o = o.reshape(b, 1, cfg.n_heads * cfg.d_head)
    return jnp.einsum("bsh,hd->bsd", o, w["wo"]), k_cache, v_cache


def _sublayer(cfg, w, x, kind, opts, mode, cache_kv=None, t=None, q_offset=0):
    """One transformer layer.  Returns (x, aux, new_kv)."""
    h = _norm(cfg, w, "ln1", x)
    if mode == "decode":
        a, k_c, v_c = _attn_decode(cfg, w, h, cache_kv[0], cache_kv[1], t, kind, opts)
        new_kv = (k_c, v_c)
    else:
        a, k, v = _attn_full_seq(cfg, w, h, kind, opts, q_offset)
        new_kv = (k, v)
    x = constrain(x + a, opts, ("B", None, None))
    h = _norm(cfg, w, "ln2", x)
    mlp_out, aux = _mlp(cfg, w, h, opts)
    return constrain(x + mlp_out, opts, ("B", None, None)), aux, new_kv


# ---------------------------------------------------------------------------
# Full-sequence forward (train / prefill)
# ---------------------------------------------------------------------------
def _embed(cfg, params, tokens, prefix_embeds, opts):
    x = params["embed"][tokens].astype(opts.act_dtype)
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(opts.act_dtype), x], axis=1)
    return constrain(x, opts, ("B", None, None))


def forward(cfg: ModelConfig, params: dict, tokens: Array,
            prefix_embeds: Optional[Array] = None,
            opts: RunOptions = RunOptions(),
            mode: str = "train",
            cache: Optional[dict] = None,
            max_len: Optional[int] = None):
    """mode='train': (logits, aux).  mode='prefill': (last_logits, cache)."""
    n_macro, m, kinds = macro_shape(cfg)
    x = _embed(cfg, params, tokens, prefix_embeds, opts)
    b, s, _ = x.shape

    want_cache = mode == "prefill"

    def block(x, block_w):
        auxes = 0.0
        kvs = []
        for j in range(m):
            wj = {k: v[j] for k, v in block_w.items()}
            x, aux, kv = _sublayer(cfg, wj, x, kinds[j], opts, "full_seq")
            auxes = auxes + aux
            kvs.append(kv)
        return x, auxes, kvs

    def scan_body(x, block_w):
        if opts.remat == "full":
            bl = jax.checkpoint(block, policy=jax.checkpoint_policies.nothing_saveable)
        else:
            bl = block
        x, aux, kvs = bl(x, block_w)
        if want_cache:
            ks = jnp.stack([kv[0] for kv in kvs])  # (m, B, S, KV, DH)
            vs = jnp.stack([kv[1] for kv in kvs])
            return x, (aux, ks, vs)
        return x, (aux, None, None)

    x, (auxes, ks, vs) = jax.lax.scan(scan_body, x, params["layers"])
    x = _norm(cfg, params, "final_norm", x)
    aux = jnp.sum(auxes) if cfg.n_experts else 0.0

    head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    if mode == "hidden":
        return x, aux
    if mode == "train":
        logits = jnp.einsum("bsd,vd->bsv", x, head,
                            preferred_element_type=jnp.float32)
        return logits, aux

    # prefill: build the cache
    last = x[:, -1:, :]
    logits = jnp.einsum("bsd,vd->bsv", last, head,
                        preferred_element_type=jnp.float32)
    new_cache = _fill_cache(cfg, ks, vs, s, opts, max_len)
    return logits[:, 0], new_cache


def _fill_cache(cfg, ks, vs, s, opts, max_len=None):
    """ks/vs: (n_macro, m, B, S, KV, DH) fresh keys — pack into cache layout."""
    n_macro, m, kinds = macro_shape(cfg)
    max_len = max_len if max_len is not None else s
    cache: dict[str, Any] = {"t": jnp.asarray(s, jnp.int32)}

    def pad_to(arr, width):
        if arr.shape[-3] >= width:
            return arr
        pad = [(0, 0)] * arr.ndim
        pad[-3] = (0, width - arr.shape[-3])
        return jnp.pad(arr, pad)

    def pack_ring(k_part, v_part, width):
        # keep last ``width`` positions, arranged at ring slots (pos % width)
        w = min(width, s)
        k_last = k_part[..., s - w:, :, :]
        v_last = v_part[..., s - w:, :, :]
        if w < width:  # context shorter than the ring — pad tail slots
            pad = [(0, 0)] * k_last.ndim
            pad[-3] = (0, width - w)
            k_last = jnp.pad(k_last, pad)
            v_last = jnp.pad(v_last, pad)
            return k_last.astype(opts.act_dtype), v_last.astype(opts.act_dtype)
        # roll so that physical slot i holds position with pos % width == i
        shift = (s - w) % width
        k_last = jnp.roll(k_last, shift, axis=-3)
        v_last = jnp.roll(v_last, shift, axis=-3)
        return k_last.astype(opts.act_dtype), v_last.astype(opts.act_dtype)

    if cfg.global_every:
        wl = kvcache.cache_len(cfg, max_len, "local")
        cache["k_local"], cache["v_local"] = pack_ring(
            ks[:, : m - 1], vs[:, : m - 1], wl)
        cache["k_global"] = pad_to(ks[:, m - 1:].astype(opts.act_dtype), max_len)
        cache["v_global"] = pad_to(vs[:, m - 1:].astype(opts.act_dtype), max_len)
    else:
        w = kvcache.cache_len(cfg, max_len, kinds[0])
        if w == s and max_len == s:
            cache["k"], cache["v"] = ks.astype(opts.act_dtype), vs.astype(opts.act_dtype)
        elif kinds[0] == "full":
            cache["k"] = pad_to(ks.astype(opts.act_dtype), max_len)
            cache["v"] = pad_to(vs.astype(opts.act_dtype), max_len)
        else:
            cache["k"], cache["v"] = pack_ring(ks, vs, w)
    return cache


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------
def decode_step(cfg: ModelConfig, params: dict, cache: dict, tokens: Array,
                opts: RunOptions = RunOptions()):
    """tokens: (B, 1) int32.  Returns (logits (B, V), new_cache)."""
    n_macro, m, kinds = macro_shape(cfg)
    t = cache["t"]
    x = params["embed"][tokens[:, :1]].astype(opts.act_dtype)

    if cfg.global_every:
        xs = (params["layers"], cache["k_local"], cache["v_local"],
              cache["k_global"], cache["v_global"])

        def body(x, scanned):
            block_w, kl, vl, kg, vg = scanned
            new_kl, new_vl, new_kg, new_vg = [], [], [], []
            for j in range(m):
                wj = {k: v[j] for k, v in block_w.items()}
                if kinds[j] == "local":
                    x, _, (nk, nv) = _sublayer(cfg, wj, x, "local", opts,
                                               "decode", (kl[j], vl[j]), t)
                    new_kl.append(nk); new_vl.append(nv)
                else:
                    x, _, (nk, nv) = _sublayer(cfg, wj, x, "global", opts,
                                               "decode", (kg[0], vg[0]), t)
                    new_kg.append(nk); new_vg.append(nv)
            return x, (jnp.stack(new_kl), jnp.stack(new_vl),
                       jnp.stack(new_kg), jnp.stack(new_vg))

        x, (kl, vl, kg, vg) = jax.lax.scan(body, x, xs)
        new_cache = {"t": t + 1, "k_local": kl, "v_local": vl,
                     "k_global": kg, "v_global": vg}
    else:
        xs = (params["layers"], cache["k"], cache["v"])

        def body(x, scanned):
            block_w, kc, vc = scanned
            nks, nvs = [], []
            for j in range(m):
                wj = {k: v[j] for k, v in block_w.items()}
                x, _, (nk, nv) = _sublayer(cfg, wj, x, kinds[j], opts,
                                           "decode", (kc[j], vc[j]), t)
                nks.append(nk); nvs.append(nv)
            return x, (jnp.stack(nks), jnp.stack(nvs))

        x, (ks, vs) = jax.lax.scan(body, x, xs)
        new_cache = {"t": t + 1, "k": ks, "v": vs}

    x = _norm(cfg, params, "final_norm", x)
    head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("bsd,vd->bsv", x, head,
                        preferred_element_type=jnp.float32)
    return logits[:, 0], new_cache


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------
def lm_loss(cfg: ModelConfig, params: dict, tokens: Array, labels: Array,
            prefix_embeds: Optional[Array] = None,
            opts: RunOptions = RunOptions()):
    """Chunked cross-entropy (vocab stays sharded; see chunked_lm_loss)."""
    x, aux = forward(cfg, params, tokens, prefix_embeds, opts, "hidden")
    if prefix_embeds is not None:           # loss only over token positions
        x = x[:, prefix_embeds.shape[1]:]
    head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    loss = chunked_lm_loss(x, head, labels, opts)
    if cfg.n_experts:
        loss = loss + 0.01 * aux
    return loss
