"""Plain reference of InternLM2 (arXiv:2403.17297; the HF
``modeling_internlm2.py``), independent of the program under test.

A pre-norm decoder: token embedding; per layer RMSNorm -> grouped-query
attention with rotary positions (rotate-half form, theta ``rope_theta``),
causal softmax at 1/sqrt(d_head) -> output projection -> residual;
RMSNorm -> SwiGLU MLP ``down(silu(gate(x)) * up(x))`` -> residual; final
RMSNorm and an untied output head.  RMSNorm's epsilon is 1e-5.

Everything runs in float32 with ``Precision.HIGHEST``, one layer per jitted
call, so a 12-layer 20B stage fits beside its bf16 weights.

The weights are the benchmark's own, drawn from the seed in the type they
are served in.  They are laid out as the program expects to be handed them
(``layers`` stacked as ``(n_layers, 1, ...)``; ``w1`` the MLP's up
projection, ``w2`` its gate, ``w3`` its down projection), and
:func:`check_layout` refuses a program whose parameters differ.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
RMS_EPS = 1e-5
DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def layer_shapes(c: dict) -> dict[str, tuple]:
    d, f = c["d_model"], c["d_ff"]
    h, kv, dh = c["n_heads"], c["n_kv_heads"], d_head(c)
    return {"wq": (d, h * dh), "wk": (d, kv * dh), "wv": (d, kv * dh),
            "wo": (h * dh, d), "ln1_scale": (d,), "ln2_scale": (d,),
            "w1": (d, f), "w2": (d, f), "w3": (f, d)}


def d_head(c: dict) -> int:
    return c.get("d_head") or c["d_model"] // c["n_heads"]


def weight_shapes(c: dict) -> dict:
    dt = DTYPES[c["dtype"]]
    lay = {k: jax.ShapeDtypeStruct((c["n_layers"], 1) + s, dt)
           for k, s in layer_shapes(c).items()}
    top = {"embed": (c["vocab"], c["d_model"]),
           "final_norm_scale": (c["d_model"],),
           "lm_head": (c["vocab"], c["d_model"])}
    return {"layers": lay,
            **{k: jax.ShapeDtypeStruct(s, dt) for k, s in top.items()}}


def make_weights(c: dict, seed: int):
    """All weights in one jitted call on the device, from the seed.

    Matrices are normal with std 1/sqrt(fan_in) (the embedding std 1, so
    that its rows have the scale the first RMSNorm expects); norm scales are
    normal around 1 with std 0.1, so that a scale applied to the wrong
    tensor shows."""
    shapes = weight_shapes(c)
    flat, tree = jax.tree.flatten_with_path(shapes)

    def make(key):
        out = []
        for i, (path, s) in enumerate(flat):
            name = path[-1].key
            k = jax.random.fold_in(key, i)
            if "scale" in name:
                x = 1.0 + 0.1 * jax.random.normal(k, s.shape, jnp.float32)
            else:
                std = 1.0 if name == "embed" else s.shape[-2] ** -0.5
                if name == "lm_head":
                    std = s.shape[-1] ** -0.5
                x = std * jax.random.normal(k, s.shape, jnp.float32)
            out.append(x.astype(s.dtype))
        return jax.tree.unflatten(tree, out)

    return jax.jit(make)(seed_key(seed))


def seed_key(seed: int):
    """A PRNG key holding all of a (possibly over 32-bit) seed."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def check_layout(program_params, weights) -> None:
    """Refuse to hand the weights to a program that lays them out
    otherwise: the reference would then read another model."""
    want = jax.tree.map(lambda a: (a.shape, a.dtype), weights)
    got = jax.tree.map(lambda a: (a.shape, a.dtype), program_params)
    if jax.tree.structure(want) != jax.tree.structure(got) or \
            jax.tree.leaves(want) != jax.tree.leaves(got):
        raise SystemExit("the program's parameters are laid out otherwise "
                         "than the reference's weights; the benchmark's "
                         "loader needs the new layout")


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _rms(x, scale):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + RMS_EPS) \
        * scale


def _rope(x, pos, theta):
    """Rotate-half rotary embedding. x: (N, T, H, D)."""
    dh = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]        # (T, D/2)
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None, :]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    rot = jnp.concatenate([-x2, x1], -1)
    return x * cos + rot * sin


@functools.partial(jax.jit, static_argnames=("n_heads", "n_kv", "theta"))
def _layer(x, w, *, n_heads, n_kv, theta):
    """One decoder layer in float32. x: (N, T, D); w: this layer's weights
    (any float type, read as float32)."""
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    n, t, _ = x.shape
    dh = w["wq"].shape[1] // n_heads
    h = _rms(x, w["ln1_scale"])
    q = jnp.einsum("ntd,de->nte", h, w["wq"], precision=HIGHEST)
    k = jnp.einsum("ntd,de->nte", h, w["wk"], precision=HIGHEST)
    v = jnp.einsum("ntd,de->nte", h, w["wv"], precision=HIGHEST)
    pos = jnp.arange(t)
    q = _rope(q.reshape(n, t, n_heads, dh), pos, theta)
    k = _rope(k.reshape(n, t, n_kv, dh), pos, theta)
    v = v.reshape(n, t, n_kv, dh)
    # grouped-query: query head j reads kv head j // (n_heads // n_kv)
    q = q.reshape(n, t, n_kv, n_heads // n_kv, dh)
    s = jnp.einsum("nqgrd,nkgd->ngrqk", q, k, precision=HIGHEST) / np.sqrt(dh)
    causal = pos[:, None] >= pos[None, :]
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("ngrqk,nkgd->nqgrd", p, v, precision=HIGHEST)
    x = x + jnp.einsum("nte,ed->ntd", o.reshape(n, t, n_heads * dh),
                       w["wo"], precision=HIGHEST)
    h = _rms(x, w["ln2_scale"])
    gate = jnp.einsum("ntd,df->ntf", h, w["w2"], precision=HIGHEST)
    up = jnp.einsum("ntd,df->ntf", h, w["w1"], precision=HIGHEST)
    return x + jnp.einsum("ntf,fd->ntd", jax.nn.silu(gate) * up, w["w3"],
                          precision=HIGHEST)


@functools.partial(jax.jit, static_argnames=("first",))
def _final_norm(x, scale, *, first):
    return _rms(x[:, first:], scale.astype(jnp.float32))


@jax.jit
def _head(x, head):
    return jnp.einsum("ntd,vd->ntv", x, head.astype(jnp.float32),
                      precision=HIGHEST)


HEAD_ROWS = 16384         # output-head rows upcast at a time


def logits(c: dict, weights, tokens: np.ndarray, first: int,
           quantize=None) -> np.ndarray:
    """Float32 logits at positions ``first ..`` of each row of ``tokens``
    (N, T): the distribution each next token was drawn from.

    ``quantize(a, amax)`` maps each weight matrix (or a block of one,
    with the whole tensor's largest magnitude) before use: the control
    computes the same forward with its weights rounded to a lower
    precision."""
    def q(a, whole=None):
        if quantize is None:
            return a
        amax = jnp.max(jnp.abs(a if whole is None else whole))
        return quantize(a, amax)

    embed = weights["embed"]
    x = q(embed[jnp.asarray(tokens)], embed).astype(jnp.float32)
    lay = weights["layers"]
    for i in range(c["n_layers"]):
        w = {k: (v[i, 0] if "scale" in k else q(v[i, 0]))
             for k, v in lay.items()}
        x = _layer(x, w, n_heads=c["n_heads"], n_kv=c["n_kv_heads"],
                   theta=float(c["rope_theta"]))
    x = _final_norm(x, weights["final_norm_scale"], first=first)
    head = weights["lm_head"]
    return np.concatenate(
        [np.asarray(_head(x, q(head[v:v + HEAD_ROWS], head)))
         for v in range(0, head.shape[0], HEAD_ROWS)], axis=-1)
