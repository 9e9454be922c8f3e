"""Operations and bytes that a piece of work needs, from its shapes alone.

These count what the algorithm requires, not what an implementation
happens to do, so the same work is counted whatever computes it: causal
attention reads the half square including the diagonal, a decode step
reads the cache up to its own position and no further, and a request's
model FLOPs are those of the tokens it was served.
"""
from __future__ import annotations

import json
import pathlib

PEAKS = pathlib.Path(__file__).resolve().parents[1] / "peaks.json"


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip of ``device_kind``; an unknown kind
    is an error, never a default."""
    with open(PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise SystemExit(f"no peaks for device kind {device_kind!r} in "
                         f"{PEAKS.name}; known: {sorted(table)}")
    return table[device_kind]


def flash_attention(b: int, h: int, kv: int, s: int, d: int,
                    itemsize: int = 2) -> tuple[float, float]:
    """Causal self-attention over ``s`` positions: (flops, bytes).  QK^T
    and PV over the s(s+1)/2 causal pairs; q, k, v read and o written
    once."""
    pairs = s * (s + 1) / 2
    flops = 4.0 * b * h * d * pairs
    nbytes = itemsize * (2 * b * h * s * d + 2 * b * kv * s * d)
    return flops, nbytes


def flash_decode(b: int, h: int, kv: int, t: int, d: int,
                 itemsize: int = 2) -> tuple[float, float]:
    """One query token at position ``t`` against the cache: (flops, bytes).
    Keys and values of positions 0..t read once; q read and o written."""
    n = t + 1
    flops = 4.0 * b * h * d * n
    nbytes = itemsize * (2 * b * n * kv * d + 2 * b * h * d)
    return flops, nbytes


def roofline_s(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: the larger of the compute and
    the memory bound."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])


def matmul_params(c: dict) -> int:
    """Weights that every token multiplies in the decoder stack."""
    d, f = c["d_model"], c["d_ff"]
    h, kv = c["n_heads"], c["n_kv_heads"]
    dh = c.get("d_head") or d // h
    per_layer = d * h * dh * 2 + d * kv * dh * 2 + 3 * d * f
    return c["n_layers"] * per_layer


def request_flops(c: dict, prompt_len: int, gen_len: int) -> float:
    """Model FLOPs of serving one request: the prompt's prefill (which
    yields the first token) and ``gen_len - 1`` decode steps, each token
    through every weight matrix and attending causally, and the output head
    for each of the ``gen_len`` tokens."""
    d = c["d_model"]
    h = c["n_heads"]
    dh = c.get("d_head") or d // h
    p, g = prompt_len, gen_len
    tokens = p + g - 1
    dense = 2.0 * matmul_params(c) * tokens
    head = 2.0 * c["vocab"] * d * g
    # attention: token at position i attends to i + 1 positions
    pairs = tokens * (tokens + 1) / 2
    attn = 4.0 * c["n_layers"] * h * dh * pairs
    return dense + head + attn
