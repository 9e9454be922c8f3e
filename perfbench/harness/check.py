"""What decides ``correct``: the served tokens against the plain reference.

After the window has closed and the program's state is freed, a sample of
the finished requests, drawn from the seed, is taken with the tokens the
timed decode loop was fed.  The reference runs once over each prompt and
its served tokens, in float32, and reads at every served position how far
the served token's logit lies below the reference's best, in units of the
standard deviation of the reference's logits there (``token_gap_sd``).  The
widest gap over the sample is held to the configuration's limit.  Greedy
decoding makes each served token the program's own argmax, so the gap is
the program's rounding seen through the reference.

The same comparison also accounts for every request due: each one is
either refused at the door or finished with a full row of tokens.

The control (``tools/readings.py``) puts the reference, with its weights in
fp8 (e4m3, one scale per tensor), in the program's place: at each position
it picks its own argmax, and that token's gap is read the same way.
"""
from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp
import numpy as np

SAMPLE = 8              # requests compared per run: 8 x 64 served tokens
MIN_TOKENS = 256        # served tokens a run has to compare


def reference(config: dict):
    name = config["reference"]
    return importlib.import_module(f"perfbench.reference.{name}")


def served_rows(batches, requests, gen_len: int) -> tuple[dict, int]:
    """uid -> (bucket, row, tokens (gen_len,)) for every finished request
    whose row the decode loop served, and the count of finished requests
    it did not (a row past the batch, or a loop cut short)."""
    rows, unserved = {}, 0
    host = {}
    for r in requests:
        b = r.batch
        if b is None:
            continue
        if id(b) not in host:
            host[id(b)] = (np.concatenate([np.asarray(t) for t in b.tokens],
                                          axis=1) if b.tokens else None)
        toks = host[id(b)]
        if toks is None or toks.shape[1] != gen_len or r.row >= toks.shape[0]:
            unserved += 1
            continue
        rows[r.uid] = (b.bucket, r.row, toks[r.row])
    return rows, unserved


def sample(uids: list[int], seed: int, k: int = SAMPLE) -> list[int]:
    rng = np.random.default_rng([seed, 2])
    uids = sorted(uids)
    pick = rng.choice(len(uids), size=min(k, len(uids)), replace=False)
    return [uids[i] for i in sorted(pick)]


def gaps_sd(ref_logits: np.ndarray, tokens: np.ndarray) -> np.ndarray:
    """(max - logit of ``tokens``) / std, per position.  ref_logits:
    (..., V) float32; tokens: (...) ints."""
    best = ref_logits.max(-1)
    got = np.take_along_axis(ref_logits, tokens[..., None], -1)[..., 0]
    return (best - got) / ref_logits.std(-1)


def sequences(picked, rows, prompts, gen_len: int):
    """One row (prompt + served tokens) for each picked request, and the
    served tokens each row is read against.  A sample smaller than
    ``SAMPLE`` repeats its last row, so the reference always runs at one
    shape and compiles once."""
    seqs, of = [], []
    for uid in picked:
        bucket, row, toks = rows[uid]
        seqs.append(np.concatenate([prompts[bucket][row],
                                    toks[: gen_len - 1]]))
        of.append((len(of), toks))
    seqs += seqs[-1:] * (SAMPLE - len(seqs))
    return np.stack(seqs), of


def compare(config: dict, weights, seqs: np.ndarray, of, prompt_len: int,
            quantize=None) -> dict:
    """Reference logits over ``seqs``; the served tokens' widest gap, and
    (for the control) the widest gap of the reference's own picks under
    ``quantize``."""
    ref = reference(config)
    logits = ref.logits(config, weights, seqs, prompt_len - 1)
    served = np.concatenate([gaps_sd(logits[i], t) for i, t in of])
    out = {"token_gap_sd": float(served.max()),
           "token_gap_mean": float(served.mean()),
           "tokens_compared": int(served.size)}
    if quantize is not None:
        low = ref.logits(config, weights, seqs, prompt_len - 1, quantize)
        picks = low.argmax(-1)
        ctrl = np.concatenate([gaps_sd(logits[i], picks[i]) for i, _ in of])
        out.update(control_gap_sd=float(ctrl.max()),
                   control_gap_mean=float(ctrl.mean()))
    return out


@jax.jit
def fp8(a, amax):
    """Round weights to 8-bit floats (4 exponent and 3 mantissa bits) with
    one scale per tensor (``amax`` is the tensor's largest magnitude), kept
    in float32: the control.  ``reduce_precision`` is the rounding XLA will
    not fold away, as it may a cast down and back up."""
    s = amax.astype(jnp.float32) / 240.0      # the format's largest finite
    q = jax.lax.reduce_precision(a.astype(jnp.float32) / s,
                                 exponent_bits=4, mantissa_bits=3)
    return q * s
