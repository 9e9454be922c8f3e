"""The one traffic generator: turns a mix file's parameters into requests.

Two kinds of mix:

* ``open_mmpp`` -- an open loop.  The arrivals of a run follow a two-state
  Markov-modulated Poisson process (quiet / burst), the shape of the
  program's ``mmpp`` scenario (``serving/traces.py``, copied here so that
  the yardstick cannot move with the program).  The work is fixed: a run of
  ``seconds`` at ``rate_rps`` always holds ``round(rate_rps * seconds)``
  requests at the same due times, drawn once from the mix's
  ``pattern_seed``.  The run's seed draws what the requests hold (the
  prompts, and the weights they are served with), not when they come: a
  seed that moved the bursts would change the work itself, and the tail
  of one 51 s window would then swing by some tens of percent from seed to
  seed.
* ``closed`` -- ``clients`` callers, each sending its next request the
  moment the previous one completes.

Both carry the request shape (``prompt_len``, ``gen_len``) and the fixed
latency objective ``slo_ms``; nothing here is derived from a measurement of
the program.
"""
from __future__ import annotations

import json
import pathlib

import numpy as np

MIX_DIR = pathlib.Path(__file__).resolve().parents[1] / "mixes"


def load_mix(traffic: str, config: str, mix_dir: pathlib.Path = MIX_DIR
             ) -> dict:
    """The mix ``traffic`` as one cell runs it: ``mixes/<traffic>.json``,
    overlaid with the config's own operating point
    ``mixes/<traffic>/<config>.json`` (rate, SLO) where that file exists."""
    with open(mix_dir / f"{traffic}.json") as f:
        mix = json.load(f)
    point = mix_dir / traffic / f"{config}.json"
    if point.exists():
        with open(point) as f:
            mix.update(json.load(f))
    for key in ("kind", "prompt_len", "gen_len", "batch_lattice", "slo_ms"):
        if key not in mix:
            raise ValueError(f"mix {traffic} for {config} has no {key!r}")
    return mix


def mmpp_gaps(n: int, burst_factor: float, p_switch: float,
              pattern_seed: int) -> np.ndarray:
    """``n`` inter-arrival gaps of a quiet/burst MMPP (unit quiet mean).
    After each arrival the chain flips state with probability
    ``p_switch``; the burst state's mean gap is ``1 / burst_factor``."""
    rng = np.random.default_rng(pattern_seed)
    gaps = np.empty(n)
    state = 0
    for i in range(n):
        if rng.random() < p_switch:
            state = 1 - state
        gaps[i] = rng.exponential(1.0 / burst_factor if state else 1.0)
    return gaps


def open_arrivals(mix: dict, seconds: float) -> np.ndarray:
    """Due times (ms from the window's start) of an ``open_mmpp`` run."""
    n = max(int(round(mix["rate_rps"] * seconds)), 1)
    gaps = mmpp_gaps(n, mix["burst_factor"], mix["p_switch"],
                     mix["pattern_seed"])
    # scale to the exact offered rate: n arrivals in ``seconds``, the first
    # at 0 and the last one gap before the window closes
    gaps = gaps * (seconds * 1e3 / gaps.sum())
    return np.concatenate([[0.0], np.cumsum(gaps[:-1])])


def prompts(vocab: int, batch_lattice, prompt_len: int, seed: int
            ) -> dict[int, np.ndarray]:
    """The prompt rows each batch bucket serves, drawn from the seed.

    The program pads a batch of n requests to its bucket and serves the
    bucket's rows, so a request's prompt is fixed by (bucket, row)."""
    rng = np.random.default_rng([seed, 1])
    return {b: rng.integers(0, vocab, (b, prompt_len), dtype=np.int32)
            for b in sorted(batch_lattice)}
