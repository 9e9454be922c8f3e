"""Serving profiles for the model zoo: the ESG <-> TPU bridge.

The paper reads function latencies from measured profile tables; here each
architecture becomes a servable function whose latency over the
(batch, vcpu, vtpu-chips) lattice comes from the v5e roofline model —
calibrated against the dry-run's compiled cost analysis when the cell JSONs
exist (useful-FLOPs overhead factor), analytic otherwise.

A "job" = one inference request: prefill(prompt_len) + gen_len decode steps.
vTPU semantics per DESIGN §2: g chips serve the task as a pjit sub-mesh —
batch data-parallel + per-inference tensor-parallel, with an ICI efficiency
penalty that grows with g.
"""
from __future__ import annotations

import dataclasses
import json
import pathlib

import numpy as np

from repro.configs.registry import ModelConfig, get_config, ARCH_IDS
from repro.core.profiles import FunctionProfile, ProfileTable
from repro.launch.roofline import V5E, peaks

_V5E = peaks(V5E)

DRYRUN_DIR = pathlib.Path(__file__).resolve().parents[3] / \
    "benchmarks" / "results" / "dryrun"


@dataclasses.dataclass(frozen=True)
class ServingSpec:
    prompt_len: int = 512
    gen_len: int = 64
    cpu_ms_per_job: float = 3.0        # tokenize/detokenize host work
    cold_ms: float = 8000.0            # weights load + compile cache hit
    input_mb: float = 0.05             # request payload


class TPUFunctionProfile(FunctionProfile):
    """FunctionProfile whose exec_ms comes from the roofline model."""

    def __init__(self, cfg: ModelConfig, spec: ServingSpec = ServingSpec(),
                 overhead: float | None = None):
        self._cfg = cfg
        self._spec = spec
        self._overhead = overhead if overhead is not None \
            else _calibrated_overhead(cfg.name)
        t1 = self._exec_ms_raw(1, 1, 1)
        super().__init__(name=cfg.name, t1_ms=t1, cold_ms=spec.cold_ms,
                         input_mb=spec.input_mb, cpu_frac=0.0,
                         model_mb=2.0 * cfg.n_params / 1e6)  # bf16 weights

    # latency model --------------------------------------------------------
    def _decode_ms(self, batch: int, chips: int) -> float:
        n = self._cfg.n_active_params
        w_bytes = 2.0 * self._cfg.n_params          # bf16 weights read
        kv_bytes = 2.0 * 2 * self._cfg.n_layers * self._cfg.n_kv_heads * \
            self._cfg.d_head * self._spec.prompt_len * batch
        t_mem = (w_bytes + kv_bytes) / (chips * _V5E.hbm_bw)
        t_flop = 2.0 * n * batch / (chips * _V5E.flops)
        ici = 1.0 + 0.08 * np.log2(max(chips, 1))   # collective penalty
        return max(t_mem, t_flop) * ici * self._overhead * 1e3

    def _prefill_ms(self, batch: int, chips: int) -> float:
        n = self._cfg.n_active_params
        toks = batch * self._spec.prompt_len
        t_flop = 2.0 * n * toks / (chips * _V5E.flops)
        t_mem = 2.0 * self._cfg.n_params / (chips * _V5E.hbm_bw)
        ici = 1.0 + 0.08 * np.log2(max(chips, 1))
        return max(t_flop, t_mem) * ici * self._overhead * 1e3

    def _exec_ms_raw(self, batch: int, vcpu: int, chips: int) -> float:
        t = self._prefill_ms(batch, chips) + \
            self._spec.gen_len * self._decode_ms(batch, chips)
        t_cpu = self._spec.cpu_ms_per_job * batch / (vcpu ** 0.7)
        return t + t_cpu

    def exec_ms(self, c, quota_vgpu=None) -> float:  # Config(batch,vcpu,vgpu)
        # fractional quota throttles the TPU part only — host tokenize/
        # detokenize work is unaffected by the accelerator share
        t_tpu = self._prefill_ms(c.batch, c.vgpu) + \
            self._spec.gen_len * self._decode_ms(c.batch, c.vgpu)
        t_cpu = self._spec.cpu_ms_per_job * c.batch / (c.vcpu ** 0.7)
        return t_tpu * self.quota_factor(c, quota_vgpu) + t_cpu


def _calibrated_overhead(arch: str) -> float:
    """Compiled-FLOPs / model-FLOPs from the decode dry-run cell — how much
    wider the real compiled graph is than the 2ND ideal."""
    f = DRYRUN_DIR / f"{arch}__decode_32k__single.json"
    try:
        d = json.loads(f.read_text())
        r = d["roofline"]
        useful = r.get("useful_ratio", 1.0)
        if useful and 0.02 < useful <= 1.0:
            return float(np.clip(1.0 / useful, 1.0, 4.0))
    except Exception:
        pass
    return 1.3


def zoo_tables(archs: list[str] | None = None,
               spec: ServingSpec = ServingSpec(),
               max_chips: int = 8) -> dict[str, ProfileTable]:
    out = {}
    for a in archs or ARCH_IDS:
        fp = TPUFunctionProfile(get_config(a), spec)
        out[a] = ProfileTable.build(fp, vgpus=tuple(range(1, max_chips + 1)))
    return out
