"""What the chip-side tools share: the path, the compile cache, the cell."""
from __future__ import annotations

import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]


def setup_path() -> None:
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def served(workload: str, seed: int):
    """The cell's set-up, built once for many windows."""
    from perfbench.harness import traffic
    from perfbench.harness.cell import Served, load_config, load_spec
    spec = load_spec()
    cell = next(w for w in spec["workloads"] if w["name"] == workload)
    mix = traffic.load_mix(cell["traffic"], cell["config"])
    return Served(cell, load_config(spec, cell["config"]), mix, seed)
