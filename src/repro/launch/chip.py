"""What the entry points that run on a chip share: the persistent
compile cache and the device they report.

Nothing here touches JAX's config at import; entry points call
:func:`use_compile_cache` once, before they compile anything.
"""
from __future__ import annotations

import os
import pathlib

import jax

REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]
# fixed, git-ignored path in the checkout: the cache's key includes the
# directory, so a path that moves between runs never hits
DEFAULT_CACHE_DIR = REPO_ROOT / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its
    directory.  An externally set ``JAX_COMPILATION_CACHE_DIR`` is left
    to JAX's own reading of it; otherwise the cache goes to
    :data:`DEFAULT_CACHE_DIR`."""
    external = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if external:
        return external
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)


def device_info() -> dict:
    """The device JAX runs on, as every result names it."""
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
