"""Pallas TPU kernels of the served path (flash attention prefill,
flash decode, WKV6), each with a jitted ``ops`` wrapper and a jnp
``ref`` oracle."""
from __future__ import annotations

import jax


def interpret_mode() -> bool:
    """Whether the kernels run in Pallas interpret mode: compiled on a
    TPU, interpreted on the CPU (correctness only).  Any other backend
    is an error, never a silent fallback."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(f"Pallas kernels run on a TPU, or interpreted on "
                       f"the CPU; the backend is {backend!r}")
