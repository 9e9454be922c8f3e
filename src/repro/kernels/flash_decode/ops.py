"""Jitted wrappers for flash_decode (interpreted on the CPU)."""
from __future__ import annotations

import functools

import jax

from repro.kernels import interpret_mode
from repro.kernels.flash_decode.flash_decode import (
    flash_decode as _kernel, flash_decode_dynamic as _kernel_dyn)
from repro.kernels.flash_decode.ref import decode_ref


@functools.partial(jax.jit,
                   static_argnames=("t", "window", "local_block", "block_k"))
def flash_decode(q, k_cache, v_cache, *, t, window=None, local_block=None,
                 block_k=512):
    return _kernel(q, k_cache, v_cache, t=t, window=window,
                   local_block=local_block, block_k=block_k,
                   interpret=interpret_mode())


@functools.partial(jax.jit,
                   static_argnames=("window", "local_block", "block_k"))
def flash_decode_at(q, k_cache, v_cache, t, *, window=None, local_block=None,
                    block_k=512):
    """``flash_decode`` with a *traced* position ``t`` (scalar prefetch):
    one compiled executable serves the whole decode loop — the variant
    the serving executor and the model decode path use, since a static
    ``t`` would recompile every token."""
    return _kernel_dyn(q, k_cache, v_cache, t, window=window,
                       local_block=local_block, block_k=block_k,
                       interpret=interpret_mode())
