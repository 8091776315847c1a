"""jit'd wrapper for the RG-LRU scan kernel (interpret mode off-TPU)."""
from __future__ import annotations

import functools
from typing import Tuple

import jax

from repro.compat import on_tpu
from repro.kernels.rglru.kernel import rglru_scan_kernel


@functools.partial(jax.jit, static_argnames=("block_w",))
def rglru_scan(a, b, h0, *, block_w: int = 128
               ) -> Tuple[jax.Array, jax.Array]:
    """Diagonal linear recurrence h_t = a_t h_{t-1} + b_t over (B, S, W).
    Returns (all states, final state)."""
    return rglru_scan_kernel(a, b, h0, block_w=block_w,
                             interpret=not on_tpu())
