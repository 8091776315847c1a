"""Public jit'd wrapper for the flash-attention kernel.

Off-TPU the kernel executes in interpret mode (the Pallas body runs as
traced jnp); on a TPU backend it compiles with Mosaic.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax

from repro.compat import on_tpu
from repro.kernels.flash_attention.kernel import flash_attention_kernel


@functools.partial(jax.jit, static_argnames=("causal", "window", "scale",
                                             "block_q", "block_k",
                                             "seq_offset"))
def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None,
                    scale: Optional[float] = None, block_q: int = 128,
                    block_k: int = 128, seq_offset: int = 0):
    """Blocked online-softmax attention; see kernel.py for the TPU layout.

    q: (B, Sq, H, d); k/v: (B, Sk, KV, d) with H % KV == 0.
    """
    return flash_attention_kernel(
        q, k, v, causal=causal, window=window, scale=scale,
        block_q=block_q, block_k=block_k, seq_offset=seq_offset,
        interpret=not on_tpu())
