"""Public jit'd wrapper for the blocked-SDCA kernel: one outer CoCoA round
(all K workers' LocalSDCA in a single kernel launch + the 1/K averaging)."""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.compat import on_tpu
from repro.core.dual import Loss
from repro.kernels.sdca.kernel import sdca_block_kernel


@functools.partial(jax.jit, static_argnames=("loss", "num_steps", "m_total",
                                             "lam"))
def sdca_block_solve(
    X: jax.Array,        # (K, m_b, d) worker data blocks
    y: jax.Array,        # (K, m_b)
    alpha: jax.Array,    # (K, m_b)
    w: jax.Array,        # (d,)
    key: jax.Array,
    *,
    loss: Loss,
    lam: float,
    m_total: int,
    num_steps: int,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One synchronous outer round: every worker runs H = num_steps local
    coordinate steps from the shared w; returns the 1/K-averaged updates
    (new_alpha (K, m_b), new_w (d,), delta_w_per_worker (K, d))."""
    K, m_b, _ = X.shape
    lm = lam * m_total
    idx = jax.random.randint(key, (K, num_steps), 0, m_b)
    da, dw = sdca_block_kernel(X, y, alpha, w, idx, loss=loss, lm=lm,
                               interpret=not on_tpu())
    new_alpha = alpha + da / K
    new_w = w + jnp.sum(dw, axis=0) / K
    return new_alpha, new_w, dw
