"""Blocked LocalSDCA as one Pallas kernel (the paper's compute hot spot).

Procedure P is a *sequential* scalar-update loop: pick coordinate i, dot
w.x_i, closed-form delta, rank-1 update of w. On an accelerator a naive
port round-trips HBM every step (one (d,) read + write per coordinate) and
is latency-bound. TPU adaptation:

  * grid = (K,): one program per worker block (Algorithm 1's "for all
    workers in parallel" IS the kernel grid).
  * the whole block X (m_b x d) and the private w copy are VMEM-resident
    for the program's lifetime; the H coordinate steps run inside one
    lax.fori_loop with w in vector registers and ZERO HBM traffic between
    steps.  Each step reads its row straight from the ref
    (``X_ref[pl.ds(i, 1), :]``).
  * everything indexed by a coordinate lives in SMEM, one (1, n) row per
    program: the draws and the step mask (n = H), and y, ||x||^2/(lam m)
    and alpha (n = m_b), plus the runtime lam*m scalar.  A step reads
    alpha_i as a scalar and writes alpha_i + delta back, so alpha is
    updated in place with no vector gather.
  * the sequential-dependence math of the paper is preserved exactly:
    what changes is only WHERE the iterates live (SMEM/VMEM/VREG vs HBM).
    In interpret mode the iterates equal ref.py's bit for bit wherever
    XLA's CPU backend emits the two row reductions alike (every engine
    shape the tests run).
  * coordinate choices are passed in as an (K, H) int32 array (computed
    with the standard jax PRNG outside) so kernel and oracle see identical
    randomness.

Memory per program (:func:`kernel_bytes`, double-buffered, f32): VMEM
2 * 4B * (pad8(m_b)*pad128(d) + 2*8*pad128(d)), SMEM 2 * 4B * (2H + 4m_b).
m_b=784, d=2000, H=784 => 13.1 MB of VMEM (under v5e's 16 MiB default
scoped limit) and 38 KB of SMEM.  Larger blocks raise the scoped VMEM
limit up to ``VMEM_LIMIT_BYTES``; a block beyond it, or beyond v5e's
1 MiB of SMEM, raises ``ValueError`` (streaming rows from HBM is not
implemented).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.dual import Loss

DEFAULT_SCOPED_VMEM_BYTES = 16 * 2**20   # v5e's default scoped VMEM limit
# v5e has 128 MiB of VMEM per core; leave headroom for Mosaic's own scratch
VMEM_LIMIT_BYTES = 100 * 2**20
SMEM_LIMIT_BYTES = 2**20                 # v5e's SMEM per core


def _round_up(x: int, k: int) -> int:
    return -(-x // k) * k


def kernel_bytes(m_b: int, d: int, H: int,
                 itemsize: int = 4) -> Tuple[int, int]:
    """(VMEM, SMEM) bytes one grid step holds, double-buffered: the tile-
    padded (m_b, d) X block and the w row in and out in VMEM; the draws,
    step mask, y, ||x||^2 and alpha in and out in SMEM."""
    vmem = 2 * itemsize * (_round_up(m_b, 8) * _round_up(d, 128)
                           + 2 * 8 * _round_up(d, 128))
    return vmem, 2 * 4 * (2 * H + 4 * m_b)


def _sdca_kernel(idx_ref, mask_ref, y_ref, xsq_ref, a_ref, lm_ref, X_ref,
                 w_ref, a_out_ref, dw_ref, *, loss: Loss, H: int):
    """One program = one leaf's H sequential coordinate maximizations.

    SMEM: idx_ref / mask_ref (1, H), y_ref / xsq_ref / a_ref / a_out_ref
    (1, m_b), lm_ref (1, 1).  VMEM: X_ref (m_b, d), w_ref / dw_ref (1, d).
    a_out_ref is the working copy of alpha and leaves as the new alpha."""
    m_b = a_ref.shape[1]
    lm = lm_ref[0, 0]

    def copy(j, c):
        a_out_ref[0, j] = a_ref[0, j]
        return c

    jax.lax.fori_loop(0, m_b, copy, 0)

    def body(h, w_c):
        i = idx_ref[0, h]
        x_i = X_ref[pl.ds(i, 1), :]                             # (1, d)
        a_i = a_out_ref[0, i]
        wx = jnp.sum(w_c * x_i, axis=1, keepdims=True)          # VPU dot
        # the step mask gates idle ticks / padded steps (1.0 is exact)
        dlt = loss.coord_delta(wx, a_i, y_ref[0, i],
                               xsq_ref[0, i]) * mask_ref[0, h]   # (1, 1)
        a_out_ref[0, i] = a_i + dlt[0, 0]
        return w_c + (dlt / lm) * x_i                           # rank-1

    w_end = jax.lax.fori_loop(0, H, body, w_ref[...])
    dw_ref[...] = w_end - w_ref[...]


def sdca_block_kernel(
    X: jax.Array,      # (K, m_b, d)
    y: jax.Array,      # (K, m_b)
    alpha: jax.Array,  # (K, m_b)
    w: jax.Array,      # (d,) shared, or (K, d) per-block (engine schedules)
    idx: jax.Array,    # (K, H)
    *,
    loss: Loss,
    lm,
    step_mask: jax.Array = None,  # optional (K, H) 0/1 per-step gating
    interpret: bool = True,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (delta_alpha (K, m_b), delta_w (K, d)).

    ``w`` may be the classic shared (d,) iterate (every program reads the
    same block) or a per-worker (K, d) batch -- the unified engine gives
    each leaf its own w replica between syncs.  ``step_mask`` zeroes the
    coordinate delta of masked steps, which is how the engine runs leaves
    with heterogeneous H (padded to H_max) and idle ticks inside one grid.
    ``lm`` (lambda * m_total) may be a Python float or a TRACED scalar --
    it enters the kernel as an SMEM operand, so one compiled kernel serves
    a whole regularization grid.

    Raises ``ValueError`` when one (m_b, d) block needs more VMEM than
    ``VMEM_LIMIT_BYTES`` or more SMEM than ``SMEM_LIMIT_BYTES``."""
    K, m_b, d = X.shape
    H = idx.shape[1]
    need, smem_need = kernel_bytes(m_b, d, H, X.dtype.itemsize)
    if need > VMEM_LIMIT_BYTES or smem_need > SMEM_LIMIT_BYTES:
        raise ValueError(
            f"sdca leaf block ({m_b} x {d}, H={H}, {X.dtype}) needs {need} "
            f"bytes of VMEM and {smem_need} bytes of SMEM, over the "
            f"kernel's limits ({VMEM_LIMIT_BYTES}, {SMEM_LIMIT_BYTES}); "
            "use smaller leaf blocks (more leaves)")
    dtype = X.dtype
    mask = (jnp.ones((K, H), dtype) if step_mask is None
            else step_mask.astype(dtype))
    lm_arr = jnp.full((1, 1), lm, dtype)
    if w.ndim == 2:
        w_in = w.reshape(K, 1, d)
        w_spec = pl.BlockSpec((None, 1, d), lambda k: (k, 0, 0))
    else:
        w_in = w.reshape(1, d)
        w_spec = pl.BlockSpec((1, d), lambda k: (0, 0))        # shared w
    smem = pltpu.MemorySpace.SMEM

    def row(n):              # one (1, n) SMEM row of a (K, 1, n) operand
        return pl.BlockSpec((None, 1, n), lambda k: (k, 0, 0),
                            memory_space=smem)

    a_end, dw = pl.pallas_call(
        functools.partial(_sdca_kernel, loss=loss, H=H),
        grid=(K,),
        in_specs=[row(H), row(H), row(m_b), row(m_b), row(m_b),
                  pl.BlockSpec(memory_space=smem),            # lm scalar
                  pl.BlockSpec((None, m_b, d), lambda k: (k, 0, 0)),
                  w_spec],
        out_specs=[row(m_b),
                   pl.BlockSpec((None, 1, d), lambda k: (k, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((K, 1, m_b), dtype),
                   jax.ShapeDtypeStruct((K, 1, d), dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=max(DEFAULT_SCOPED_VMEM_BYTES, need + 2**20)),
        interpret=interpret,
        name="sdca",
    )(idx.astype(jnp.int32).reshape(K, 1, H), mask.reshape(K, 1, H),
      y.reshape(K, 1, m_b), (jnp.sum(X * X, axis=2) / lm).reshape(K, 1, m_b),
      alpha.reshape(K, 1, m_b), lm_arr, X, w_in)
    return a_end.reshape(K, m_b) - alpha, dw.reshape(K, d)
