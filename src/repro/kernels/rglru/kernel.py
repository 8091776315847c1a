"""RG-LRU recurrence as a Pallas kernel (Griffin's hot loop, TPU-adapted).

The recurrence h_t = a_t h_{t-1} + b_t is sequential in t but elementwise
in the channel dim. GPU implementations lean on warp-level scans; the TPU
adaptation instead:

  * grid = (B, W/block_w): each program owns a (S, block_w) channel strip
    resident in VMEM (lane-dim block_w a multiple of 128 for full VREG
    occupancy),
  * walks t in *chunks of T_CHUNK rows*, keeping the running h in VREGs;
    within a chunk the first-order recurrence is evaluated by log2(T_CHUNK)
    rounds of the classic parallel-prefix combine
    (a, b) ∘ (a', b') = (a·a', a'·b + b') realized with jnp.roll/where on
    the (T_CHUNK, block_w) tile — VPU work, no HBM traffic,
  * one VMEM read of (a, b) and one write of h per element total: the
    kernel is HBM-bandwidth-bound at ~3 streams, the roofline floor for
    this op (the jnp associative_scan oracle materializes O(log S) full
    intermediates instead).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

T_CHUNK = 256


def _chunk_prefix(a: jax.Array, b: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """In-chunk inclusive prefix of the recurrence along axis 0 via
    log-depth combines. a, b: (T, w) -> (A, Bc) with
    h_t = A_t * h_{-1} + Bc_t."""
    T = a.shape[0]
    k = 1
    while k < T:
        a_sh = jnp.roll(a, k, axis=0)
        b_sh = jnp.roll(b, k, axis=0)
        row = jax.lax.broadcasted_iota(jnp.int32, a.shape, 0)
        valid = row >= k
        a_new = jnp.where(valid, a * a_sh, a)
        b_new = jnp.where(valid, a * b_sh + b, b)
        a, b = a_new, b_new
        k *= 2
    return a, b


def _rglru_kernel(a_ref, b_ref, h0_ref, h_ref, hlast_ref, *, chunk: int):
    S = a_ref.shape[0]

    def body(c, h):                               # h: (1, w) running state
        rows = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
        A, Bc = _chunk_prefix(a_ref[rows, :].astype(jnp.float32),
                              b_ref[rows, :].astype(jnp.float32))
        h_chunk = A * h + Bc                      # (chunk, w)
        h_ref[rows, :] = h_chunk.astype(h_ref.dtype)
        return h_chunk[chunk - 1:]

    h = jax.lax.fori_loop(0, S // chunk, body,
                          h0_ref[...].astype(jnp.float32))
    hlast_ref[...] = h.astype(hlast_ref.dtype)


def rglru_scan_kernel(a: jax.Array, b: jax.Array, h0: jax.Array,
                      block_w: int = 128, interpret: bool = True
                      ) -> Tuple[jax.Array, jax.Array]:
    """a, b: (B, S, W) f32; h0: (B, W). Returns (h (B,S,W), h_last (B,W)).

    S must be a multiple of ``T_CHUNK`` (or shorter than it)."""
    B, S, W = a.shape
    block_w = min(block_w, W)
    chunk = min(T_CHUNK, S)
    if W % block_w or S % chunk:
        raise ValueError(f"rglru kernel needs W % block_w == 0 and "
                         f"S % {chunk} == 0; got W={W}, block_w={block_w}, "
                         f"S={S}")
    grid = (B, W // block_w)
    # h0 / h_last ride as (B, 1, W) so their blocks keep a full-size
    # second-minor dim (TPU block tiling)
    h, hlast = pl.pallas_call(
        functools.partial(_rglru_kernel, chunk=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, S, block_w), lambda i, j: (i, 0, j)),
            pl.BlockSpec((None, S, block_w), lambda i, j: (i, 0, j)),
            pl.BlockSpec((None, 1, block_w), lambda i, j: (i, 0, j)),
        ],
        out_specs=[
            pl.BlockSpec((None, S, block_w), lambda i, j: (i, 0, j)),
            pl.BlockSpec((None, 1, block_w), lambda i, j: (i, 0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, W), a.dtype),
            jax.ShapeDtypeStruct((B, 1, W), a.dtype),
        ],
        interpret=interpret,
    )(a, b, h0.reshape(B, 1, W))
    return h, hlast.reshape(B, W)
