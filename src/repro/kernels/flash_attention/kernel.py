"""Blocked online-softmax attention (FlashAttention), TPU-adapted.

TPU mapping (vs. the CUDA original):
  * grid = (B, H, Sq/block_q): each program owns one MXU-aligned query
    block; K/V for that (batch, kv-head) live in VMEM for the program's
    lifetime (HBM->VMEM once, not once per query block pass as on SMEM-
    limited GPUs).  The kernel works on head-major (B, H, S, d) arrays so
    every block's last two dims are (rows, d) tiles; the wrapper
    transposes the model's (B, S, H, d) layout in and out.
  * the k-loop is a lax.fori_loop over MXU-aligned (block_k x d) slices
    with *data-dependent trip bounds*: causal masking prunes blocks above
    the diagonal, sliding windows prune blocks below `window` -- the
    pruning is on loop bounds (skipped compute), not just masks.
  * online softmax state (m, l, acc) stays in VREGs (f32), one rescale per
    k block; GQA is an index_map trick (q-head h reads kv-head h*KV//H),
    never a materialized repeat.

VMEM per program, double-buffered: 2 * (2*Sk*d + 2*block_q*d) * bytes.
Seq 2048 at d=128/bf16 is ~2.2 MiB, inside v5e's 16 MiB default scoped
VMEM; K/V blocks beyond that limit raise it explicitly (up to 100 MiB of
v5e's 128 MiB).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0**30
DEFAULT_SCOPED_VMEM_BYTES = 16 * 2**20   # v5e's default scoped VMEM limit
VMEM_LIMIT_BYTES = 100 * 2**20           # of v5e's 128 MiB per core


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, *, block_k: int, scale: float,
                 causal: bool, window: Optional[int], seq_offset: int):
    block_q, d = q_ref.shape
    Sk = k_ref.shape[0]
    qi = pl.program_id(2)
    q_start = qi * block_q + seq_offset  # absolute position of first query

    q = q_ref[...].astype(jnp.float32) * scale

    # trip bounds: causal prunes blocks past this q block's last row;
    # a window prunes blocks older than (first row - window).
    nk = Sk // block_k
    if causal:
        hi = jnp.minimum((q_start + block_q + block_k - 1) // block_k, nk)
    else:
        hi = nk
    if window is not None:
        lo = jnp.maximum((q_start - window + 1) // block_k, 0)
    else:
        lo = 0

    qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)

    def body(j, carry):
        m_i, l_i, acc = carry
        rows = pl.ds(pl.multiple_of(j * block_k, block_k), block_k)
        k_blk = k_ref[rows, :].astype(jnp.float32)
        v_blk = v_ref[rows, :].astype(jnp.float32)
        s = q @ k_blk.T  # (block_q, block_k) on the MXU
        kpos = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_k), 1)
        mask = jnp.ones_like(s, dtype=jnp.bool_)
        if causal:
            mask &= qpos >= kpos
        if window is not None:
            mask &= (qpos - kpos) < window
        s = jnp.where(mask, s, NEG_INF)

        m_new = jnp.maximum(m_i, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_i - m_new)
        l_new = l_i * alpha + jnp.sum(p, axis=1)
        acc = acc * alpha[:, None] + p @ v_blk
        return m_new, l_new, acc

    m0 = jnp.full((block_q,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q,), jnp.float32)
    acc0 = jnp.zeros((block_q, d), jnp.float32)
    m_i, l_i, acc = jax.lax.fori_loop(lo, hi, body, (m0, l0, acc0))

    o_ref[...] = (acc / (l_i[:, None] + 1e-30)).astype(o_ref.dtype)


def flash_attention_kernel(
    q: jax.Array, k: jax.Array, v: jax.Array, *,
    causal: bool = True, window: Optional[int] = None,
    scale: Optional[float] = None, block_q: int = 128, block_k: int = 128,
    seq_offset: int = 0, interpret: bool = True,
) -> jax.Array:
    """q: (B, Sq, H, d); k/v: (B, Sk, KV, d). Returns (B, Sq, H, d)."""
    B, Sq, H, D = q.shape
    _, Sk, KV, _ = k.shape
    assert H % KV == 0
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    assert Sq % block_q == 0 and Sk % block_k == 0
    s = scale if scale is not None else D**-0.5

    itemsize = q.dtype.itemsize
    need = 2 * itemsize * (2 * Sk * D + 2 * block_q * D)
    if need > VMEM_LIMIT_BYTES:
        raise ValueError(
            f"flash attention K/V blocks (Sk={Sk}, d={D}, {q.dtype}) need "
            f"{need} bytes of VMEM, over the kernel's {VMEM_LIMIT_BYTES}-"
            "byte limit")

    grid = (B, H, Sq // block_q)
    kernel = functools.partial(
        _attn_kernel, block_k=block_k, scale=s, causal=causal,
        window=window, seq_offset=seq_offset)
    qh, kh, vh = (t.transpose(0, 2, 1, 3) for t in (q, k, v))

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, None, block_q, D),
                         lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((None, None, Sk, D),
                         lambda b, h, i, KV=KV, H=H: (b, h * KV // H, 0, 0)),
            pl.BlockSpec((None, None, Sk, D),
                         lambda b, h, i, KV=KV, H=H: (b, h * KV // H, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, block_q, D),
                               lambda b, h, i: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct(qh.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=max(DEFAULT_SCOPED_VMEM_BYTES, need + 2**20)),
        interpret=interpret,
    )(qh, kh, vh)
    return out.transpose(0, 2, 1, 3)
