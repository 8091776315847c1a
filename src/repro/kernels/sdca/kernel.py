"""Blocked LocalSDCA as one Pallas kernel (the paper's compute hot spot).

Procedure P is a *sequential* scalar-update loop: pick coordinate i, dot
w.x_i, closed-form delta, rank-1 update of w.  Each step depends on the
last, so one leaf's solve is bound by the latency of a step, not by its
bytes.  TPU adaptation:

  * leaves are packed into sublanes: P = min(8, K) leaves share every
    vreg, leaf k in sublane k, so each vector op of a coordinate step does
    P leaves' work for one step's latency.  A program solves R = Q * P
    leaves, Q <= 4 such packs as one (R, .) array whose packs are
    independent chains the scheduler interleaves.  The grid is (K / R,
    H / Hc): leaf groups, then chunks of Hc steps (``"arbitrary"``: the
    chunks of one group run in order).  When K is not a multiple of P the
    last pack is padded with slots that draw no coordinate (step mask 0,
    outputs dropped).
  * a packed step reads alpha_i, y_i and ||x_i||^2/(lam m) from (R, m_b)
    vector blocks by a one-hot lane select and writes alpha_i + delta back
    by the same select, so alpha never leaves the vector unit (no scalar
    round trip on the dependence chain) and a coordinate drawn twice sees
    its updated value.  The drawn index and the step mask arrive as one
    (R, 2) tile a step.  The select costs m_b / 128 vregs a step whatever
    P, so packing pays only while P leaves' share of a step's latency
    outweighs it: :func:`leaf_packing` packs where a packed step is
    cheaper per leaf than a single leaf's (measured costs, v5e), else
    runs one leaf a program (R = 1: K = 1, the mesh backend's one leaf a
    chip, and a few leaves of many rows).
  * a single leaf keeps alpha and y as (m_b / 1024, 8, 128) tiles: the
    step reads its index from SMEM, loads the one tile (one vreg) that
    holds coordinate i and selects within it, so a step costs the same
    whatever m_b.  ||x||^2/(lam m) is an SMEM row read by index.
  * rows are gathered before the kernel, inside the caller's scope, in
    step order: packed, into a leaf-interleaved (K / R, H, R, d) array
    whose step h is one aligned (R, d) tile holding row i_{k,h} of leaf k
    in row k; a single leaf, into (K, H, d).  The kernel streams them in
    chunks of Hc steps, so VMEM holds a chunk of rows, never a whole leaf
    block.  Where the gathered rows would take more than
    ``ROW_GATHER_BYTES`` of HBM, the H steps are split into pieces, one
    ``pallas_call`` each in a ``lax.scan`` that carries alpha and w.
  * w and alpha are carried across the chunks of a group in the output
    blocks.
  * the sequential-dependence math of the paper is preserved exactly:
    every leaf runs the same coordinates in the same order with the same
    f32 arithmetic (``||x||^2`` is ref.py's full-block pass); what changes
    is only WHERE the iterates live.  In interpret mode the iterates equal
    ref.py's bit for bit wherever XLA's CPU backend emits the row
    reductions alike (every shape the tests run).
  * coordinate choices are passed in as an (K, H) int32 array (computed
    with the standard jax PRNG outside) so kernel and oracle see identical
    randomness.

Memory (:func:`kernel_bytes`, double-buffered, f32).  VMEM, packed: 2 * 4B
* (Hc*pad8(R)*(pad128(d) + 128) + pad8(R)*(4*pad128(m_b) + 2*pad128(d)));
a single leaf: 2 * 4B * (Hc*pad128(d) + 3*1024*(m_b//1024 + 1) +
16*pad128(d)), and SMEM 2 * 4B * (m_b + 2*Hc).  Hc is chosen so one chunk
of rows is at most ``ROW_CHUNK_BYTES``.  K=512, m_b=784, d=2000, H=784 =>
P=8, R=32, Hc=56 (14 chunks), 33.2 MB of VMEM.  The row stream lifts the
old whole-block cap on m_b * d; blocks over ``VMEM_LIMIT_BYTES`` or
``SMEM_LIMIT_BYTES`` raise ``ValueError``.  HBM: the gathered rows,
pad8(R) / R * K * H * pad128(d) * 4B, at most ``ROW_GATHER_BYTES`` a call
(under ``vmap``, that much per member).
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
LEAF_PACK = 8                            # f32 sublanes of a vreg
PACKS_PER_PROGRAM = 4                    # most packs one program solves
# a packed step costs about PACK_STEP_NS, plus PACK_TILE_NS for each 128
# lanes of m_b its one-hot selects cover, and serves P leaves; a single
# leaf's step costs about LEAF_STEP_NS (v5e, d = 2,000; PERF.md)
PACK_STEP_NS, PACK_TILE_NS, LEAF_STEP_NS = 400, 8, 190
ROW_CHUNK_BYTES = 16 * 2**20             # one buffer of streamed rows
ROW_GATHER_BYTES = 512 * 2**20           # the rows gathered for one call
TILE = 8 * 128                           # f32 words of a vreg
MAX_CHUNK_STEPS = 1024                   # SMEM steps of a single-leaf chunk


def _round_up(x: int, k: int) -> int:
    return -(-x // k) * k


def _split(n: int, parts: int) -> int:
    """The fewest parts, at least ``parts``, that divide n evenly (within
    twice ``parts``), else ``parts``."""
    parts = max(1, parts)
    return next((c for c in range(parts, 2 * parts) if n % c == 0), parts)


def leaf_packing(K: int, m_b: int) -> Tuple[int, int, int]:
    """(P, R, slots): leaves one vreg packs into its sublanes, leaves one
    program solves (a whole number of packs dividing the packs of K), and
    the leaf slots of the grid (K rounded up to whole packs).  Leaves are
    packed only where a packed step is cheaper per leaf than a single
    leaf's (R = 1, one leaf a program)."""
    P = min(LEAF_PACK, K)
    if PACK_STEP_NS + PACK_TILE_NS * -(-m_b // 128) >= LEAF_STEP_NS * P:
        return 1, 1, K
    packs = -(-K // P)
    Q = next(q for q in range(PACKS_PER_PROGRAM, 0, -1) if packs % q == 0)
    return P, Q * P, packs * P


def leaf_stats(K: int, m_b: int) -> dict:
    """How K leaves of m_b rows are packed, from shapes: ``leaf_pack``, the
    leaves one vreg holds, one a sublane, and ``leaf_slots_padded``, the
    share of the grid's leaf slots that are padding."""
    P, _, slots = leaf_packing(K, m_b)
    return {"leaf_pack": P, "leaf_slots_padded": (slots - K) / slots}


def step_plan(K: int, m_b: int, d: int, H: int, itemsize: int = 4
              ) -> Tuple[int, int, int]:
    """(pieces, Hc, C): the calls the H steps are split into, so that the
    rows gathered for one call take at most ``ROW_GATHER_BYTES``; the steps
    a chunk of streamed rows holds (at most ``ROW_CHUNK_BYTES`` a buffer);
    and the chunks of a call.  Even splits are preferred; pieces * C * Hc
    >= H, and the padded steps are masked."""
    _, R, slots = leaf_packing(K, m_b)
    row = _round_up(d, 128) * itemsize
    pieces = _split(H, -(-slots * H * row // ROW_GATHER_BYTES))
    Hp = -(-H // pieces)
    if R == 1:       # (Hc, d) row blocks: Hc a multiple of 8 unless whole
        C = _split(Hp, -(-Hp // min(MAX_CHUNK_STEPS,
                                    max(8, ROW_CHUNK_BYTES // row))))
        Hc = -(-Hp // C)
        return pieces, (_round_up(Hc, 8) if C > 1 else Hc), C
    C = _split(Hp, -(-Hp // max(1, ROW_CHUNK_BYTES // (_round_up(R, 8) * row))))
    return pieces, -(-Hp // C), C


def kernel_bytes(K: int, m_b: int, d: int, H: int,
                 itemsize: int = 4) -> Tuple[int, int]:
    """(VMEM, SMEM) bytes one grid step holds, double-buffered.  Packed: a
    chunk of rows and its (R, 2) step tiles, the (R, m_b) alpha, y and
    ||x||^2 blocks, alpha out, and the (R, d) w blocks in and out.  A
    single leaf: a chunk of rows, the alpha and y tiles, alpha out and the
    w rows in VMEM; ||x||^2 and the chunk's draws and step mask in SMEM."""
    _, R, _ = leaf_packing(K, m_b)
    _, Hc, _ = step_plan(K, m_b, d, H, itemsize)
    dp = _round_up(d, 128)
    if R == 1:
        return (2 * itemsize * (Hc * dp + 3 * TILE * (m_b // TILE + 1)
                                + 2 * 8 * dp),
                2 * 4 * (m_b + 2 * Hc))
    sub = _round_up(R, 8)
    return 2 * itemsize * (Hc * sub * (dp + 128)
                           + sub * (4 * _round_up(m_b, 128) + 2 * dp)), 0


def _carry_in(a_ref, w_ref, a_out_ref, w_out_ref):
    """The first chunk of a group starts from the input alpha and w; later
    chunks continue from the output blocks."""
    @pl.when(pl.program_id(1) == 0)
    def _():
        a_out_ref[...] = a_ref[...]
        w_out_ref[...] = w_ref[...]


def _pick(hit, v):           # v at the one-hot lanes, summed along lanes
    return jnp.sum(jnp.where(hit, v, 0.0), axis=1, keepdims=True)


def _packed_kernel(lm_ref, a_ref, w_ref, y_ref, xsq_ref, rows_ref, side_ref,
                   a_out_ref, w_out_ref, *, loss: Loss):
    """One program = one chunk of Hc coordinate steps of R packed leaves.

    SMEM: lm_ref (1, 1).  VMEM: a_ref / a_out_ref, y_ref, xsq_ref (R, m_b),
    w_ref / w_out_ref (R, d), rows_ref (Hc, R, d), side_ref (Hc, R, 2)
    holding (index, step mask) per leaf.  The out blocks carry alpha and w
    from chunk to chunk and leave as the new alpha and w."""
    _carry_in(a_ref, w_ref, a_out_ref, w_out_ref)
    lm = lm_ref[0, 0]
    lane = jax.lax.broadcasted_iota(jnp.int32, a_out_ref.shape, 1)
    yb, xsqb = y_ref[...], xsq_ref[...]

    def body(h, carry):
        a, w = carry
        x = rows_ref[h]                                          # (R, d)
        s = side_ref[h]                                          # (R, 2)
        hit = lane == s[:, 0:1].astype(jnp.int32)                # one-hot
        wx = jnp.sum(w * x, axis=1, keepdims=True)               # VPU dot
        # the step mask gates idle ticks / padded steps (1.0 is exact)
        dlt = loss.coord_delta(wx, _pick(hit, a), _pick(hit, yb),
                               _pick(hit, xsqb)) * s[:, 1:2].astype(x.dtype)
        return jnp.where(hit, a + dlt, a), w + (dlt / lm) * x    # rank-1

    a, w = jax.lax.fori_loop(0, rows_ref.shape[0], body,
                             (a_out_ref[...], w_out_ref[...]))
    a_out_ref[...] = a
    w_out_ref[...] = w


def _one_leaf_kernel(lm_ref, ix_ref, mk_ref, xsq_ref, a_ref, w_ref, y_ref,
                     rows_ref, a_out_ref, w_out_ref, *, loss: Loss):
    """One program = one chunk of Hc coordinate steps of a single leaf.

    SMEM: lm_ref (1, 1), ix_ref / mk_ref (1, Hc) the drawn indices and step
    mask, xsq_ref (1, m_b) ||x||^2/(lam m).  VMEM: a_ref / a_out_ref, y_ref
    (T, 8, 128) tiles (entry i at [i // 1024, i // 128 % 8, i % 128]),
    w_ref / w_out_ref (1, d), rows_ref (Hc, d).  A step touches only the
    tile that holds i."""
    _carry_in(a_ref, w_ref, a_out_ref, w_out_ref)
    lm = lm_ref[0, 0]
    word = (jax.lax.broadcasted_iota(jnp.int32, (8, 128), 0) * 128
            + jax.lax.broadcasted_iota(jnp.int32, (8, 128), 1))

    def pick(hit, v):        # (1, 1): v at the one hit of the tile (exact)
        return jnp.sum(_pick(hit, v), axis=0, keepdims=True)

    def body(h, w):
        i = ix_ref[0, h]
        t = i // TILE
        hit = word == i % TILE
        a_t = a_out_ref[t]                                       # (8, 128)
        x = rows_ref[pl.ds(h, 1), :]                             # (1, d)
        wx = jnp.sum(w * x, axis=1, keepdims=True)               # VPU dot
        # a padded step's index m_b is a pad word of the tiles, but past
        # the end of the SMEM row
        xsq_i = xsq_ref[0, jnp.minimum(i, xsq_ref.shape[1] - 1)]
        dlt = loss.coord_delta(wx, pick(hit, a_t), pick(hit, y_ref[t]),
                               xsq_i) * mk_ref[0, h]
        a_out_ref[t] = jnp.where(hit, a_t + dlt, a_t)
        return w + (dlt / lm) * x                                # rank-1

    w_out_ref[...] = jax.lax.fori_loop(0, rows_ref.shape[0], body,
                                       w_out_ref[...])


def _packed_call(X, lm_arr, a, w, y, xsq, ix, mk, *, R, Hc, C, loss,
                 vmem, interpret):
    """One ``pallas_call`` over packed leaves: ``a``, ``w``, ``y``, ``xsq``
    hold every leaf slot, ``ix`` / ``mk`` (slots, C * Hc) the draws and
    step mask.  Returns the new (a, w)."""
    K, m_b, d = X.shape
    slots = a.shape[0]
    G = slots // R

    def interleave(v):       # (slots, C Hc) -> (G, C Hc, R): step tiles
        return v.reshape(G, R, C * Hc).transpose(0, 2, 1)

    ixt = interleave(ix)
    leaf = jnp.minimum(jnp.arange(slots), K - 1).reshape(G, 1, R)
    rows = X.at[leaf + jnp.zeros_like(ixt), jnp.minimum(ixt, m_b - 1)].get(
        mode="promise_in_bounds")                              # (G,CHc,R,d)
    side = jnp.stack([ixt.astype(jnp.float32),
                      interleave(mk).astype(jnp.float32)],
                     axis=-1)                                  # (G,CHc,R,2)

    def block(n):            # a group's (R, n) rows, the same every chunk
        return pl.BlockSpec((R, n), lambda g, c: (g, 0))

    def chunk(n):            # a group's chunk of Hc (R, n) step tiles
        return pl.BlockSpec((None, Hc, R, n), lambda g, c: (g, c, 0, 0))

    return pl.pallas_call(
        functools.partial(_packed_kernel, loss=loss),
        grid=(G, C),
        in_specs=[pl.BlockSpec(memory_space=pltpu.MemorySpace.SMEM),
                  block(m_b), block(d), block(m_b), block(m_b), chunk(d),
                  chunk(2)],
        out_specs=[block(m_b), block(d)],
        out_shape=[jax.ShapeDtypeStruct(a.shape, a.dtype),
                   jax.ShapeDtypeStruct(w.shape, w.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=vmem),
        interpret=interpret,
        name="sdca",
    )(lm_arr, a, w, y, xsq, rows, side)


def _one_leaf_call(X, lm_arr, a, w, y, xsq, ix, mk, *, Hc, C, loss, vmem,
                   interpret):
    """One ``pallas_call`` with one leaf a program (arguments as
    :func:`_packed_call`, with slots == K).  ``xsq`` stays as computed, in
    SMEM: padded to whole tiles, XLA may fuse the pad into the norm pass
    and round it differently from the reference."""
    K, m_b, d = X.shape
    T = m_b // TILE + 1      # holds index m_b, the padded steps' draw

    def tiles(v):            # (K, m_b) -> (K, T, 8, 128)
        return jnp.pad(v, ((0, 0), (0, T * TILE - m_b))).reshape(
            K, T, 8, 128)

    rows = X.at[jnp.arange(K)[:, None], jnp.minimum(ix, m_b - 1)].get(
        mode="promise_in_bounds")                              # (K, CHc, d)
    smem = pltpu.MemorySpace.SMEM
    steps = pl.BlockSpec((None, None, 1, Hc), lambda k, c: (k, c, 0, 0),
                         memory_space=smem)

    def spec(shape, memory_space=None):  # a leaf's block, the same every chunk
        return pl.BlockSpec((None,) + shape, lambda k, c: (k,) + (0,) * len(
            shape), memory_space=memory_space)

    def stream(n):           # a leaf's chunk of Hc steps
        return pl.BlockSpec((None, Hc, n), lambda k, c: (k, c, 0))

    a_t, w_t = pl.pallas_call(
        functools.partial(_one_leaf_kernel, loss=loss),
        grid=(K, C),
        in_specs=[pl.BlockSpec(memory_space=smem), steps, steps,
                  spec((1, m_b), smem), spec((T, 8, 128)), spec((1, d)),
                  spec((T, 8, 128)), stream(d)],
        out_specs=[spec((T, 8, 128)), spec((1, d))],
        out_shape=[jax.ShapeDtypeStruct((K, T, 8, 128), a.dtype),
                   jax.ShapeDtypeStruct((K, 1, d), w.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=vmem),
        interpret=interpret,
        name="sdca",
    )(lm_arr, ix.reshape(K, C, 1, Hc), mk.reshape(K, C, 1, Hc), xsq[:, None],
      tiles(a), w[:, None], tiles(y), rows)
    return a_t.reshape(K, T * TILE)[:, :m_b], w_t.reshape(K, d)


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

    ``w`` may be the classic shared (d,) iterate (every leaf starts from
    it) or a per-worker (K, d) batch -- the unified engine gives each leaf
    its own w replica between syncs.  ``step_mask`` zeroes the coordinate
    delta of masked steps, which is how the engine runs leaves with
    heterogeneous H (padded to H_max) and idle ticks inside one grid.
    ``lm`` (lambda * m_total) may be a Python float or a TRACED scalar --
    it enters the kernel as an SMEM operand, so one compiled kernel serves
    a whole regularization grid.  The row gather, ||x||^2 and the step
    tiles are computed here, in the caller's scope, around the
    ``pallas_call`` (one, or one in a scan over pieces of the steps).

    Raises ``ValueError`` when a program's blocks need more VMEM than
    ``VMEM_LIMIT_BYTES`` or more SMEM than ``SMEM_LIMIT_BYTES``."""
    K, m_b, d = X.shape
    H = idx.shape[1]
    dtype = X.dtype
    need, smem_need = kernel_bytes(K, m_b, d, H, dtype.itemsize)
    if need > VMEM_LIMIT_BYTES or smem_need > SMEM_LIMIT_BYTES:
        raise ValueError(
            f"sdca leaf block ({m_b} x {d}, H={H}, {dtype}) needs {need} "
            f"bytes of VMEM and {smem_need} bytes of SMEM, over the "
            f"kernel's limits ({VMEM_LIMIT_BYTES}, {SMEM_LIMIT_BYTES}); "
            "use smaller leaf blocks (more leaves)")
    _, R, slots = leaf_packing(K, m_b)
    pieces, Hc, C = step_plan(K, m_b, d, H, dtype.itemsize)
    mask = (jnp.ones((K, H), dtype) if step_mask is None
            else step_mask.astype(dtype))
    # padded slots (leaves >= K, steps >= H) hold the index m_b, which no
    # coordinate matches, and step mask 0; they gather the real row m_b - 1
    # of leaf K - 1, so every delta they compute is finite x 0
    pad = ((0, slots - K), (0, pieces * C * Hc - H))

    def split(v, fill):      # (K, H) -> (pieces, slots, C Hc)
        return jnp.pad(v, pad, constant_values=fill).reshape(
            slots, pieces, C * Hc).transpose(1, 0, 2)

    def leaves(v):           # (K, n) -> (slots, n)
        return jnp.pad(v, ((0, slots - K), (0, 0)))

    kw = dict(Hc=Hc, C=C, loss=loss, interpret=interpret,
              vmem=max(DEFAULT_SCOPED_VMEM_BYTES, need + 2**20))
    call = (functools.partial(_one_leaf_call, **kw) if R == 1
            else functools.partial(_packed_call, R=R, **kw))
    lm_arr = jnp.full((1, 1), lm, dtype)
    yk, xsq = leaves(y), leaves(jnp.sum(X * X, axis=2) / lm)

    def solve(carry, piece):
        return call(X, lm_arr, *carry, yk, xsq, *piece), None

    ix, mk = split(idx.astype(jnp.int32), m_b), split(mask, 0)
    carry = (leaves(alpha),
             leaves(jnp.broadcast_to(w, (K, d)) if w.ndim == 1 else w))
    if pieces == 1:
        carry, _ = solve(carry, (ix[0], mk[0]))
    else:
        carry, _ = jax.lax.scan(solve, carry, (ix, mk))
    a_end, w_end = carry
    return a_end[:K] - alpha, w_end[:K] - w
