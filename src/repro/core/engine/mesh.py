"""Mesh backend: run a level-homogeneous :class:`TreePlan` as a sharded
device program (``shard_map`` + ``lax`` collectives), with the Pallas
blocked-SDCA kernel at the leaves.

The mesh axes are one *admissible grouping* of the plan: internal depth d
of the tree maps onto mesh axis ``axes[L-1-d]`` (axes listed innermost
first), so every depth-d sync group is exactly the set of devices sharing
coordinates on the axes above.  Because mesh plans are level-homogeneous
(every node at a depth shares (rounds, fan-out) and all leaves are
congruent), the flat tick schedule factors back into nested ``fori_loop``s
with one collective per sync -- the natural lowering on a device mesh, and
bit-compatible with the host backend because both consume the same
per-solve key plan (the legacy-RNG replay from ``engine.plan``).

Like the host backend, the compiled program is memoized on
(plan fingerprint, mesh, axes, loss, flags) and takes the warm-start
state ``(alpha0, w0)`` -- and the regularization scalar ``lm`` = lambda*m
-- as runtime inputs, so ``repro.api.Session`` can run it in
per-root-round chunks without retracing, and a lambda grid shares one
device program.

Async / stale sync: the program also takes the ``(n, S)`` leaf-major
participation mask (see ``engine.plan``).  Each depth's sync weights every
*leaf* shard by ``p / prod(K_d..K_L-1)`` and psums over ALL axes at that
depth and deeper (so partially-present subtrees renormalize exactly like
the host backend), carrying explicit per-depth snapshots and the
group-coherent server ``w`` (``srvW``) that bounded-staleness re-joins fold
into.  An all-ones mask reduces every gate to the synchronous program.

Runtime schedules: the program also takes the ``(n, S, h_max)`` leaf-major
step mask (see ``engine.plan.steps_for_h``).  Every solve slot draws the
full H-capacity coordinate stream; the mask gates the trailing deltas in
the Pallas kernel (its ``step_mask`` operand), so heterogeneous / replanned
H is a runtime input of the one cached device program.  All-ones step
masks multiply the deltas by exactly 1.0 -- bit-identical to the static-H
program.

Edge compression (tentpole): a plan whose per-depth compression specs are
non-trivial routes every sync's ``w``-delta through the edge's
(quantize + dequantize) roundtrip with an error-feedback residual carried
in the program state, exactly like the host backend -- mesh plans need ONE
spec per depth (level-homogeneous compression).  ``compression=None``
plans trace the pre-compression program unchanged.

Sync lowering (``sync=``):

* ``"psum"`` (default): replicated server state -- every device carries
  the full per-depth ``snapW``/``srvW`` ``d``-vectors and each sync is one
  ``psum``.  Bit-identical to the host backend.
* ``"reduce_scatter"``: the big-``d`` path.  Per-depth server state lives
  SHARDED over the depth's sync group (each device owns a
  ``ceil(d / G_d)`` chunk, ``G_d`` the group's device count): a sync is
  ``psum_scatter`` of the (optionally compressed) local delta into the
  shard, then one ``all_gather`` to rebuild the full ``w`` the leaf solve
  needs.  Chunk placement is whatever tiled ``psum_scatter``/``all_gather``
  agree on, so the lowering never assumes (or computes) a device-ordering
  convention.  Per-device persistent
  server state drops from ``2 L d`` to ``2 sum_d ceil(d/G_d)``
  (:func:`mesh_state_floats`), which is what lets ``d >> VMEM`` problems
  run.  Requires full participation (the sharded snapshot reconstruction
  assumes group-coherent server state); numerically equivalent to
  ``"psum"`` up to float reassociation of the sum.
"""
from __future__ import annotations

import functools
import math
from collections import OrderedDict
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from repro.compat import on_tpu
from repro.core import compression as comp_mod
from repro.core.dual import Loss
from repro.core.engine.plan import (
    TreePlan, full_participation, full_steps, key_plan)
from repro.core.tree import TreeNode

# the tree programs psum over axis subsets (per-level averaging), which
# shard_map's replication check cannot express
shard_map = functools.partial(jax.shard_map, check_vma=False)


def make_mesh(shape: Sequence[int], axes: Sequence[str],
              devices=None) -> Mesh:
    """``jax.make_mesh`` with ``Auto`` axis types.  The engine places data
    with explicit ``NamedSharding``s and ``shard_map`` and indexes its
    outputs numpy-style, which ``Explicit`` axes (``jax.make_mesh``'s
    default) reject on a sharded dimension."""
    return jax.make_mesh(tuple(shape), tuple(axes), devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def auto_axes(mesh: Mesh) -> Mesh:
    """``mesh`` itself when every axis is ``Auto``, else the same devices
    and names with ``Auto`` axes."""
    if all(t == AxisType.Auto for t in mesh.axis_types):
        return mesh
    return Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * mesh.devices.ndim)

Array = jax.Array

_MESH_EXEC_CACHE: OrderedDict = OrderedDict()
_MESH_EXEC_CACHE_MAX = 16
# hit/miss counters + named key fields + bounded miss log, mirroring
# engine.host: the mesh column of executor_cache_stats()["by_backend"]
# (mesh rebuilds used to be invisible to cache-stats assertions)
MESH_KEY_FIELDS = ("plan_fingerprint", "loss", "gamma", "axes", "mesh",
                   "use_kernel", "carry_state", "sync", "batched",
                   "accelerated")
_MESH_CACHE_STATS = {"hits": 0, "misses": 0}
_MISS_LOG: list = []
_MISS_LOG_MAX = 64

SYNC_MODES = ("psum", "reduce_scatter")


def mesh_executor_cache_stats() -> dict:
    """Mesh executor-cache counters: {hits, misses, size}."""
    return dict(_MESH_CACHE_STATS, size=len(_MESH_EXEC_CACHE))


def mesh_executor_cache_keys() -> list:
    """Current mesh-cache keys as named dicts (see ``MESH_KEY_FIELDS``)."""
    from repro.core.engine.host import _named_key
    return [_named_key(MESH_KEY_FIELDS, k) for k in _MESH_EXEC_CACHE]


def kernel_stats(plan: TreePlan, use_kernel: bool) -> dict:
    """The ``Session.run`` span stats of the leaf solve: with the kernel,
    how ``sdca`` packs each device's one leaf
    (:func:`repro.kernels.sdca.kernel.leaf_stats`); empty where the leaf
    runs the XLA reference."""
    if not use_kernel:
        return {}
    from repro.kernels.sdca.kernel import leaf_stats
    return leaf_stats(1, plan.m_b)


def _check_plan_mesh(plan: TreePlan, mesh: Mesh, axes: Sequence[str]):
    assert plan.levels is not None, (
        "the mesh backend needs a level-homogeneous plan (balanced tree, "
        "uniform per-depth rounds); use the host backend otherwise")
    assert plan.weighting == "uniform", (
        "mesh lowering uses per-level psum/K averaging (uniform weights)")
    L = len(axes)
    assert plan.depth == L, (plan.depth, L)
    sizes = [dict(mesh.shape)[a] for a in axes]
    for d in range(L):
        assert plan.levels[d].group_size == sizes[L - 1 - d], (
            f"depth {d} fan-out {plan.levels[d].group_size} != mesh axis "
            f"{axes[L - 1 - d]} size {sizes[L - 1 - d]}")
    assert int(plan.leaf_sizes.min()) == plan.m_b, \
        "mesh backend needs equal blocks"


def _comp_specs(plan: TreePlan):
    """The per-depth (kind, frac) compression spec of a mesh-lowerable
    plan; raises when a depth mixes specs across edges (mesh lowering is
    one collective per depth, so the spec must be level-uniform)."""
    specs = []
    for dd in range(plan.depth):
        pairs = {(int(k), float(f)) for k, f in
                 zip(plan.compress_kind[dd], plan.compress_frac[dd],
                     strict=True)}
        if len(pairs) != 1:
            raise ValueError(
                f"mesh backend needs ONE compression spec per depth; depth "
                f"{dd} mixes "
                f"{sorted(comp_mod.spec_name(*p) for p in pairs)}")
        specs.append(next(iter(pairs)))
    return specs


def mesh_state_floats(plan: TreePlan, d_feat: int, *,
                      sync: str = "psum") -> int:
    """Per-device PERSISTENT carry floats of the mesh program (the state a
    chunked/carry_state session threads: blocked alpha, the ``w`` replica,
    per-depth snapshots/servers, error-feedback residuals).  The
    ``reduce_scatter`` lowering keeps per-depth server state sharded over
    the depth's sync group, which is its big-``d`` memory win."""
    if sync not in SYNC_MODES:
        raise ValueError(f"sync must be one of {SYNC_MODES}, got {sync!r}")
    L, m_b = plan.depth, plan.m_b
    ks = [plan.levels[d].group_size for d in range(L)]
    specs = _comp_specs(plan)
    n_res = sum(1 for k, _ in specs if k != comp_mod.KIND_NONE)
    base = m_b + d_feat + L * m_b + n_res * d_feat
    if sync == "psum":
        return base + 2 * L * d_feat          # snapW + srvW, replicated
    shard = sum(-(-d_feat // math.prod(ks[d:])) for d in range(L))
    return base + shard                       # sharded server (snap == srv)


def get_mesh_executor(
    plan: TreePlan,
    mesh: Mesh,
    *,
    axes: Sequence[str],
    loss: Loss,
    use_kernel: bool = True,
    carry_state: bool = False,
    sync: str = "psum",
    batched: bool = False,
    accelerated: bool = False,
):
    """Build (or fetch from cache) the jitted ``shard_map`` program for
    ``plan`` on ``mesh``.

    Signature: ``fn(Xs, ys, a0, w0, kys, part, steps, lm) ->
    (alpha_blocked, w_rows)`` with ``Xs (n, m_b, d)``, ``a0 (n, m_b)``
    sharded over the (reversed) axes, ``w0 (d,)`` replicated, ``kys
    (n, S, 2)`` the leaf-major per-solve key plan, ``part (n, S)`` the
    leaf-major participation mask (all-ones for the synchronous schedule),
    ``steps (n, S, h_max)`` the leaf-major runtime step mask (all-ones for
    the static-H schedule), and ``lm`` the replicated RUNTIME
    regularization scalar lambda*m
    (:func:`repro.core.engine.host.regularizer_scale`) -- neither lambda
    nor the H schedule is a cache key, so regularization AND local-H grids
    reuse one device program.

    ``sync`` picks the collective lowering: ``"psum"`` (replicated server
    state, bit-identical to the host backend) or ``"reduce_scatter"``
    (sharded server state for big ``d``; requires full participation --
    see the module docstring).

    ``carry_state=True`` returns a :class:`~repro.core.engine.host.
    StateExecutor` threading the full per-leaf state across chunk
    invocations as ONE opaque pytree: ``step(Xs, ys, state, kys, part,
    steps, lm) -> state`` -- the complete carry async and compressed
    sessions need (the flat ``(alpha, w)`` pair drops absent leaves'
    divergent replicas and the error-feedback residuals).

    ``batched=True`` returns the fused-sweep flavor: the per-shard program
    is ``jax.vmap``-ped over a leading config axis B INSIDE the
    ``shard_map`` (collectives batch elementwise under vmap, so every
    member's psum / tiled ``psum_scatter`` / ``all_gather`` is bitwise the
    standalone one).  Batched operands gain a leading B over the leaf-
    sharded dimension -- ``a0 (B, n, m_b)``, ``w0 (B, d)``, ``kys
    (B, n, S, 2)``, ``steps (B, n, S, h_max)``, ``lm (B,)`` -- while
    ``Xs``/``ys``/``part`` stay shared.  Composes with ``carry_state``
    (every state leaf carries the leading B axis) and both sync modes.

    ``accelerated=True`` is the ``sdca_acc`` flavor (see
    :func:`repro.core.engine.host.get_host_executor`): one trailing
    runtime scalar ``acceleration`` (shared across a batch), per-depth
    momentum anchors in the carry, and the server combine extrapolates
    both sides of the primal-dual pair; ``acceleration == 0`` is
    bit-identical to the plain program."""
    mesh = auto_axes(mesh)
    _check_plan_mesh(plan, mesh, axes)
    if sync not in SYNC_MODES:
        raise ValueError(f"sync must be one of {SYNC_MODES}, got {sync!r}")
    cache_key = (plan.fingerprint, loss.name, loss.gamma,
                 tuple(axes), mesh, bool(use_kernel), bool(carry_state),
                 sync, bool(batched), bool(accelerated))
    fn = _MESH_EXEC_CACHE.get(cache_key)
    if fn is not None:
        _MESH_CACHE_STATS["hits"] += 1
        _MESH_EXEC_CACHE.move_to_end(cache_key)
        return fn

    L = len(axes)
    m_b = plan.m_b
    rounds = [plan.levels[d].rounds for d in range(L)]
    ks = [plan.levels[d].group_size for d in range(L)]
    axis_of_depth = [axes[L - 1 - d] for d in range(L)]
    # a depth-d sync spans this axis and every deeper one: psum over the
    # whole leaf set of the group, so partially-present subtrees weight
    # per-LEAF exactly like the host backend's segment sums
    axes_from = [tuple(axis_of_depth[d:]) for d in range(L)]
    # uniform per-leaf w-weight at depth d: (1/K_d) / leaves-per-child
    wcoef_leaf = [1.0 / math.prod(ks[d:]) for d in range(L)]
    group_dev = [math.prod(ks[d:]) for d in range(L)]   # G_d per depth
    H = plan.h_max
    rs = sync == "reduce_scatter"

    specs = _comp_specs(plan)
    comp_depths = [dd for dd in range(L)
                   if specs[dd][0] != comp_mod.KIND_NONE]
    comp_idx = {dd: i for i, dd in enumerate(comp_depths)}

    @jax.named_scope("codec")
    def roundtrip_vec(depth, target):
        """The receiver's view of this depth's compressed (d,) delta."""
        kind, frac = specs[depth]
        if kind == comp_mod.KIND_INT8:
            return comp_mod.int8_roundtrip(target)
        k = comp_mod.topk_count(target.shape[-1], frac)
        return comp_mod.topk_roundtrip(target, k)

    @jax.named_scope("leaf_solve")
    def leaf_solve(Xs, ys, a, w, k_t, st_t, lm):
        """One Procedure-P call on this shard's (1, m_b) block, drawing the
        tick's coordinates from the replayed per-solve key; ``st_t`` is the
        slot's (1, H) runtime step mask (all-ones => the static-H solve,
        bit-for-bit: the mask multiplies each delta by 1.0)."""
        ix = jax.random.randint(k_t, (H,), 0, m_b)[None]  # legacy draw shape
        if use_kernel:
            from repro.kernels.sdca.kernel import sdca_block_kernel
            da, dw = sdca_block_kernel(Xs, ys, a, w, ix, loss=loss, lm=lm,
                                       step_mask=st_t,
                                       interpret=not on_tpu())
        else:
            from repro.kernels.sdca.ref import sdca_block_ref
            da, dw = sdca_block_ref(Xs, ys, a, w, ix, loss=loss, lm=lm,
                                    step_mask=st_t)
        return da, dw[0]

    def _geom(d_feat):
        """Sharded-server geometry.  ``shard``/``gather`` are each other's
        inverse BY CONSTRUCTION: a shard is what tiled ``psum_scatter``
        assigns this device (contributing ``x / G`` from every member of
        the group, whose sum is ``x`` again for group-uniform ``x``), and
        ``gather`` is the matching tiled ``all_gather`` -- so chunk
        placement follows the collectives' own device order and the
        lowering never materializes a device-position index.  (That is
        deliberate: device-varying ``dynamic_slice`` offsets derived from
        ``axis_index``, and participation gates over tiled-collective
        values, both abort XLA's sharding-propagation pass when they feed
        a loop carry.)  ``pad_w``/``unpad`` round the leaf's ``w`` replica
        up to the largest group-padded size: the loop-carried replica must
        keep a collective-aligned length for the same reason."""
        p_sz = [-(-d_feat // g) for g in group_dev]
        d_pad = max(g * p for g, p in zip(group_dev, p_sz, strict=True))

        def shard(dd, x):
            # x must be uniform across the depth-dd group (server state is)
            xp = jnp.pad(x, (0, group_dev[dd] * p_sz[dd] - d_feat))
            return jax.lax.psum_scatter(
                xp * (1.0 / group_dev[dd]), axes_from[dd],
                scatter_dimension=0, tiled=True)

        def gather(dd, sh):
            return jax.lax.all_gather(
                sh, axes_from[dd], tiled=True)[:d_feat]

        def scatter_sum(dd, x):
            xp = jnp.pad(x, (0, group_dev[dd] * p_sz[dd] - d_feat))
            return jax.lax.psum_scatter(
                xp, axes_from[dd], scatter_dimension=0, tiled=True)

        def pad_w(x):
            return jnp.pad(x, (0, d_pad - d_feat))

        def unpad(x):
            return x[:d_feat]

        return shard, gather, scatter_sum, pad_w, unpad

    def make_run(Xs, ys, kys, part, steps, lm, acceleration=None):
        """Build the recursive rounds-driver over this shard's inputs:
        Xs (1, m_b, d), kys (1, S, 2), part (1, S), steps (1, S, H);
        ``lm`` is the replicated runtime lambda*m scalar, ``acceleration``
        the runtime server-momentum scalar (accelerated programs only).
        The carry is a tuple whose first three slots are always
        (a, w, t_c); the server tail is lowering-specific:

        * psum: ``(a, w, t_c, snapA, snapW, srvW[, srvP, srvA], res)``
        * reduce_scatter: ``(a, w, t_c, snapA, srv_sh[, srvP_sh, srvA],
          res)`` with ``srv_sh`` the per-depth sharded server/snapshot
          chunks (one vector under full participation -- snap == srv)

        where the bracketed momentum anchors exist only in accelerated
        programs (``srvP`` anchors the server w sequence, ``srvA`` the
        combined alpha -- both sides extrapolate with the same runtime
        coefficient, preserving the linear alpha -> w consistency)."""
        dt = Xs.dtype
        one = jnp.ones((), dt)
        acc = None
        if accelerated:
            acc = jnp.asarray(acceleration, dt)
        if rs:
            shard, gather, scatter_sum, pad_w, unpad = _geom(Xs.shape[-1])
        else:
            pad_w = unpad = lambda x: x

        def gates(depth, part, t_c):
            """Participation-renormalized weights of the tick's sync."""
            wc = jnp.asarray(wcoef_leaf[depth], dt)
            p = jax.lax.dynamic_index_in_dim(part, t_c - 1, axis=1,
                                             keepdims=False)[0].astype(dt)
            absent = jax.lax.psum((one - p) * wc, axes_from[depth])
            present = jax.lax.psum(p * wc, axes_from[depth])
            denom = jnp.where(absent == 0, one,
                              jnp.where(present > 0, present, one))
            act = present > 0
            attend = (p > 0) & act
            # a partially-present child subtree is represented by its
            # surviving shards (all carrying the child's full delta): their
            # per-leaf weight scales up by |child| / |present in child|
            if depth < L - 1:
                cnt = jax.lax.psum(p, axes_from[depth + 1])
                size = jnp.asarray(float(math.prod(ks[depth + 1:])), dt)
                corr = size / jnp.maximum(cnt, one)
            else:
                corr = one
            return p, wc, denom, act, attend, corr

        def compress_delta(depth, delta, res, attend=None):
            """Error feedback: compress(delta + residual), residual
            advancing only when this shard actually delivers (``attend``
            None -- the full-participation reduce_scatter path -- advances
            unconditionally)."""
            if depth not in comp_idx:
                return delta, res
            ri = comp_idx[depth]
            target = delta.astype(jnp.float32) + res[ri]
            approx = roundtrip_vec(depth, target)
            r_new = target - approx if attend is None else \
                jnp.where(attend, target - approx, res[ri])
            res = res[:ri] + (r_new,) + res[ri + 1:]
            return approx.astype(dt), res

        @jax.named_scope("level_sync")
        def sync_psum(depth, carry, parent_sync):
            """The depth-`depth` aggregation at tick ``t_c - 1`` with
            participation-renormalized weights; absent shards keep their
            state/snapshots, the group server stays coherent for them.
            ``parent_sync`` flags that the parent also syncs at this tick
            (its own call handles the shallower bookkeeping then)."""
            if accelerated:
                a, w, t_c, snapA, snapW, srvW, srvP, srvA, res = carry
            else:
                a, w, t_c, snapA, snapW, srvW, res = carry
            K = ks[depth]
            p, wc, denom, act, attend, corr = gates(depth, part, t_c)
            delta, res = compress_delta(depth, w - snapW[depth], res,
                                        attend)
            tot = jax.lax.psum((p * wc / denom) * corr * delta,
                               axes_from[depth])
            srv_base = srvW[depth] + tot
            base_a = snapA[depth] + (a - snapA[depth]) / (denom * K)
            if accelerated:
                # paired Nesterov-style extrapolation (see engine.host):
                # both sides move along their un-extrapolated combination
                # sequences with the same coefficient; acceleration == 0
                # selects the base exactly (a where, bit-identical)
                ext_w = srv_base + acc * (srv_base - srvP[depth])
                srv_new = jnp.where(acc != 0, ext_w, srv_base)
                ext_a = base_a + acc * (base_a - srvA[depth])
                new_a = jnp.where(acc != 0, ext_a, base_a)
                srvP = srvP.at[depth].set(
                    jnp.where(act, srv_base, srvP[depth]))
                srvA = srvA.at[depth].set(
                    jnp.where(attend, base_a, srvA[depth]))
            else:
                srv_new = srv_base
                new_a = base_a
            a = jnp.where(attend, new_a, a)
            w = jnp.where(attend, srv_new, w)
            # server advance at this depth + deeper rebase, group-wide
            for d2 in range(depth, L):
                srvW = srvW.at[d2].set(jnp.where(act, srv_new, srvW[d2]))
            if accelerated:
                # deeper momentum anchors restart from the pulled state
                # (zero velocity after a rebase), exactly as on the host
                for d2 in range(depth + 1, L):
                    srvP = srvP.at[d2].set(
                        jnp.where(act, srv_new, srvP[d2]))
                    srvA = srvA.at[d2].set(
                        jnp.where(attend, a, srvA[d2]))
            # snapshots are per-shard private state: participants only;
            # depths shallower than this sync fast-forward to the server
            # baseline the pulled state embeds -- unless the parent syncs
            # at this very tick and refreshes them itself
            for d2 in range(depth, L):
                snapA = snapA.at[d2].set(jnp.where(attend, a, snapA[d2]))
                snapW = snapW.at[d2].set(jnp.where(attend, w, snapW[d2]))
            ff = attend & jnp.logical_not(parent_sync)
            for d2 in range(depth):
                snapW = snapW.at[d2].set(jnp.where(ff, srvW[d2], snapW[d2]))
            if accelerated:
                return a, w, t_c, snapA, snapW, srvW, srvP, srvA, res
            return a, w, t_c, snapA, snapW, srvW, res

        @jax.named_scope("level_sync")
        def sync_rs(depth, carry, parent_sync):
            """The reduce_scatter lowering of the depth sync: reconstruct
            the (group-coherent) snapshot from this depth's server shards,
            ``psum_scatter`` the (optionally compressed) local delta into
            the shard, then one ``all_gather`` for the full post-sync
            ``w``.  Deeper server shards rebase by re-slicing that full
            vector; snap == srv under the full participation this path
            assumes (the participation mask is NOT consulted -- the
            session refuses to route partial-participation schedules
            here), which is also what lets the sync run ungated: XLA's
            sharding propagation aborts on participation-``where`` gates
            over tiled-collective values."""
            if accelerated:
                a, w, t_c, snapA, srv_sh, srvP_sh, srvA, res = carry
            else:
                a, w, t_c, snapA, srv_sh, res = carry
            K = ks[depth]
            wc = jnp.asarray(wcoef_leaf[depth], dt)
            snap_full = gather(depth, srv_sh[depth])
            delta, res = compress_delta(depth, unpad(w) - snap_full, res)
            tot_sh = scatter_sum(depth, wc * delta)
            base_sh = srv_sh[depth] + tot_sh
            base_a = snapA[depth] + (a - snapA[depth]) / K
            if accelerated:
                # paired extrapolation on the SHARDED server chunks (the
                # anchors live in shard layout, so momentum costs no extra
                # collective) and on the combined alpha
                ext_sh = base_sh + acc * (base_sh - srvP_sh[depth])
                new_sh = jnp.where(acc != 0, ext_sh, base_sh)
                ext_a = base_a + acc * (base_a - srvA[depth])
                a = jnp.where(acc != 0, ext_a, base_a)
                srvP_sh = (srvP_sh[:depth] + (base_sh,)
                           + srvP_sh[depth + 1:])
                srvA = srvA.at[depth].set(base_a)
            else:
                new_sh = base_sh
                a = base_a
            w_new = gather(depth, new_sh)
            w = pad_w(w_new)
            for d2 in range(depth, L):
                snapA = snapA.at[d2].set(a)
                srv_sh = (srv_sh[:d2] + (shard(d2, w_new),)
                          + srv_sh[d2 + 1:])
                if accelerated and d2 > depth:
                    # deeper anchors restart at the pulled state
                    srvP_sh = (srvP_sh[:d2] + (srv_sh[d2],)
                               + srvP_sh[d2 + 1:])
                    srvA = srvA.at[d2].set(a)
            if accelerated:
                return a, w, t_c, snapA, srv_sh, srvP_sh, srvA, res
            return a, w, t_c, snapA, srv_sh, res

        sync = sync_rs if rs else sync_psum

        def leaf_step(carry):
            a, w, t_c = carry[0], unpad(carry[1]), carry[2]
            k_t = jax.lax.dynamic_index_in_dim(kys, t_c, axis=1,
                                               keepdims=False)[0]
            st_t = jax.lax.dynamic_index_in_dim(steps, t_c, axis=1,
                                                keepdims=False)
            da, dw = leaf_solve(Xs, ys, a, w, k_t, st_t, lm)
            return (carry[0] + da, pad_w(w + dw), t_c + 1) + carry[3:]

        def run(depth, carry):
            """One full solve of a depth-`depth` node: rounds[depth] rounds,
            each recursing below then aggregating over this depth's group
            (Algorithm 2)."""
            T = rounds[depth]

            def one_round(i, c):
                c = leaf_step(c) if depth == L - 1 else run(depth + 1, c)
                parent_sync = (i == T - 1) if depth > 0 else jnp.bool_(False)
                return sync(depth, c, parent_sync)
            return jax.lax.fori_loop(0, T, one_round, carry)

        def init_tail(a0, w0):
            """The server tail + residuals of a run-start carry (leaf-level
            shapes: a0 (1, m_b), w0 (d,)).  Accelerated programs insert
            the momentum anchors (initialized at the run-start state, so
            the first sync extrapolates along its own first delta) between
            the server slots and the residuals."""
            d_feat = w0.shape[-1]
            snapA0 = jnp.broadcast_to(a0[None], (L,) + a0.shape)
            res0 = tuple(jnp.zeros((d_feat,), jnp.float32)
                         for _ in comp_depths)
            if rs:
                srv0 = tuple(shard(dd, w0) for dd in range(L))
                if accelerated:
                    return (snapA0, srv0, srv0, snapA0, res0)
                return (snapA0, srv0, res0)
            snapW0 = jnp.broadcast_to(w0[None], (L, d_feat))
            if accelerated:
                return (snapA0, snapW0, snapW0, snapW0, snapA0, res0)
            return (snapA0, snapW0, snapW0, res0)

        return run, init_tail, pad_w, unpad

    def program(Xs, ys, a0, w0, kys, part, steps, lm, acceleration=None):
        # Xs (1, m_b, d), a0 (1, m_b), w0 (d,), kys (1, S, 2),
        # part (1, S), steps (1, S, H) on this shard; lm (and the
        # accelerated flavor's momentum coefficient) replicated scalars
        d_feat = Xs.shape[-1]
        run, init_tail, pad_w, unpad = make_run(Xs, ys, kys, part, steps,
                                                lm, acceleration)
        carry = (a0, pad_w(w0), jnp.int32(0)) + init_tail(a0, w0)
        out = run(0, carry)
        a_end, w_end = out[0], unpad(out[1])
        return a_end, jnp.broadcast_to(w_end[None], (1, d_feat))

    def program_state(Xs, ys, state, kys, part, steps, lm,
                      acceleration=None):
        # state is leaf-major (every leaf owns dim 0 of each element):
        # a0 (1, m_b), wrows (1, d), sA (1, L, m_b), then the lowering's
        # server tail (psum: sW/sV (1, L, d), accelerated inserts the sP
        # (1, L, d) / sPA (1, L, m_b) anchors; rs: per-depth (1, p_d)
        # shards, accelerated inserts the anchor shards + sPA), then
        # per-compressed-depth residuals (1, d)
        run, _, pad_w, unpad = make_run(Xs, ys, kys, part, steps, lm,
                                        acceleration)
        a0, wrows, sA = state[0], state[1], state[2]
        n_res = len(comp_depths)
        if rs:
            srv = tuple(s[0] for s in state[3:3 + L])
            k = 3 + L
            if accelerated:
                srvP = tuple(s[0] for s in state[k:k + L])
                sPA = state[k + L]
                k = k + L + 1
            res = tuple(r[0] for r in state[k:])
            if accelerated:
                carry = (a0, pad_w(wrows[0]), jnp.int32(0),
                         sA[0][:, None, :], srv, srvP,
                         sPA[0][:, None, :], res)
                out = run(0, carry)
                a2, w2, _, sA2, srv2, srvP2, sPA2, res2 = out
                return ((a2, unpad(w2)[None], sA2[:, 0, :][None])
                        + tuple(s[None] for s in srv2)
                        + tuple(s[None] for s in srvP2)
                        + (sPA2[:, 0, :][None],)
                        + tuple(r[None] for r in res2))
            carry = (a0, pad_w(wrows[0]), jnp.int32(0),
                     sA[0][:, None, :], srv, res)
            out = run(0, carry)
            a2, w2, _, sA2, srv2, res2 = out
            return ((a2, unpad(w2)[None], sA2[:, 0, :][None])
                    + tuple(s[None] for s in srv2)
                    + tuple(r[None] for r in res2))
        sW, sV = state[3], state[4]
        if accelerated:
            sP, sPA = state[5], state[6]
            res = tuple(r[0] for r in state[7:7 + n_res])
            carry = (a0, wrows[0], jnp.int32(0), sA[0][:, None, :], sW[0],
                     sV[0], sP[0], sPA[0][:, None, :], res)
            out = run(0, carry)
            a2, w2, _, sA2, sW2, sV2, sP2, sPA2, res2 = out
            return ((a2, w2[None], sA2[:, 0, :][None], sW2[None],
                     sV2[None], sP2[None], sPA2[:, 0, :][None])
                    + tuple(r[None] for r in res2))
        res = tuple(r[0] for r in state[5:5 + n_res])
        carry = (a0, wrows[0], jnp.int32(0), sA[0][:, None, :], sW[0],
                 sV[0], res)
        out = run(0, carry)
        a2, w2, _, sA2, sW2, sV2, res2 = out
        return ((a2, w2[None], sA2[:, 0, :][None], sW2[None], sV2[None])
                + tuple(r[None] for r in res2))

    spec_in = P(tuple(reversed(axes)))
    # batched programs shard the SECOND dim (the leaf dim) and keep the
    # leading config axis B replicated; per-shard values then carry a
    # leading B the program vmaps over INSIDE the shard_map
    spec_b = P(None, tuple(reversed(axes)))
    if carry_state:
        from repro.core.engine.host import StateExecutor
        n = plan.n_leaves
        sharding = NamedSharding(mesh, spec_b if batched else spec_in)

        if batched:
            if accelerated:
                def program_state_b(Xs, ys, state, kys, part, steps, lm,
                                    acceleration):
                    return jax.vmap(
                        lambda st, ky, sp, l: program_state(
                            Xs, ys, st, ky, part, sp, l, acceleration)
                    )(state, kys, steps, lm)
            else:
                def program_state_b(Xs, ys, state, kys, part, steps, lm):
                    return jax.vmap(
                        lambda st, ky, sp, l: program_state(
                            Xs, ys, st, ky, part, sp, l)
                    )(state, kys, steps, lm)
            state_specs = (spec_in, spec_in, spec_b, spec_b, spec_in,
                           spec_b, P()) + ((P(),) if accelerated else ())
            # the chunk carry (arg 2) is DONATED: callers rebind
            # ``state = step(...)`` every chunk
            step = jax.jit(shard_map(
                program_state_b, mesh=mesh, in_specs=state_specs,
                out_specs=spec_b), donate_argnums=(2,))
        else:
            state_specs = (spec_in,) * 6 + (P(),) \
                + ((P(),) if accelerated else ())
            step = jax.jit(shard_map(
                program_state, mesh=mesh, in_specs=state_specs,
                out_specs=spec_in), donate_argnums=(2,))

        def init_state(a0, wr):
            # run-start server tail from replicated-per-leaf (a, w) rows;
            # a device computation because the rs shards are
            # position-dependent (the geometry lives inside shard_map)
            _, init_tail, _, _ = make_run(
                jnp.zeros((1, m_b, wr.shape[-1]), wr.dtype),
                None, None, None, None, None,
                0.0 if accelerated else None)
            tail = init_tail(a0, wr[0])
            sA = tail[0]
            flat = []
            for t in tail[1:]:
                for x in (t if isinstance(t, tuple) else (t,)):
                    if x.ndim == 3 and x.shape[1] == 1:
                        # (L, 1, m_b) alpha-shaped anchor -> (1, L, m_b)
                        flat.append(x[:, 0, :][None])
                    else:
                        flat.append(x[None])
            return (a0, wr, sA[:, 0, :][None]) + tuple(flat)

        if batched:
            init_prog = jax.jit(shard_map(
                lambda a0, wr: jax.vmap(init_state)(a0, wr),
                mesh=mesh, in_specs=(spec_b, spec_b), out_specs=spec_b))
        else:
            init_prog = jax.jit(shard_map(
                init_state, mesh=mesh, in_specs=(spec_in, spec_in),
                out_specs=spec_in))

        def init(X, alpha, w):
            dt = X.dtype
            d_feat = X.shape[1]
            if batched:
                B = alpha.shape[0]
                a0 = jnp.asarray(alpha, dt).reshape(B, n, m_b)
                wr = jnp.broadcast_to(
                    jnp.asarray(w, dt)[:, None, :], (B, n, d_feat))
            else:
                a0 = jnp.asarray(alpha, dt).reshape(n, m_b)
                wr = jnp.broadcast_to(jnp.asarray(w, dt)[None], (n, d_feat))
            a0 = jax.device_put(a0, sharding)
            wr = jax.device_put(wr, sharding)
            return init_prog(a0, wr)

        if batched:
            def finalize(state):
                return (state[0].reshape(state[0].shape[0], -1),
                        state[1][:, 0])
        else:
            def finalize(state):
                return state[0].reshape(-1), state[1][0]

        fn = StateExecutor(init=init, step=step, finalize=jax.jit(finalize))
    elif batched:
        if accelerated:
            def program_b(Xs, ys, a0, w0, kys, part, steps, lm,
                          acceleration):
                return jax.vmap(
                    lambda a, w, ky, sp, l: program(
                        Xs, ys, a, w, ky, part, sp, l, acceleration)
                )(a0, w0, kys, steps, lm)
        else:
            def program_b(Xs, ys, a0, w0, kys, part, steps, lm):
                return jax.vmap(
                    lambda a, w, ky, sp, l: program(
                        Xs, ys, a, w, ky, part, sp, l)
                )(a0, w0, kys, steps, lm)
        fn = jax.jit(shard_map(
            program_b, mesh=mesh,
            in_specs=(spec_in, spec_in, spec_b, P(), spec_b, spec_in,
                      spec_b, P()) + ((P(),) if accelerated else ()),
            out_specs=(spec_b, spec_b),
        ))
    elif accelerated:
        fn = jax.jit(shard_map(
            program, mesh=mesh,
            in_specs=(spec_in, spec_in, spec_in, P(), spec_in, spec_in,
                      spec_in, P(), P()),
            out_specs=(spec_in, spec_in),
        ))
    else:
        fn = jax.jit(shard_map(
            program, mesh=mesh,
            in_specs=(spec_in, spec_in, spec_in, P(), spec_in, spec_in,
                      spec_in, P()),
            out_specs=(spec_in, spec_in),
        ))
    # miss counted only after a successful build (see engine.host)
    from repro.core.engine.host import _named_key
    _MESH_CACHE_STATS["misses"] += 1
    _MISS_LOG.append({"backend": "mesh",
                      "key": _named_key(MESH_KEY_FIELDS, cache_key)})
    del _MISS_LOG[:-_MISS_LOG_MAX]
    _MESH_EXEC_CACHE[cache_key] = fn
    while len(_MESH_EXEC_CACHE) > _MESH_EXEC_CACHE_MAX:
        _MESH_EXEC_CACHE.popitem(last=False)
    return fn


def execute_plan_mesh(
    plan: TreePlan,
    tree: TreeNode,
    X: Array,
    y: Array,
    mesh: Mesh,
    *,
    axes: Sequence[str],
    loss: Loss,
    lam: float,
    key=None,
    use_kernel: bool = True,
    alpha0: Array = None,
    w0: Array = None,
    participation: Array = None,
    steps: Array = None,
    sync: str = "psum",
) -> Tuple[Array, Array]:
    """Run the plan on ``mesh``; returns (alpha (m,), w (d,)).  ``alpha0``/
    ``w0`` warm-start the run (cold all-zeros by default);
    ``participation`` is the (S, n) sync-attendance mask (all-ones -- the
    synchronous schedule -- by default); ``steps`` the (S, n, h_max)
    runtime step mask (all-ones -- the static-H schedule -- by default);
    ``sync`` the collective lowering (``"psum"`` / ``"reduce_scatter"``,
    see :func:`get_mesh_executor`)."""
    mesh = auto_axes(mesh)
    _check_plan_mesh(plan, mesh, axes)
    n, m_b = plan.n_leaves, plan.m_b
    m, d_feat = X.shape
    assert n * m_b == m, (n, m_b, m)

    fn = get_mesh_executor(plan, mesh, axes=axes, loss=loss,
                           use_kernel=use_kernel, sync=sync)
    keys = key_plan(tree, plan, key)                        # (S, n, 2)
    keys_leaf = jnp.asarray(keys.transpose(1, 0, 2))        # (n, S, 2)
    if participation is None:
        participation = full_participation(plan)
    part_leaf = jnp.asarray(participation, X.dtype).T       # (n, S)
    if steps is None:
        steps = full_steps(plan)
    steps_leaf = jnp.asarray(                               # (n, S, h_max)
        np.asarray(steps, np.float32).transpose(1, 0, 2), X.dtype)

    a0 = jnp.zeros((n, m_b), X.dtype) if alpha0 is None else \
        jnp.asarray(alpha0, X.dtype).reshape(n, m_b)
    w_start = jnp.zeros((d_feat,), X.dtype) if w0 is None else \
        jnp.asarray(w0, X.dtype)
    spec_in = P(tuple(reversed(axes)))
    Xs = jax.device_put(X.reshape(n, m_b, d_feat), NamedSharding(mesh, spec_in))
    ys = jax.device_put(y.reshape(n, m_b), NamedSharding(mesh, spec_in))
    kys = jax.device_put(keys_leaf, NamedSharding(mesh, spec_in))
    part = jax.device_put(part_leaf, NamedSharding(mesh, spec_in))
    stp = jax.device_put(steps_leaf, NamedSharding(mesh, spec_in))
    from repro.core.engine.host import regularizer_scale
    alpha, w = fn(Xs, ys, a0, w_start, kys, part, stp,
                  regularizer_scale(lam, plan.m_total, X.dtype))
    return alpha.reshape(m), w[0]


def tree_from_mesh_axes(
    mesh: Mesh,
    axes: Sequence[str],
    rounds: Sequence[int],
    *,
    local_steps: int,
    m_leaf: int,
) -> TreeNode:
    """The tree whose recursion IS the mesh-axis hierarchy: ``axes`` are
    listed innermost (leaf level) first, so the root fans out over
    ``axes[-1]`` and runs ``rounds[-1]`` rounds."""
    from repro.core.engine.plan import balanced_tree
    sizes = [dict(mesh.shape)[a] for a in axes]
    return balanced_tree(
        list(reversed(sizes)), list(reversed(rounds)),
        local_steps=local_steps, m_leaf=m_leaf)
