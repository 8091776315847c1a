"""Host backend: execute a :class:`~repro.core.engine.plan.TreePlan` as ONE
jit-compiled ``lax.scan`` over ticks.

Per tick: a batched leaf solve (vmapped Procedure P, or the Pallas
``sdca_block_kernel`` with per-block w and step masks), then the tick's sync
events bottom-up (per-leaf alpha rescale against the depth snapshot and a
segment-sum weighted w-average), then snapshot refreshes.  The whole nested
recursion therefore costs one compile and zero per-child Python dispatch --
compare the legacy recursion's O(tree x rounds) jit calls and full-vector
``alpha.at[sl].add`` copies.

Async / stale sync: the executor takes a runtime ``(S, n)`` participation
mask (see ``engine.plan``).  A leaf whose mask is 0 at a tick is absent
from that tick's syncs: present children's weights are renormalized, the
absent leaf's state, snapshots, and pending delta are left untouched, and a
per-depth *server* ``w`` carry (``srvW`` -- the post-sync aggregate each
group last agreed on, kept group-coherent even for absent leaves) lets it
re-join later: its delta since its last participation is folded into the
CURRENT server state, exactly the bounded-staleness aggregation of delayed
distributed methods.  With an all-ones mask every gate reduces to the
synchronous path bit-for-bit (``x/1.0 == x``, ``srvW == snapW``).

Runtime schedules: the executor also takes a ``(S, n, h_max)`` step mask
(see ``engine.plan.steps_for_h``).  Coordinate draws always happen at the
plan's per-leaf H capacity; the mask zeroes the deltas of trailing steps,
so per-leaf / per-slot heterogeneous H is a runtime input of the SAME
compiled program (H-axis sweeps and delay-adaptive replanning never
retrace).  An all-ones step mask multiplies the static per-leaf H gate by
exactly 1.0 -- bit-identical to the static-H schedule.

Optionally records the (dual, primal) series at root-sync ticks inside the
same program (a ``lax.cond`` so the objective is only evaluated T_root
times, as the legacy history recording did on the host).
"""
from __future__ import annotations

from collections import OrderedDict
from typing import Callable, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.compat import on_tpu
from repro.core import compression as comp_mod
from repro.core.dual import Loss
from repro.core.engine.plan import TreePlan

Array = jax.Array

# Executors are cached per (plan structure, loss, flags) so repeated solves
# with the same topology reuse one compiled program; lambda is a RUNTIME
# input (an entire regularization grid shares one executor).  LRU-bounded
# because schedule sweeps (fig4/fig5-style) still generate a fresh plan per
# configuration.
_EXEC_CACHE: OrderedDict = OrderedDict()
_EXEC_CACHE_MAX = 32
# field names of the cache-key tuple, in order -- the trace guard's
# structured miss diffs name the offending component instead of dumping
# an anonymous tuple
EXEC_KEY_FIELDS = ("plan_fingerprint", "loss", "gamma", "record_history",
                   "backend", "carry_state", "batched", "accelerated")
_EXEC_CACHE_STATS = {"hits": 0, "misses": 0}
# per-backend breakdown ("vmap" / "pallas"; the mesh and LM caches report
# their own columns through executor_cache_stats) so strict sessions and
# the benchmarks can hold a zero-unexpected-miss budget PER BACKEND
_BACKEND_STATS = {"vmap": {"hits": 0, "misses": 0},
                  "pallas": {"hits": 0, "misses": 0}}
# bounded log of recent cache misses: (backend, named key dict).  The
# trace guard reads it to attach the offending keys -- and their diff
# against the nearest cached key -- to UnexpectedRetraceError.
_MISS_LOG: list = []
_MISS_LOG_MAX = 64


def _named_key(fields, key) -> dict:
    return dict(zip(fields, key, strict=True))


def _log_miss(backend: str, named: dict):
    _MISS_LOG.append({"backend": backend, "key": named})
    del _MISS_LOG[:-_MISS_LOG_MAX]


def regularizer_scale(lam: float, m_total: int, dtype) -> jnp.ndarray:
    """The runtime regularization scalar the executors consume: lambda * m
    computed in host double precision and THEN cast, so the traced value is
    bit-identical to the one the legacy static-lambda executors closed
    over (``lm = lam * m`` as a Python float)."""
    return jnp.asarray(float(lam) * m_total, dtype)


def executor_cache_stats() -> dict:
    """Cumulative executor-cache counters across ALL engine executor
    caches: top-level ``{hits, misses, size}`` aggregate the host cache
    (back-compatible with older callers) PLUS the mesh and LM caches, and
    ``by_backend`` breaks hits/misses down per backend
    (``vmap`` / ``pallas`` / ``mesh`` / ``lm``) so a strict session or a
    benchmark can assert a zero-unexpected-miss budget for exactly the
    backend it runs on.

    Note the aggregation itself fixes a double-counting-adjacent bug: the
    mesh cache used to keep NO counters at all, so a mesh executor rebuild
    was invisible to ``Session.cache_stats()`` miss assertions."""
    from repro.core.engine import lm as lm_mod
    from repro.core.engine import mesh as mesh_mod
    mesh_stats = mesh_mod.mesh_executor_cache_stats()
    lm_stats = lm_mod.lm_executor_cache_stats()
    by_backend = {k: dict(v) for k, v in _BACKEND_STATS.items()}
    by_backend["mesh"] = {"hits": mesh_stats["hits"],
                          "misses": mesh_stats["misses"]}
    by_backend["lm"] = {"hits": lm_stats["hits"],
                        "misses": lm_stats["misses"]}
    return {
        "hits": sum(v["hits"] for v in by_backend.values()),
        "misses": sum(v["misses"] for v in by_backend.values()),
        "size": len(_EXEC_CACHE) + mesh_stats["size"] + lm_stats["size"],
        "by_backend": by_backend,
    }


def executor_cache_keys() -> list:
    """The host cache's current keys as named dicts (see
    ``EXEC_KEY_FIELDS``) -- what the trace guard diffs a miss against."""
    return [_named_key(EXEC_KEY_FIELDS, k) for k in _EXEC_CACHE]


def executor_miss_log() -> list:
    """Recent cache misses across the host + mesh caches, newest last:
    ``{"backend": ..., "key": {field: value}}`` entries."""
    from repro.core.engine import mesh as mesh_mod
    return list(_MISS_LOG) + list(mesh_mod._MISS_LOG)


def get_host_executor(
    plan: TreePlan,
    *,
    loss: Loss,
    record_history: bool = True,
    backend: str = "vmap",
    carry_state: bool = False,
    batched: bool = False,
    accelerated: bool = False,
):
    """Build (or fetch from cache) the jitted executor for ``plan``.

    The default executor has signature ``fn(X, y, keys, alpha0, w0,
    participation, steps, lm) -> (alpha, w[, duals, primals])`` with
    ``keys`` the (S, n, 2) per-solve key plan (``plan.key_plan``),
    ``(alpha0, w0)`` the flat (m,) / (d,) warm-start state (zeros for a
    cold start), ``participation`` the (S, n) 0/1 sync-attendance mask
    (``plan.full_participation`` for the synchronous schedule), ``steps``
    the (S, n, h_max) 0/1 runtime step mask (``plan.full_steps`` for the
    static-H schedule; ``plan.steps_for_h`` for heterogeneous / replanned
    H), and ``lm`` the RUNTIME regularization scalar lambda*m
    (:func:`regularizer_scale`) -- a whole lambda grid AND a whole H grid
    share one compiled program; coordinate draws happen inside it at the
    per-leaf H capacity, independent of the step mask.  The executor is
    specialized to the plan structure but re-usable across
    keys/data/start-state/masks/schedules/lambdas of the same shape.

    ``carry_state=True`` instead returns a :class:`StateExecutor` whose
    ``step(X, y, keys, state, participation, steps, lm) -> state`` threads
    the FULL blocked carry ``(a, w, snapA, snapW, srvW)`` across
    invocations: with participation masks the flat ``(alpha, w)`` pair is
    no longer a complete chunk carry (absent leaves hold divergent
    replicas and stale snapshots), so async sessions must thread this
    state instead.  Under all-ones masks ``init -> step^T -> finalize`` is
    bit-identical to the flat executor chunked the same way.

    ``batched=True`` returns the vmapped variant: one device program for a
    leading config axis B over (keys, alpha0, w0, steps, lm) -- a lambda
    grid, an RNG-seed grid, an H grid, and per-config warm-start states
    fuse into a single dispatch per chunk (``fn(X, y, keys (B,S,n,2),
    alpha0 (B,m), w0 (B,d), participation (S,n) shared,
    steps (B,S,n,h_max), lm (B,))``).  Composes with ``carry_state``
    (init/step/finalize all carry the leading B axis).

    ``accelerated=True`` builds the ``sdca_acc`` flavor: Nesterov-style
    momentum on every server combination step.  The executor signature
    gains one trailing RUNTIME scalar ``acceleration`` (shared across a
    batch), the carry gains per-depth momentum anchors (``srvP`` for the
    server w, ``srvA`` for the combined alpha) right after ``srvW``, and
    each sync extrapolates BOTH sides of the primal-dual pair with the
    same coefficient -- ``x = base + acceleration * (base - prev)`` --
    along the un-extrapolated combination sequence, preserving
    ``w == X^T alpha / (lambda m)`` exactly (the map is linear).  ``acceleration`` is a runtime operand -- sweeping the
    momentum coefficient never retraces -- and ``acceleration == 0``
    selects the un-extrapolated base through a ``jnp.where``, so it is
    bit-identical to the plain SDCA executor."""
    if backend not in ("vmap", "pallas"):
        raise ValueError(f"unknown backend {backend!r} (use 'vmap' or "
                         "'pallas'; the mesh backend is engine.mesh)")
    # loss keyed by (name, gamma): Loss names encode their parameters (e.g.
    # 'smooth_hinge_1'), so per-call constructed losses still hit the cache
    cache_key = (plan.fingerprint, loss.name, loss.gamma,
                 bool(record_history), backend, bool(carry_state),
                 bool(batched), bool(accelerated))
    fn = _EXEC_CACHE.get(cache_key)
    if fn is None:
        fn = _build_host_executor(plan, loss=loss,
                                  record_history=record_history,
                                  backend=backend, carry_state=carry_state,
                                  batched=batched, accelerated=accelerated)
        # count the miss only once the build SUCCEEDED: incrementing
        # before the build double-counted a failing configuration (every
        # retry after the raise re-counted a miss that never populated
        # the cache, skewing the hit/miss budgets strict mode enforces)
        _EXEC_CACHE_STATS["misses"] += 1
        _BACKEND_STATS[backend]["misses"] += 1
        _log_miss(backend, _named_key(EXEC_KEY_FIELDS, cache_key))
        _EXEC_CACHE[cache_key] = fn
        while len(_EXEC_CACHE) > _EXEC_CACHE_MAX:
            _EXEC_CACHE.popitem(last=False)
    else:
        _EXEC_CACHE_STATS["hits"] += 1
        _BACKEND_STATS[backend]["hits"] += 1
        _EXEC_CACHE.move_to_end(cache_key)
    return fn


def kernel_stats(plan: TreePlan, backend: str) -> dict:
    """The ``Session.run`` span stats of the leaf solve ``backend`` runs:
    on ``pallas``, how the ``sdca`` kernel packs the plan's leaves
    (:func:`repro.kernels.sdca.kernel.leaf_stats`); empty where the leaves
    run the XLA reference."""
    if backend != "pallas":
        return {}
    from repro.kernels.sdca.kernel import leaf_stats
    return leaf_stats(plan.n_leaves, plan.m_b)


class StateExecutor(NamedTuple):
    """The state-threading executor triple (see ``get_host_executor``):
    ``init(X, alpha0, w0) -> state``, ``step(X, y, keys, state,
    participation, steps, lm) -> state``, ``finalize(state) ->
    (alpha, w)``."""
    init: Callable
    step: Callable
    finalize: Callable


def _build_host_executor(plan: TreePlan, *, loss, record_history,
                         backend, carry_state=False, batched=False,
                         accelerated=False):
    n, m_b, S, D = plan.n_leaves, plan.m_b, plan.n_ticks, plan.depth
    h_max, m = plan.h_max, plan.m_total

    # ---- static layout maps (host numpy -> closed-over constants) ------
    j = np.arange(m_b)
    gather_idx = np.minimum(plan.leaf_offsets[:, None] + j[None, :], m - 1)
    valid = (j[None, :] < plan.leaf_sizes[:, None])           # (n, m_b)
    flat_map = np.zeros((m,), np.int64)                       # i -> blocked pos
    for li in range(n):
        o, s = int(plan.leaf_offsets[li]), int(plan.leaf_sizes[li])
        flat_map[o:o + s] = li * m_b + np.arange(s)
    hmask = (np.arange(h_max)[None, :] < plan.leaf_h[:, None])  # (n, h_max)
    # leaves grouped by H so each group draws its exact randint shape (the
    # legacy draw has no prefix property, so the shape must match per leaf)
    h_groups = [
        (h, tuple(np.nonzero(plan.leaf_h == h)[0].tolist()))
        for h in sorted({int(v) for v in plan.leaf_h})
    ]
    leaf_mb = jnp.asarray(plan.leaf_sizes.astype(np.int32))

    gather_idx = jnp.asarray(gather_idx)
    valid_f = jnp.asarray(valid, jnp.float32)
    flat_map = jnp.asarray(flat_map)
    hmask = jnp.asarray(hmask, jnp.float32)
    ascale = jnp.asarray(plan.alpha_scale)                    # (D, n)
    wcoef = jnp.asarray(plan.w_coeff)                         # (D, n)
    gids = jnp.asarray(plan.group_ids)                        # (D, n)
    ngroups = plan.n_groups
    cids = jnp.asarray(plan.child_ids)                        # (D, n)
    csize = jnp.asarray(plan.child_sizes)                     # (D, n)
    nchildren = plan.n_children
    # per-tick xs
    solve_mask = jnp.asarray(plan.solve_mask)                 # (S, n)
    sync_mask = jnp.asarray(plan.sync_mask)                   # (S, D, n)
    refresh_mask = jnp.asarray(plan.refresh_mask)             # (S, D, n)
    root_sync = jnp.asarray(plan.root_sync)                   # (S,) bool

    use_kernel = backend == "pallas"
    if use_kernel:
        from repro.kernels.sdca.kernel import sdca_block_kernel
    else:
        from repro.kernels.sdca.ref import sdca_block_ref

    # ---- static edge-compression structure (tentpole) ------------------
    # executors branch STATICALLY on has_comp: compression-free plans trace
    # the exact pre-compression program (bit-identity by construction).
    # Compressed depths carry an error-feedback residual (n, d) in the scan
    # carry; leaves are grouped by (kind, frac) so every roundtrip is a
    # shape-static op (scan-safe), with per-leaf rows = per-edge messages
    # (all leaves of one child subtree hold the child's identical delta).
    has_comp = plan.has_compression
    comp_depths = [dd for dd in range(D)
                   if (plan.compress_kind[dd] != comp_mod.KIND_NONE).any()]
    comp_idx = {dd: i for i, dd in enumerate(comp_depths)}
    comp_groups = {}
    for dd in comp_depths:
        groups = {}
        for li in range(n):
            k = int(plan.compress_kind[dd, li])
            if k == comp_mod.KIND_NONE:
                continue
            f = float(plan.compress_frac[dd, li])
            groups.setdefault((k, f), []).append(li)
        comp_groups[dd] = [(k, f, tuple(ls))
                           for (k, f), ls in sorted(groups.items())]
    comp_mask = {dd: jnp.asarray(
        (plan.compress_kind[dd] != comp_mod.KIND_NONE)[:, None])
        for dd in comp_depths}

    def _scan(X: Array, y: Array, keys: Array, carry0, participation: Array,
              steps: Array, lm: Array, acceleration=None):
        """Trace the full tick scan from an explicit blocked carry; returns
        (final carry, history stack, the objective closure).  ``steps`` is
        the (S, n, h_max) runtime step mask, ``lm`` the runtime lambda*m
        scalar (:func:`regularizer_scale`), ``acceleration`` the runtime
        server-momentum scalar (accelerated executors only)."""
        dtype = X.dtype
        if accelerated:
            acceleration = jnp.asarray(acceleration, dtype)
        lam = lm / m                     # only the in-program objective
        vmask = valid_f.astype(dtype)
        with jax.named_scope("reblock"):
            Xb = X[gather_idx] * vmask[:, :, None]            # (n, m_b, d)
            yb = y[gather_idx] * vmask                        # (n, m_b)

        def draw_idx(keys_s):
            """The tick's (n, h_max) coordinate draws, exactly as the legacy
            recursion would: randint(key_l, (H_l,), 0, m_b_l) per leaf."""
            idx_s = jnp.zeros((n, h_max), jnp.int32)
            for h, leaf_list in h_groups:
                rows = jnp.asarray(leaf_list)
                draws = jax.vmap(
                    lambda k, mb, h=h: jax.random.randint(k, (h,), 0, mb)
                )(keys_s[rows], leaf_mb[rows])
                idx_s = idx_s.at[rows, :h].set(draws)
            return idx_s

        def leaf_batch(a, w, keys_s, smask, steps_s):
            idx_s = draw_idx(keys_s)
            # the static per-leaf H-capacity gate x the solve slot x the
            # runtime step mask; all-ones steps multiply by exactly 1.0
            mk = (hmask * smask[:, None] * steps_s).astype(dtype)
            if use_kernel:
                return sdca_block_kernel(
                    Xb, yb, a, w, idx_s, loss=loss, lm=lm, step_mask=mk,
                    interpret=not on_tpu())
            return sdca_block_ref(Xb, yb, a, w, idx_s, loss=loss, lm=lm,
                                  step_mask=mk)

        @jax.named_scope("objective")
        def objective(a, w):
            """(dual, primal) at a root sync, where w rows are all equal."""
            w0 = w[0]
            reg = 0.5 * lam * jnp.dot(w0, w0)
            dv = -reg - jnp.sum(vmask * loss.conj_neg(a, yb)) / m
            margins = jnp.einsum("nbd,d->nb", Xb, w0)
            pv = reg + jnp.sum(vmask * loss.value(margins, yb)) / m
            return dv, pv

        @jax.named_scope("codec")
        def roundtrip(dd, target):
            """The receiver's view of this depth's per-edge messages: each
            compressed leaf row goes through its edge's (quantize +
            dequantize) in one traced op; uncompressed rows pass through."""
            approx = target
            for kind, frac, rows in comp_groups[dd]:
                rows_a = jnp.asarray(rows)
                sub = target[rows_a]
                if kind == comp_mod.KIND_INT8:
                    rt = comp_mod.int8_roundtrip(sub, keep_leading=1)
                else:
                    k = comp_mod.topk_count(sub.shape[-1], frac)
                    rt = comp_mod.topk_roundtrip(sub, k)
                approx = approx.at[rows_a].set(rt)
            return approx

        def tick(carry, xs):
            # carry layout: (a, w, snapA, snapW, srvW[, srvP][, res]) --
            # the previous-server momentum slot exists only in accelerated
            # executors, the EF residual tuple only in compressed plans
            a, w, snapA, snapW, srvW = carry[:5]
            rest = carry[5:]
            if accelerated:
                (srvP, srvA), rest = rest[:2], rest[2:]
            res = rest[0] if has_comp else ()
            keys_s, smask, sync_s, ref_s, hflag, part_s, steps_s = xs
            with jax.named_scope("leaf_solve"):
                da, dw = leaf_batch(a, w, keys_s, smask, steps_s)
                a = a + da
                w = w + dw
            # the level syncs, the deeper servers' rebase and the snapshot
            # refresh: the tick's aggregation up the tree
            with jax.named_scope("level_sync"):
                # syncs bottom-up; a leaf with part_s == 0 is absent from every
                # event of this tick.  `srvW[dd]` is the group's server state;
                # it advances (and later rebases) GROUP-wide so an absent
                # leaf's copy stays coherent with its group's.
                act_of: list = [None] * D
                for dd in range(D - 1, -1, -1):
                    ev = sync_s[dd]                               # (n,) event
                    e = ev * part_s                               # participants
                    wc = wcoef[dd].astype(dtype)
                    absent_g = jax.ops.segment_sum(
                        (ev - e) * wc, gids[dd], num_segments=ngroups[dd])
                    present_g = jax.ops.segment_sum(
                        e * wc, gids[dd], num_segments=ngroups[dd])
                    # exact 1.0 under full participation => x/denom is x/1.0,
                    # bit-identical to the synchronous path
                    denom_g = jnp.where(
                        absent_g == 0, jnp.ones((), dtype),
                        jnp.where(present_g > 0, present_g,
                                  jnp.ones((), dtype)))
                    denom = denom_g[gids[dd]]                     # (n,)
                    act = (ev > 0) & (present_g > 0)[gids[dd]]    # group live
                    eb = (e > 0)[:, None]                         # leaf attends
                    base_a = (snapA[dd] + (ascale[dd] / denom)[:, None]
                              * (a - snapA[dd]))
                    if accelerated:
                        # extrapolate alpha along its own combined sequence with
                        # the SAME coefficient as the server w below: w is the
                        # linear image X^T alpha / (lambda m) of alpha, so a
                        # shared extrapolation keeps the primal-dual pair
                        # consistent (momentum on w alone would decouple them)
                        ext_a = base_a + acceleration * (base_a - srvA[dd])
                        new_a = jnp.where(acceleration != 0, ext_a, base_a)
                        srvA = srvA.at[dd].set(jnp.where(eb, base_a, srvA[dd]))
                        a = jnp.where(eb, new_a, a)
                    else:
                        a = jnp.where(eb, base_a, a)
                    # a partially-present child is represented by its surviving
                    # leaves (all carrying the child's full delta), so their
                    # per-leaf coefficients scale up by |child| / |present|;
                    # fully-present children multiply by exactly 1.0
                    cnt_c = jax.ops.segment_sum(e, cids[dd],
                                                num_segments=nchildren[dd])
                    corr = (csize[dd]
                            / jnp.maximum(cnt_c, 1.0)[cids[dd]]).astype(dtype)
                    delta_w = w - snapW[dd]
                    if dd in comp_idx:
                        # error feedback: compress(delta + residual); the
                        # residual advances only for leaves that actually
                        # deliver at this event (e > 0)
                        ri = comp_idx[dd]
                        r_prev = res[ri]
                        target = delta_w.astype(jnp.float32) + r_prev
                        approx = roundtrip(dd, target)
                        e_col = (e > 0)[:, None]
                        res = (res[:ri]
                               + (jnp.where(e_col, target - approx, r_prev),)
                               + res[ri + 1:])
                        delta_w = jnp.where(comp_mask[dd],
                                            approx.astype(dtype), delta_w)
                    contrib = ((((wcoef[dd] * e) / denom) * corr)
                               .astype(dtype)[:, None] * delta_w)
                    tot = jax.ops.segment_sum(contrib, gids[dd],
                                              num_segments=ngroups[dd])
                    srv_base = srvW[dd] + tot[gids[dd]]
                    if accelerated:
                        # Nesterov-style server momentum: extrapolate along the
                        # un-extrapolated combination sequence x_t (= srv_base,
                        # kept in srvP); the leaves work from the lookahead
                        # y_t = x_t + acc (x_t - x_{t-1}).  acceleration == 0
                        # selects srv_base exactly (bit-identical to plain
                        # SDCA -- a where, not a multiply, so even signed
                        # zeros survive).
                        srv_ext = srv_base + acceleration * (
                            srv_base - srvP[dd])
                        srv_new = jnp.where(acceleration != 0, srv_ext,
                                            srv_base)
                        srvP = srvP.at[dd].set(
                            jnp.where(act[:, None], srv_base, srvP[dd]))
                    else:
                        srv_new = srv_base
                    srvW = srvW.at[dd].set(
                        jnp.where(act[:, None], srv_new, srvW[dd]))
                    w = jnp.where(eb, srv_new, w)
                    act_of[dd] = act
                # rebase deeper servers onto the shallowest live sync's result
                # (group-wide, absent leaves included): after a depth-dd pull
                # the subtree's deeper groups restart from the pulled state
                for dd in range(D - 1, -1, -1):                   # shallow wins
                    src = srvW[dd]
                    for d2 in range(dd + 1, D):
                        srvW = srvW.at[d2].set(
                            jnp.where(act_of[dd][:, None], src, srvW[d2]))
                        if accelerated:
                            # deeper momentum anchors restart from the pulled
                            # state too (zero velocity after a rebase); the
                            # alpha anchor restarts from the post-sync alpha
                            srvP = srvP.at[d2].set(
                                jnp.where(act_of[dd][:, None], src, srvP[d2]))
                            srvA = srvA.at[d2].set(
                                jnp.where(act_of[dd][:, None], a, srvA[d2]))
                # snapshot refresh is per-leaf private state: participants only.
                # Depths shallower than the leaf's shallowest attended sync
                # fast-forward to the server baseline instead: the pulled group
                # state embeds the CURRENT shallow servers (a re-joining leaf's
                # next shallow delta must not re-deliver content the server
                # already has).  Under full participation srvW == snapW, so the
                # fast-forward is a bitwise no-op.
                refb = ((ref_s * part_s[None, :]) > 0)[..., None]  # (D, n, 1)
                attended = ((jnp.max(sync_s, axis=0) * part_s) > 0)  # (n,)
                ffwd = jnp.logical_not(refb) & attended[None, :, None]
                snapA = jnp.where(refb, a[None], snapA)
                snapW = jnp.where(refb, w[None],
                                 jnp.where(ffwd, srvW, snapW))
            if record_history:
                out = jax.lax.cond(
                    hflag, lambda aw: objective(*aw),
                    lambda aw: (jnp.array(jnp.nan, dtype),
                                jnp.array(jnp.nan, dtype)),
                    (a, w))
            else:
                out = None
            carry_out = (a, w, snapA, snapW, srvW)
            if accelerated:
                carry_out = carry_out + (srvP, srvA)
            if has_comp:
                carry_out = carry_out + (res,)
            return carry_out, out

        xs = (keys, solve_mask.astype(dtype), sync_mask.astype(dtype),
              refresh_mask.astype(dtype), root_sync,
              participation.astype(dtype), steps.astype(dtype))
        carry, hist = jax.lax.scan(tick, carry0, xs)
        return carry, hist, objective

    def _init_carry(X: Array, alpha0: Array, w0_in: Array):
        """The blocked run-start carry from flat state; snapshots and the
        group servers start at the run-start state (for a cold start that
        is all-zeros, the pre-warm-start behavior).  Compressed plans
        append the per-compressed-depth error-feedback residuals (zeros at
        run start)."""
        dtype = X.dtype
        d_feat = X.shape[1]
        a0 = jnp.zeros((n * m_b,), dtype).at[flat_map].set(
            alpha0.astype(dtype)).reshape(n, m_b)
        w0 = jnp.broadcast_to(w0_in.astype(dtype)[None], (n, d_feat))
        carry = (a0, w0, jnp.broadcast_to(a0[None], (D, n, m_b)),
                 jnp.broadcast_to(w0[None], (D, n, d_feat)),
                 jnp.broadcast_to(w0[None], (D, n, d_feat)))
        if accelerated:
            # momentum anchors (srvP for w, srvA for alpha) start at the
            # run-start state: the first sync of a run (or of a resumed
            # chunk carry) extrapolates along its own first combination
            # delta
            carry = carry + (jnp.broadcast_to(w0[None], (D, n, d_feat)),
                             jnp.broadcast_to(a0[None], (D, n, m_b)))
        if has_comp:
            carry = carry + (tuple(
                jnp.zeros((n, d_feat), jnp.float32) for _ in comp_depths),)
        return carry

    def _solve(X, y, keys, alpha0, w0_in, participation, steps, lm,
               acceleration=None):
        carry0 = _init_carry(X, alpha0, w0_in)
        carry, hist, objective = _scan(X, y, keys, carry0,
                                       participation, steps, lm, acceleration)
        a, w = carry[0], carry[1]
        alpha = a.reshape(-1)[flat_map]
        if record_history:
            d0, p0 = objective(carry0[0], carry0[1])
            duals = jnp.concatenate([d0[None], hist[0]])
            primals = jnp.concatenate([p0[None], hist[1]])
            return alpha, w[0], duals, primals
        return alpha, w[0]

    if carry_state:
        if accelerated:
            def step_fn(X, y, keys, state, participation, steps, lm,
                        acceleration):
                carry, _, _ = _scan(X, y, keys, state, participation,
                                    steps, lm, acceleration)
                return carry
        else:
            def step_fn(X, y, keys, state, participation, steps, lm):
                carry, _, _ = _scan(X, y, keys, state, participation,
                                    steps, lm)
                return carry

        def finalize(state):
            return state[0].reshape(-1)[flat_map], state[1][0]

        if batched:
            # leading config axis B over (state, keys, steps, lm); X/y, the
            # participation mask, and the momentum scalar are shared across
            # the batch.  The chunk carry is DONATED: callers rebind
            # ``state = step(...)`` every chunk, so the previous chunk's
            # blocked state buffers are reused in place.
            step_axes = (None, None, 0, 0, None, 0, 0)
            if accelerated:
                step_axes = step_axes + (None,)
            return StateExecutor(
                init=jax.jit(jax.vmap(_init_carry, in_axes=(None, 0, 0))),
                step=jax.jit(jax.vmap(step_fn, in_axes=step_axes),
                             donate_argnums=(3,)),
                finalize=jax.jit(jax.vmap(finalize)))
        return StateExecutor(init=jax.jit(_init_carry),
                             step=jax.jit(step_fn, donate_argnums=(3,)),
                             finalize=jax.jit(finalize))
    if accelerated:
        def solve_acc(X, y, keys, alpha0, w0_in, participation, steps, lm,
                      acceleration):
            return _solve(X, y, keys, alpha0, w0_in, participation, steps,
                          lm, acceleration)
        if batched:
            return jax.jit(jax.vmap(
                solve_acc, in_axes=(None, None, 0, 0, 0, None, 0, 0, None)))
        return jax.jit(solve_acc)

    def solve_fn(X, y, keys, alpha0, w0_in, participation, steps, lm):
        return _solve(X, y, keys, alpha0, w0_in, participation, steps, lm)

    if batched:
        return jax.jit(jax.vmap(solve_fn,
                                in_axes=(None, None, 0, 0, 0, None, 0, 0)))
    return jax.jit(solve_fn)


def execute_plan(
    plan: TreePlan,
    X: Array,
    y: Array,
    keys,
    *,
    loss: Loss,
    lam: float,
    record_history: bool = True,
    backend: str = "vmap",
    alpha0: Array = None,
    w0: Array = None,
    participation: Array = None,
    steps: Array = None,
) -> Tuple:
    """Convenience: build/fetch the executor and run it once (``keys`` is
    the (S, n, 2) per-solve key plan from ``plan.key_plan``; ``alpha0``/
    ``w0`` warm-start the run, defaulting to the cold all-zeros state;
    ``participation`` is the (S, n) sync-attendance mask, all-ones --
    the synchronous schedule -- by default; ``steps`` the (S, n, h_max)
    runtime step mask, all-ones -- the static-H schedule -- by default).
    ``lam`` is a runtime input of the (lambda-free) cached executor, not
    a cache key."""
    from repro.core.engine.plan import full_participation, full_steps
    fn = get_host_executor(plan, loss=loss,
                           record_history=record_history, backend=backend)
    if alpha0 is None:
        alpha0 = jnp.zeros((plan.m_total,), X.dtype)
    if w0 is None:
        w0 = jnp.zeros((X.shape[1],), X.dtype)
    if participation is None:
        participation = full_participation(plan)
    if steps is None:
        steps = full_steps(plan)
    return fn(X, y, jnp.asarray(keys), alpha0, w0,
              jnp.asarray(participation), jnp.asarray(steps),
              regularizer_scale(lam, plan.m_total, X.dtype))
