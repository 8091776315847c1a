"""LM TreeSync as a mesh-backend *Method* on the schedule IR.

The paper's tree schedule (H local iterations per level, nested per-level
rounds) is method-agnostic; this module supplies the LM-training side of
the Method protocol (see ``engine.method``): the local step is one
optimizer update per replica and the per-level combine is a (masked)
mean over that level's sub-axis of the replica dim -- versus SDCA's
(dalpha, dw) aggregation in ``engine.host`` / ``engine.mesh``.

Unlike the legacy ``core.treesync.make_treesync_step`` (which bakes the
per-level periods into the trace), the step built here takes them as a
runtime ``(L,)`` int32 operand: ``cum = jnp.cumprod(periods)`` and
``(step_no % cum[level]) == 0`` produce exactly the same ``lax.cond``
structure as the legacy static path -- bit-identical at fixed periods,
zero retraces when an ``AdaptiveSchedule`` re-plans them mid-run.

Optional runtime operands (each a separate compiled variant, selected by
static flags so the plain path stays bit-identical to legacy):

  * ``masked=True``    -- a per-replica ``(R,)`` participation mask:
    participants within a sync group receive the group mean of the
    participants; absentees keep their own (stale) state and rejoin at a
    later sync, mirroring the SDCA stale-snapshot straggler semantics.
  * ``with_lr=True``   -- a traced scalar learning rate overriding the
    optimizer's built-in schedule, so an (lr x seed) sweep is one
    vmapped dispatch of one executor.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.configs.base import ModelConfig
from repro.core import compression as comp_mod
from repro.launch.mesh import axis_size
from repro.models import transformer
from repro.optim import Optimizer

PyTree = Any


# ---------------------------------------------------------------------------
# replica-stacked state (moved here from core.treesync; re-exported there)
# ---------------------------------------------------------------------------
@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["params", "opt_state", "step", "residual"], meta_fields=[])
@dataclasses.dataclass
class TreeSyncState:
    params: PyTree      # (R, ...) replica-stacked
    opt_state: PyTree   # (R, ...)
    step: jax.Array     # scalar int32
    residual: Optional[PyTree] = None  # error feedback (compressed mode)


def stack_replicas(tree: PyTree, n: int) -> PyTree:
    return jax.tree.map(
        lambda t: jnp.broadcast_to(t[None], (n,) + t.shape), tree)


def init_lm_state(cfg: ModelConfig, optimizer: Optimizer, key, n_replicas: int,
                  compression: str = "none") -> TreeSyncState:
    params = transformer.init_params(cfg, key)
    opt = optimizer.init(params)
    state = TreeSyncState(
        params=stack_replicas(params, n_replicas),
        opt_state=stack_replicas(opt, n_replicas),
        step=jnp.zeros((), jnp.int32),
    )
    if comp_mod.spec_name(*comp_mod.parse_spec(compression)) != "none":
        compressor = comp_mod.get_compressor(compression)
        state.residual = stack_replicas(
            compressor.init_residual(params), n_replicas)
    return state


def consensus_params(state: TreeSyncState, level_sizes=None) -> PyTree:
    """The fully-averaged model (what you checkpoint / serve)."""
    return jax.tree.map(lambda t: jnp.mean(t.astype(jnp.float32), axis=0),
                        state.params)


def split_batch(batch: Dict[str, jax.Array], n_replicas: int
                ) -> Dict[str, jax.Array]:
    """(B, ...) -> (R, B/R, ...)."""
    def one(t):
        B = t.shape[0]
        assert B % n_replicas == 0, (B, n_replicas)
        return t.reshape((n_replicas, B // n_replicas) + t.shape[1:])

    return {k: one(v) for k, v in batch.items()}


# ---------------------------------------------------------------------------
# per-level combine: (masked) mean over one sub-axis of the replica dim
# ---------------------------------------------------------------------------
def _mean_over_level(tree: PyTree, level_sizes: Sequence[int], level: int
                     ) -> PyTree:
    """Average the (R, ...) replica dim over sub-axis `level` of its
    (s_{L-1}, ..., s_0) factorization (level 0 = innermost/fastest)."""
    idx = len(level_sizes) - 1 - level  # position in the reshaped tuple

    def one(t):
        if t.ndim == 0 or jnp.issubdtype(t.dtype, jnp.integer):
            return t  # step counters etc: identical across replicas
        shp = t.shape
        r = t.reshape(tuple(level_sizes) + shp[1:])
        r = jnp.mean(r.astype(jnp.float32), axis=idx, keepdims=True)
        r = jnp.broadcast_to(
            r, tuple(level_sizes) + shp[1:])
        return r.reshape(shp).astype(t.dtype)

    return jax.tree.map(one, tree)


def _mean_over_prefix(tree: PyTree, level_sizes: Sequence[int], upto: int
                      ) -> PyTree:
    """Average over levels 0..upto simultaneously (one fused collective)."""
    keep = len(level_sizes) - 1 - upto  # leading dims to keep

    def one(t):
        if t.ndim == 0 or jnp.issubdtype(t.dtype, jnp.integer):
            return t
        shp = t.shape
        r = t.reshape(tuple(level_sizes) + shp[1:])
        axes = tuple(range(keep, len(level_sizes)))
        r = jnp.mean(r.astype(jnp.float32), axis=axes, keepdims=True)
        r = jnp.broadcast_to(r, tuple(level_sizes) + shp[1:])
        return r.reshape(shp).astype(t.dtype)

    return jax.tree.map(one, tree)


def _masked_mean(tree: PyTree, mask: jax.Array, level_sizes: Sequence[int],
                 axes_idx: Tuple[int, ...]) -> PyTree:
    """Masked mean over sub-axes `axes_idx` of the replica factorization:
    participants get the mean of the participants in their group, absentees
    keep their own value (stale-snapshot rejoin)."""
    L = len(level_sizes)
    m = mask.astype(jnp.float32).reshape(tuple(level_sizes))

    def one(t):
        if t.ndim == 0 or jnp.issubdtype(t.dtype, jnp.integer):
            return t
        shp = t.shape
        r = t.reshape(tuple(level_sizes) + shp[1:]).astype(jnp.float32)
        mb = m.reshape(tuple(level_sizes) + (1,) * (len(shp) - 1))
        num = jnp.sum(r * mb, axis=axes_idx, keepdims=True)
        den = jnp.maximum(jnp.sum(mb, axis=axes_idx, keepdims=True), 1.0)
        mean = jnp.broadcast_to(num / den, tuple(level_sizes) + shp[1:])
        out = jnp.where(mb > 0.0, mean, r)
        return out.reshape(shp).astype(t.dtype)

    del L
    return jax.tree.map(one, tree)


def _masked_mean_over_level(tree: PyTree, mask: jax.Array,
                            level_sizes: Sequence[int], level: int) -> PyTree:
    idx = len(level_sizes) - 1 - level
    return _masked_mean(tree, mask, level_sizes, (idx,))


def _masked_mean_over_prefix(tree: PyTree, mask: jax.Array,
                             level_sizes: Sequence[int], upto: int) -> PyTree:
    keep = len(level_sizes) - 1 - upto
    return _masked_mean(tree, mask, level_sizes,
                        tuple(range(keep, len(level_sizes))))


# ---------------------------------------------------------------------------
# the step builder
# ---------------------------------------------------------------------------
def build_lm_step(cfg: ModelConfig, optimizer: Optimizer, *,
                  level_sizes: Tuple[int, ...], compression: str = "none",
                  average_opt_state: bool = True, masked: bool = False,
                  with_lr: bool = False) -> Callable:
    """Build the (unjitted) replica-stacked LM train step.

    Signature: ``step(state, batch, periods[, participation][, lr])``
    with ``periods`` a runtime (L,) int32 array (L = len(level_sizes)),
    ``participation`` a runtime (R,) float mask (masked=True only) and
    ``lr`` a traced scalar (with_lr=True only).
    """
    L = len(level_sizes)
    use_comp = comp_mod.spec_name(*comp_mod.parse_spec(compression)) != "none"
    compressor = comp_mod.get_compressor(compression) if use_comp else None

    def local_step(params, opt_state, batch, lr):
        def loss_fn(p):
            total, metrics = transformer.forward_train(cfg, p, batch)
            return total, metrics

        with jax.named_scope("forward_backward"):
            (_, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
        with jax.named_scope("optimizer"):
            if with_lr:
                params, opt_state = optimizer.update(
                    params, grads, opt_state, lr=lr)
            else:
                params, opt_state = optimizer.update(params, grads,
                                                     opt_state)
        return params, opt_state, metrics

    vstep = jax.vmap(local_step, in_axes=(0, 0, 0, None))

    def sync_level(params, opt_state, mask, level):
        if masked:
            params = _masked_mean_over_level(params, mask, level_sizes, level)
        else:
            params = _mean_over_level(params, level_sizes, level)
        if average_opt_state:
            def avg(t):
                if t.ndim == 0:
                    return t
                if masked:
                    return _masked_mean_over_level(
                        {"x": t}, mask, level_sizes, level)["x"]
                return _mean_over_level({"x": t}, level_sizes, level)["x"]

            opt_state = jax.tree.map(avg, opt_state)
        return params, opt_state

    def compressed_outer_sync(params, residual, mask):
        """Cross-outermost-level averaging of int8/topk-compressed deltas
        with error feedback. The anchor is the current inner-level mean
        (already identical within each outer group after the inner sync)."""
        if masked:
            inner_mean = _masked_mean_over_prefix(
                params, mask, level_sizes, L - 2) if L > 1 else params
        else:
            inner_mean = _mean_over_prefix(params, level_sizes, L - 2) \
                if L > 1 else params
        delta = jax.tree.map(lambda p, a: p.astype(jnp.float32) - a.astype(
            jnp.float32), params, inner_mean)
        wire, new_residual = compressor.compress(delta, residual)
        deq = compressor.decompress(wire)
        if masked:
            avg_delta = _masked_mean_over_level(deq, mask, level_sizes, L - 1)
            avg_inner = _masked_mean_over_level(
                inner_mean, mask, level_sizes, L - 1)
        else:
            avg_delta = _mean_over_level(deq, level_sizes, L - 1)
            avg_inner = _mean_over_level(inner_mean, level_sizes, L - 1)
        new_params = jax.tree.map(
            lambda a, d, p: (a.astype(jnp.float32) + d).astype(p.dtype),
            avg_inner, avg_delta, params)
        if masked:
            # absentees keep their pre-sync params and EF residual exactly
            def keep_own(new, old):
                mb = mask.reshape((-1,) + (1,) * (old.ndim - 1))
                return jnp.where(mb > 0.0, new, old)

            new_params = jax.tree.map(
                lambda n, o: keep_own(n, o) if o.ndim > 0 else n,
                new_params, params)
            new_residual = jax.tree.map(
                lambda n, o: keep_own(n, o) if o.ndim > 0 else n,
                new_residual, residual)
        return new_params, new_residual

    def step(state, batch, periods, participation=None, lr=None):
        params, opt_state, residual = (state.params, state.opt_state,
                                       state.residual)
        params, opt_state, metrics = vstep(params, opt_state, batch, lr)
        step_no = state.step + 1
        cum = jnp.cumprod(periods.astype(jnp.int32)) if L else None
        mask = participation

        # the per-level syncs due at this step, the compressed outer one
        # included
        with jax.named_scope("tree_sync"):
            for level in range(L):
                is_outer = level == L - 1
                due = (step_no % cum[level]) == 0

                if is_outer and use_comp:
                    def do(ps, os, res):
                        ps, res = compressed_outer_sync(ps, res, mask)
                        return ps, os, res

                    def skip(ps, os, res):
                        return ps, os, res

                    params, opt_state, residual = jax.lax.cond(
                        due, do, skip, params, opt_state, residual)
                else:
                    params, opt_state = jax.lax.cond(
                        due,
                        functools.partial(sync_level, mask=mask, level=level),
                        lambda ps, os: (ps, os),
                        params, opt_state)

        new_state = TreeSyncState(params=params, opt_state=opt_state,
                                  step=step_no, residual=residual)
        mmean = jax.tree.map(lambda m: jnp.mean(m, axis=0), metrics)
        return new_state, mmean

    return step


# ---------------------------------------------------------------------------
# cached executors (one compile per (config, variant); sweeps vmap on top)
# ---------------------------------------------------------------------------
_EXECUTOR_CACHE: Dict[Tuple, Callable] = {}
_CACHE_STATS = {"hits": 0, "misses": 0}


def present_axes(mesh: Mesh, sync_axes: Sequence[str]) -> Tuple[str, ...]:
    """Mesh axes actually present (size > 1), bottom-up (fastest first)."""
    return tuple(a for a in sync_axes
                 if a in mesh.axis_names and axis_size(mesh, a) > 1)


def level_sizes_for(mesh: Mesh, sync_axes: Sequence[str]) -> Tuple[int, ...]:
    """Replica-dim factorization (s_{L-1}, ..., s_0): outermost level
    first, matching the reshape order of the (R, ...) replica dim."""
    return tuple(axis_size(mesh, a)
                 for a in reversed(present_axes(mesh, sync_axes)))


def get_lm_executor(cfg: ModelConfig, optimizer: Optimizer, *,
                    level_sizes: Tuple[int, ...], compression: str = "none",
                    average_opt_state: bool = True, masked: bool = False,
                    with_lr: bool = False, batched: bool = False) -> Callable:
    """Memoized jitted LM step. ``batched=True`` returns the fused-sweep
    variant: state/batch/periods/lr gain a leading grid dim B via vmap
    (participation stays unbatched) -- one executor, one dispatch per grid.
    """
    key = (cfg, optimizer.name, optimizer.init, optimizer.update,
           tuple(level_sizes), compression, average_opt_state, masked,
           with_lr, batched)
    hit = key in _EXECUTOR_CACHE
    _CACHE_STATS["hits" if hit else "misses"] += 1
    if hit:
        return _EXECUTOR_CACHE[key]

    step = build_lm_step(cfg, optimizer, level_sizes=tuple(level_sizes),
                         compression=compression,
                         average_opt_state=average_opt_state, masked=masked,
                         with_lr=with_lr)
    if batched:
        # (B, R, ...) state, (R, ...) shared batch, (B, L) periods, (B,) lr
        step = jax.vmap(
            step, in_axes=(0, None, 0, None, 0 if with_lr else None))
    # the state carry is dead after each step -- donate it so XLA reuses the
    # parameter/opt-state buffers in place (callers that keep a reference,
    # e.g. warm_start, must copy before stepping)
    fn = jax.jit(step, donate_argnums=(0,))
    _EXECUTOR_CACHE[key] = fn
    return fn


def lm_executor_cache_stats() -> Dict[str, int]:
    return dict(_CACHE_STATS, size=len(_EXECUTOR_CACHE))


def clear_lm_executor_cache() -> None:
    _EXECUTOR_CACHE.clear()
    _CACHE_STATS.update(hits=0, misses=0)
