"""The :class:`Session` object: Problem x Topology x Schedule -> executor.

``Session.compile`` lowers the topology once (the chunk plan: the full tree
with the root pinned to one round), fetches the memoized executor for the
chosen backend, and validates everything up front.  ``Session.run`` then
iterates that one compiled program:

  * any number of root rounds without re-tracing,
  * warm restarts (``warm_start=`` a previous result or an ``(alpha, w)``
    pair) that bit-reproduce one longer run when continued with the
    returned ``next_key``, with the history's round/time axes continuing
    where the previous run stopped,
  * streamed history (``on_round=`` fires after every root round, not just
    at the end),
  * straggler-adaptive async execution (``straggler=`` a
    :class:`~repro.runtime.straggler.StragglerPolicy`): per chunk, sampled
    per-leaf link delays decide which leaves the barrier drops; dropped
    leaves keep solving on stale snapshots and re-join later (participation
    masks, see ``repro.core.engine.plan``), and the history records the
    simulated async wall-clock next to the synchronous-equivalent one.

All three backends sit behind ``backend=``: ``"vmap"`` (host XLA),
``"pallas"`` (blocked-SDCA leaf kernel), ``"mesh"`` (``shard_map`` device
program; level-homogeneous topologies).  Chunking is exact, not
approximate: every root round ends with a root sync that refreshes every
snapshot, so (state, RNG-chain) is a complete carry and the chunked
iterates are bit-identical to the monolithic program's.
"""
from __future__ import annotations

import contextlib
import functools
from math import prod
from typing import Callable, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import plan_check, trace_guard as guard_mod
from repro.core import dual as dual_mod
from repro.core import tree as tree_mod
from repro.core.engine import host as host_mod
from repro.core.engine import mesh as mesh_mod
from repro.core.engine import plan as plan_mod
from repro.core.engine.method import get_method
from repro.core.instrument import SolveResult, record_round, span
from repro.api.problem import Problem
from repro.api.schedule import (
    ResolvedSchedule, Schedule, leaf_h_spec, runtime_tree)
from repro.api.topology import Topology

Array = jax.Array

BACKENDS = ("vmap", "pallas", "mesh")


# lam is a TRACED scalar: lambda sweeps hit one compiled objective instead
# of retracing per value (only the loss object stays static); jit here is
# deliberate -- history recording is outside the engine's dispatch path
@functools.partial(jax.jit, static_argnames=("loss",))  # analysis: allow(jit-outside-engine)
def _objective(alpha: Array, X: Array, y: Array, loss, lam):
    w = dual_mod.w_of_alpha(alpha, X, lam)
    return (dual_mod.dual_value(alpha, X, y, loss, lam),
            dual_mod.primal_value(w, X, y, loss, lam))


def materialize_history(history) -> None:
    """Pull a deferred history's objective values to the host in ONE
    explicit ``jax.device_get`` (legal even under the strict host-sync
    guard, which blocks only IMPLICIT transfers).  ``Session.run`` records
    device scalars and calls this at stream/checkpoint/exit points; the
    sweep layer's sequential path defers further and materializes every
    member's history together, outside the member loop."""
    pending = [e for e in history if not isinstance(e["dual"], float)]
    if not pending:
        return
    vals = jax.device_get([(e["dual"], e["primal"]) for e in pending])
    for e, (dv, pv) in zip(pending, vals, strict=True):
        # recompute the gap as a host float64 subtraction so the entry is
        # bit-identical to eagerly-recorded histories
        e["dual"], e["primal"] = float(dv), float(pv)
        e["gap"] = e["primal"] - e["dual"]


class Session:
    """A compiled (problem, topology, schedule, backend) binding.

    Construct with :meth:`compile`; executors are memoized at the engine
    layer (plan fingerprint x loss x flags -- lambda is a RUNTIME input of
    the compiled program, not a cache key), so compiling the same
    configuration twice, or with a different lambda, reuses one jit
    program -- see :meth:`cache_stats`.
    """

    def __init__(self, problem: Problem, topology: Topology,
                 resolved: ResolvedSchedule, backend: str, plan, fn,
                 mesh=None, mesh_axes=None, mesh_use_kernel: bool = True,
                 mesh_sync: str = "psum",
                 acceleration: Optional[float] = None):
        self.problem = problem
        self.topology = topology
        self.resolved = resolved
        self.backend = backend
        self.plan = plan
        self._fn = fn
        self.fitted_C = None        # set when DelayModel(C="auto") calibrated
        self._guard = None          # TraceGuard when compiled strict
        self._mesh = mesh
        self._mesh_axes = mesh_axes
        self._mesh_use_kernel = mesh_use_kernel
        self._mesh_sync = mesh_sync
        # None = plain "sdca"; a float (0.0 included) = the "sdca_acc"
        # method with this server-momentum coefficient as the default
        # runtime operand
        self.acceleration = acceleration
        # how the engine's leaf kernel packs the leaves, for the run span
        self._kernel_stats = (
            mesh_mod.kernel_stats(plan, mesh_use_kernel) if backend == "mesh"
            else host_mod.kernel_stats(plan, backend))
        if backend == "mesh":
            from jax.sharding import NamedSharding, PartitionSpec as P
            spec = P(tuple(reversed(mesh_axes)))
            sh = NamedSharding(mesh, spec)
            n, m_b = plan.n_leaves, plan.m_b
            self._spec_sharding = sh
            self._Xs = jax.device_put(
                problem.X.reshape(n, m_b, problem.d), sh)
            self._ys = jax.device_put(problem.y.reshape(n, m_b), sh)

    # ------------------------------------------------------------------
    @classmethod
    def compile(
        cls,
        problem: Problem,
        topology: Topology,
        schedule: Optional[Schedule] = None,
        *,
        backend: str = "vmap",
        mesh=None,
        mesh_axes: Optional[Sequence[str]] = None,
        mesh_use_kernel: bool = True,
        mesh_sync: str = "psum",
        strict=False,
    ) -> "Session":
        """Lower ``topology`` under ``schedule`` and bind the ``backend``
        executor.  ``mesh``/``mesh_axes`` (axes innermost-first, as in
        ``engine.mesh``) and ``mesh_use_kernel`` (Pallas vs pure-jnp leaf
        solver) apply to ``backend="mesh"`` only; when the mesh is omitted,
        one matching the plan's per-depth fan-outs is built from the
        available devices.

        ``mesh_sync`` selects the mesh sync lowering: ``"psum"``
        (replicated server state, bit-identical to the host backends) or
        ``"reduce_scatter"`` (server state sharded across each sync
        group's devices -- per-device server memory drops from ``O(L*d)``
        to ``O(L*d/K)``, the big-``d`` path; full participation only, so
        it composes with compression but not with ``straggler=``).

        A non-SDCA problem (``Problem.lm(...)``) dispatches by its
        ``method`` marker to that method's session type (the plan IR is
        method-agnostic; the Method supplies local step + combine).

        ``strict`` (bool, or a :class:`repro.analysis.TraceGuard`) turns
        the run loop's performance contract into errors: an unexpected
        executor-cache miss raises ``UnexpectedRetraceError`` with a
        structured diff of the offending cache key, implicit host
        transfers inside the dispatch region raise ``HostSyncError``
        (from the second chunk on -- the first chunk's builds legally
        upload constants), and ``TraceGuard(sanitize=True)`` checks the
        chunk carry for NaN/Inf every round.  The plan-IR verifier
        (``repro.analysis.verify_plan``) runs on EVERY compile, strict
        or not."""
        if getattr(problem, "method", "sdca") not in ("sdca", None):
            from repro.api.lm import LMSession
            return LMSession.compile(problem, topology, schedule,
                                     backend=backend, mesh=mesh,
                                     strict=strict)
        if backend not in BACKENDS:
            raise ValueError(f"unknown backend {backend!r}; use {BACKENDS}")
        schedule = schedule or Schedule()
        if problem.m != topology.m_total:
            raise ValueError(
                f"problem has m={problem.m} examples but the topology "
                f"assigns {topology.m_total}")
        fitted_C = None
        if (schedule.rounds == "auto" and schedule.delay is not None
                and getattr(schedule.delay, "C", None) == "auto"):
            # only rounds="auto" consumes the DelayModel; an explicit-rounds
            # schedule would ignore the fitted C, so don't pay the pilot
            schedule, fitted_C = _calibrate_C(problem, topology, schedule)
        resolved = schedule.resolve(topology)
        # Schedule(acceleration=) selects the accelerated method flavor --
        # a structural executor variant; the coefficient itself stays a
        # runtime operand of the compiled programs
        acceleration = schedule.acceleration
        method = get_method("sdca_acc" if acceleration is not None
                            else "sdca")
        plan = plan_mod.compile_tree(resolved.chunk_tree,
                                     weighting=resolved.weighting,
                                     compression=resolved.compression)
        # every compiled plan passes the structural verifier (geometry,
        # schedule coherence, aggregation convexity, compression specs,
        # RNG schedule-independence, fingerprint soundness) BEFORE an
        # executor is built against it
        plan_check.verify_plan(plan)
        guard = guard_mod.as_trace_guard(strict)

        if backend in ("vmap", "pallas"):
            fn = method.executor(
                plan=plan, backend=backend, loss=problem.loss,
                record_history=False)
            sess = cls(problem, topology, resolved, backend, plan, fn,
                       acceleration=acceleration)
            sess.fitted_C = fitted_C
            sess._guard = guard
            return sess

        # ---- mesh backend -------------------------------------------
        if plan.levels is None:
            raise ValueError(
                "backend='mesh' needs a level-homogeneous topology "
                "(uniform per-depth fan-out/rounds, congruent leaves)")
        if resolved.weighting != "uniform":
            raise ValueError("backend='mesh' supports weighting='uniform'")
        if mesh_sync not in mesh_mod.SYNC_MODES:
            raise ValueError(f"unknown mesh_sync {mesh_sync!r}; use "
                             f"{mesh_mod.SYNC_MODES}")
        D = plan.depth
        if mesh is None:
            sizes = [plan.levels[d].group_size for d in range(D)]  # top-down
            names = tuple(f"lvl{d}" for d in range(D))
            need = prod(sizes)
            have = len(jax.devices())
            if have < need:
                raise RuntimeError(
                    f"backend='mesh' needs {need} devices for fan-outs "
                    f"{sizes}, have {have} (set "
                    "XLA_FLAGS=--xla_force_host_platform_device_count=N on "
                    "CPU, or pass mesh=)")
            mesh = mesh_mod.make_mesh(sizes, names,
                                      devices=jax.devices()[:need])
            mesh_axes = tuple(reversed(names))       # innermost first
        elif mesh_axes is None:
            raise ValueError("pass mesh_axes (innermost level first) "
                             "together with an explicit mesh")
        else:
            mesh = mesh_mod.auto_axes(mesh)
        fn = method.executor(
            plan=plan, backend="mesh", mesh=mesh, axes=tuple(mesh_axes),
            loss=problem.loss, use_kernel=mesh_use_kernel, sync=mesh_sync)
        sess = cls(problem, topology, resolved, backend, plan, fn,
                   mesh=mesh, mesh_axes=tuple(mesh_axes),
                   mesh_use_kernel=mesh_use_kernel, mesh_sync=mesh_sync,
                   acceleration=acceleration)
        sess.fitted_C = fitted_C
        sess._guard = guard
        return sess

    # ------------------------------------------------------------------
    @property
    def level_plan(self):
        """The eq.-(12) planner output when the schedule was ``"auto"``."""
        return self.resolved.level_plan

    @property
    def default_rounds(self) -> int:
        return self.resolved.rounds

    @property
    def bytes_per_round(self) -> float:
        """Simulated uplink bytes one root round ships under this plan's
        per-edge compression (``engine.plan.plan_bytes_per_round``) -- the
        quantity the delay model's bandwidth terms charge; compare against
        an uncompressed session of the same topology for the wire saving."""
        return plan_mod.plan_bytes_per_round(
            self.plan, self.problem.d,
            dtype_bytes=self.problem.X.dtype.itemsize)

    @staticmethod
    def cache_stats() -> dict:
        """Engine-layer executor-cache counters (hits/misses/size)."""
        return host_mod.executor_cache_stats()

    # ------------------------------------------------------------------
    def run(
        self,
        rounds: Optional[int] = None,
        *,
        key: Optional[Array] = None,
        warm_start: Union[SolveResult, Tuple[Array, Array], None] = None,
        record_history: bool = True,
        history_every: int = 1,
        on_round: Optional[Callable[[dict], None]] = None,
        straggler=None,
        lam: Optional[float] = None,
        local_h=None,
        acceleration: Optional[float] = None,
        checkpoint=None,
        _ef_state=None,
        _history_prefix=(),
        _defer_history: bool = False,
        _final_save: bool = True,
    ) -> SolveResult:
        """Run ``rounds`` root rounds (default: the schedule's).

        ``warm_start`` continues from a previous state; passing the previous
        :class:`SolveResult` also continues its RNG chain (``next_key``)
        unless ``key`` overrides it, making split runs bit-identical to one
        long run -- and continues the history's round/time axes, so split
        histories concatenate into one monotone series.  ``on_round(entry)``
        streams each history entry as it is produced (requires
        ``record_history=True``).

        ``history_every=k`` records only every k-th root round (plus the
        initial state and ALWAYS the final round), so very long runs don't
        pay the per-round objective evaluation; the iterates are unaffected.

        ``lam`` overrides the problem's regularization for THIS run:
        lambda is a runtime input of the cached executors (not a compile
        key), so running a whole regularization grid through one session
        never retraces -- :meth:`sweep` batches exactly this.  Warm
        starting from a :class:`SolveResult` produced under a DIFFERENT
        lambda rebuilds the primal from the dual (``w = X^T alpha /
        (lam m)``, the eq.-(13) invariant) automatically; a plain
        ``(alpha, w)`` pair is taken as-is, so rebuild ``w`` yourself
        when crossing lambdas.

        ``local_h`` overrides the LOCAL iteration count for this run -- a
        scalar or a per-leaf sequence.  The schedule is a runtime input of
        the cached executors (a step mask gating trailing coordinate
        steps; draws always cover the compiled per-leaf H capacity, so the
        RNG stream is schedule-independent): running many H values through
        one session never retraces.  Values are clamped to the compiled
        capacity -- compile with ``Schedule(h_cap=...)`` for headroom.
        Default: the schedule's own runtime H (``resolved.runtime_h``)
        when an ``h_cap`` was declared, else the full compiled H
        (bit-identical to the static program).

        ``straggler`` (a :class:`~repro.runtime.straggler.StragglerPolicy`)
        switches the run to straggler-adaptive async execution: each chunk,
        the policy samples per-leaf sync delays from the topology's nominal
        link delays, drops straggling leaves from the barrier (bounded
        consecutive skips; dropped leaves keep solving on stale snapshots
        and re-join with renormalized weights), and the history's ``time``
        axis accrues the simulated *async* wall-clock, with the
        synchronous-equivalent time in ``time_sync`` and the participant
        count in ``participants``.  The final chunk always runs a full
        barrier so the returned iterates satisfy ``w = A alpha``.  An
        always-participate policy is bit-identical to the synchronous
        run.  When the policy carries an ``adaptive=AdaptiveSchedule``,
        its replanned H is fed back into the NEXT chunk's step-mask
        operand (clamped to the compiled capacity): the session replans
        with ZERO retraces, and each chunk's executed H is recorded in the
        history (``"h"``).

        ``checkpoint`` (a directory path or a
        :class:`~repro.runtime.fault.CheckpointPolicy`) snapshots the
        exact chunk carry every ``policy.every`` root rounds (plus always
        the final round): flat (alpha, w), the advanced root RNG key and
        any error-feedback residuals, with enough metadata (plan
        fingerprint, round/time cursors, lambda, local_h, recorded
        history) that :meth:`resume` restarts bit-identically on ANY
        backend -- including a mesh with a different device count.
        Checkpointing composes with compression but not with
        ``straggler=`` (a mid-run blocked state under skipped syncs holds
        divergent per-leaf replicas the flat payload cannot represent).
        ``acceleration`` overrides the server-momentum coefficient for
        THIS run (sessions compiled with ``Schedule(acceleration=...)``
        only): the coefficient is a runtime scalar operand of the
        ``sdca_acc`` executors, so sweeping it never retraces, and ``0``
        is bit-identical to the plain method.  Accelerated runs thread
        the executors' full blocked state (the per-depth momentum
        anchors) across chunks; they compose with compression but not
        with ``straggler=`` or ``checkpoint=``.

        ``_ef_state`` / ``_history_prefix`` / ``_final_save`` are
        :meth:`resume`'s private restore hooks; ``_defer_history`` leaves
        the recorded entries' objective values as device scalars for the
        caller to materialize in one batch (:func:`materialize_history`
        -- the sweep layer's sequential path)."""
        T = self.resolved.rounds if rounds is None else int(rounds)
        if T < 0:
            raise ValueError(f"rounds must be >= 0, got {T}")
        with span("Session.run", rounds=T, backend=self.backend,
                  **self._kernel_stats) as sp:
            return self._run(
                sp, T, key=key, warm_start=warm_start,
                record_history=record_history, history_every=history_every,
                on_round=on_round, straggler=straggler, lam=lam,
                local_h=local_h, acceleration=acceleration,
                checkpoint=checkpoint, _ef_state=_ef_state,
                _history_prefix=_history_prefix,
                _defer_history=_defer_history, _final_save=_final_save)

    def _run(self, sp, T, *, key, warm_start, record_history, history_every,
             on_round, straggler, lam, local_h, acceleration, checkpoint,
             _ef_state, _history_prefix, _defer_history, _final_save
             ) -> SolveResult:
        """:meth:`run`'s body, inside its ``Session.run`` span ``sp``: the
        stages below get spans of their own, and ``sp`` gets the call's
        first round, recorded objective calls and host-to-device bytes."""
        every = int(history_every)
        if every < 1:
            raise ValueError(f"history_every must be >= 1, got {every}")
        X, y = self.problem.X, self.problem.y
        loss = self.problem.loss
        lam = self.problem.lam if lam is None else float(lam)
        m = self.problem.m

        accelerated = self.acceleration is not None
        if acceleration is not None and not accelerated:
            raise ValueError(
                "this session runs the plain 'sdca' method; compile with "
                "Schedule(acceleration=...) to bind the accelerated "
                "executors (the coefficient itself is then a runtime "
                "operand)")
        acc_run = self.acceleration if acceleration is None \
            else float(acceleration)
        if accelerated and not 0.0 <= float(acc_run) <= 1.0:
            raise ValueError(
                f"acceleration must be in [0, 1], got {acc_run}")
        if accelerated and straggler is not None:
            raise ValueError(
                "acceleration does not compose with straggler=: a skipped "
                "sync leaves the momentum anchors extrapolating against "
                "stale combination states, which breaks the paired "
                "primal-dual consistency; run accelerated sessions "
                "synchronously")
        if accelerated and checkpoint is not None:
            raise ValueError(
                "acceleration does not compose with checkpoint=: the "
                "per-depth momentum anchors are part of the chunk carry "
                "but not of the flat (alpha, w, residuals) snapshot "
                "payload, so a resumed run would diverge")

        with span("Session.start_state"):
            alpha, w, k = self._start_state(warm_start, key, lam)
        K_root = len(self.resolved.chunk_tree.children)
        chunk_tree, plan = self.resolved.chunk_tree, self.plan
        h_run = local_h if local_h is not None else self.resolved.runtime_h
        dt = self.resolved.round_time_for(h_run)

        # warm restarts continue the history axes instead of resetting the
        # clock to zero and duplicating the warm state as a round-0 entry
        t0_round, t0_time = 0, 0.0
        record_initial = True
        if isinstance(warm_start, SolveResult) and warm_start.history:
            t0_round = int(warm_start.history[-1]["round"])
            t0_time = float(warm_start.history[-1]["time"])
            record_initial = False
        sp.set_metadata(first_round=t0_round + 1)

        ckpt_mgr, ck_every, k_cur = None, 0, k
        ckpt_pending, k_lag = None, 0
        if checkpoint is not None:
            if straggler is not None:
                raise ValueError(
                    "checkpoint= does not compose with straggler=: a "
                    "mid-run blocked state under skipped syncs holds "
                    "divergent per-leaf replicas and stale snapshots the "
                    "flat chunk-carry payload cannot represent; checkpoint "
                    "synchronous (or compressed) runs only")
            from repro.runtime import fault as fault_mod
            _, ckpt_mgr, ck_every = fault_mod.bind_policy(
                checkpoint, self.resolved)
            h_meta = None if local_h is None else \
                np.asarray(local_h).tolist()

        mesh = self.backend == "mesh"
        if (straggler is not None and mesh
                and self._mesh_sync == "reduce_scatter"):
            raise ValueError(
                "mesh_sync='reduce_scatter' assumes full participation "
                "(the sharded-server sync has no per-leaf gating); use "
                "mesh_sync='psum' for straggler-adaptive runs")
        state_exec = None
        if straggler is not None:
            t_compute = tree_mod.strip_delays(
                runtime_tree(chunk_tree, h_run)).solve_time()
            t_lp = max([l.t_lp for l in chunk_tree.leaves()])
            straggler.bind(self.topology.leaf_sync_delays(), t_compute,
                           t_lp=t_lp)
        guard = self._guard
        # the flat (alpha, w) pair is not a complete carry once leaves can
        # skip syncs (absent leaves keep divergent replicas and stale
        # snapshots), once edges compress (error-feedback residuals must
        # persist across root rounds), or once the server combine carries
        # momentum (the per-depth anchors outlive root-round boundaries),
        # so such runs thread the executors' full blocked state across
        # chunks instead.  Under strict mode the fetch is budgeted ONE
        # miss (the first state-carry run builds; later runs must hit).
        if straggler is not None or plan.has_compression or accelerated:
            with (guard.retrace_region(1) if guard is not None
                  and guard.error_on_retrace else contextlib.nullcontext()):
                if mesh:
                    state_exec = mesh_mod.get_mesh_executor(
                        plan, self._mesh, axes=self._mesh_axes,
                        loss=self.problem.loss,
                        use_kernel=self._mesh_use_kernel, carry_state=True,
                        sync=self._mesh_sync, accelerated=accelerated)
                else:
                    state_exec = host_mod.get_host_executor(
                        plan, loss=self.problem.loss,
                        record_history=False, backend=self.backend,
                        carry_state=True, accelerated=accelerated)
        if guard is not None and guard.error_on_retrace:
            # strict revalidation: the compiled program this session bound
            # at compile time must still be cache-resident -- a re-fetch
            # has a ZERO miss budget, so an LRU eviction (or a fingerprint
            # that drifted mid-session) raises here instead of silently
            # rebuilding inside the chunk loop
            method_name = "sdca_acc" if accelerated else "sdca"
            with guard.retrace_region(0):
                if mesh:
                    get_method(method_name).executor(
                        plan=plan, backend="mesh", mesh=self._mesh,
                        axes=self._mesh_axes, loss=self.problem.loss,
                        use_kernel=self._mesh_use_kernel,
                        sync=self._mesh_sync)
                else:
                    get_method(method_name).executor(
                        plan=plan, backend=self.backend,
                        loss=self.problem.loss, record_history=False)
        history: list = []
        clock = {"async": t0_time, "sync": t0_time}

        # history recording is DEFERRED: entries hold the objective's
        # device scalars (the tiny _objective dispatch queues behind the
        # chunk dispatches) and one EXPLICIT jax.device_get materializes
        # them -- at stream points (on_round), at checkpoint-metadata
        # builds, and once at run end -- instead of an implicit float()
        # sync per recorded round.  Under strict mode the record call runs
        # INSIDE the host-sync guard, so a reintroduced implicit transfer
        # raises HostSyncError.
        def record(t: int, a_flat: Array, extra: Optional[dict] = None):
            if not record_history:
                return
            dv, pv = _objective(a_flat, X, y, loss, float(lam))
            time = clock["async"] if straggler is not None else \
                t0_time + t * dt
            record_round(history, t0_round + t, time, dv, pv)
            if extra:
                history[-1].update(extra)
            if on_round is not None:
                materialize_history(history)     # streaming needs host values
                on_round(history[-1])

        # the runtime schedule: a step mask per chunk.  Loop-invariant
        # unless an adaptive straggler policy replans H mid-run -- then
        # only this INPUT array changes, never the compiled program.
        def steps_dev(h):
            arr = plan_mod.full_steps(plan) if h is None else \
                plan_mod.steps_for_h(plan, h)
            if mesh:
                return jax.device_put(
                    jnp.asarray(arr.transpose(1, 0, 2), X.dtype),
                    self._spec_sharding)
            return jnp.asarray(arr)

        def h_effective(h):
            """Per-leaf step counts a chunk actually runs (clamped to the
            compiled capacity, per-slot specs reduced to their max)."""
            if h is None:
                return plan.leaf_h.astype(np.int64)
            return np.minimum(leaf_h_spec(h, plan.n_leaves), plan.leaf_h)

        with span("Session.operands"):
            lm_in = host_mod.regularizer_scale(lam, m, X.dtype)
            # the momentum coefficient is a RUNTIME operand of the sdca_acc
            # executors: converted once here, never part of a cache key
            acc_args = (jnp.asarray(float(acc_run), X.dtype),) \
                if accelerated else ()
            if mesh:
                a_carry = jnp.asarray(alpha, X.dtype).reshape(
                    plan.n_leaves, plan.m_b)
            else:
                a_carry = jnp.asarray(alpha, X.dtype)
            w = jnp.asarray(w, X.dtype)
            # the all-ones mask is loop-invariant: convert (and, on mesh,
            # device_put) it once instead of per round
            if mesh:
                part_ones = jax.device_put(
                    jnp.asarray(plan_mod.full_participation(plan),
                                X.dtype).T, self._spec_sharding)
            else:
                part_ones = jnp.asarray(plan_mod.full_participation(plan))
            steps_now = steps_dev(h_run)
            state = None
            if state_exec is not None:
                state = state_exec.init(X, a_carry, w)
                if _ef_state:
                    # restore path: substitute the checkpointed
                    # error-feedback residuals (the one piece of the
                    # blocked carry that does not collapse into (alpha, w)
                    # at a root-round boundary)
                    from repro.runtime import fault as fault_mod
                    state = fault_mod.with_ef_residuals(self, state,
                                                        _ef_state)
        # host-to-device bytes of this call's converted operands (shapes
        # only: nothing here reads an array)
        upload = (lm_in.nbytes + sum(a.nbytes for a in acc_args)
                  + part_ones.nbytes + steps_now.nbytes)
        h_eff_now = h_effective(h_run)
        h_now = int(h_eff_now.max())
        adaptive = straggler is not None and \
            getattr(straggler, "adaptive", None) is not None
        next_h = None

        # strict mode: by loop entry every executor is cached (compile
        # built them, the revalidation above proved it), so each chunk
        # dispatch runs under a ZERO-miss retrace budget; the host-sync
        # guard starts at the second chunk (the first call's jit compile
        # legally uploads baked constants)
        def _dispatch_ctx(t):
            if guard is None:
                return contextlib.nullcontext()
            stack = contextlib.ExitStack()
            if guard.error_on_retrace:
                stack.enter_context(guard.retrace_region())
            if guard.guard_host_sync and t > 1:
                stack.enter_context(guard.dispatch_region())
            return stack

        # all rounds' keys in one walk of the equivalent monolithic tree
        # (the legacy chain), so the chunk loop does no host RNG work
        with span("Session.key_plan"):
            keys_all = plan_mod.chunked_key_plan(chunk_tree, plan, k, T)
        if record_initial:
            with span("Session.record", round=t0_round):
                record(0, a_carry.reshape(m) if mesh else a_carry)
        for t in range(1, T + 1):
            r_no = t0_round + t
            keys = keys_all[t - 1]
            extra = None
            prt = part_ones
            # apply last chunk's adaptive H suggestion (observed-delay
            # replanning feeds the NEXT chunk): a new input array only.
            # Compared on the EFFECTIVE per-leaf counts so a scalar
            # suggestion always replaces a heterogeneous mask, and the
            # policy's simulated compute clock is retimed to the new H.
            if next_h is not None:
                eff_next = h_effective(next_h)
                if not np.array_equal(eff_next, h_eff_now):
                    h_eff_now = eff_next
                    h_now = int(eff_next.max())
                    steps_now = steps_dev(next_h)
                    upload += steps_now.nbytes
                    straggler.retime(tree_mod.strip_delays(
                        runtime_tree(chunk_tree, next_h)).solve_time())
                next_h = None
            # history decimation: every k-th round, plus always the last
            rec_now = record_history and (t % every == 0 or t == T)
            if straggler is not None:
                step = straggler.step(final=(t == T))
                part = plan_mod.chunk_participation(plan, step.mask)
                prt = jax.device_put(
                    jnp.asarray(part, X.dtype).T, self._spec_sharding) \
                    if mesh else jnp.asarray(part)
                upload += prt.nbytes
                clock["async"] += step.dt_async
                clock["sync"] += step.dt_sync
                extra = {"time_sync": clock["sync"],
                         "participants": int(step.mask.sum())}
                if adaptive:
                    extra["h"] = h_now
                    if step.h_suggest is not None:
                        next_h = int(min(max(step.h_suggest, 1),
                                         plan.h_max))
            # operand conversion stays OUTSIDE the guarded region: inside
            # it every implicit host transfer is an error
            with span("Session.key_upload", round=r_no):
                kys = jax.device_put(
                    jnp.asarray(keys.transpose(1, 0, 2)),
                    self._spec_sharding) if mesh else jnp.asarray(keys)
            upload += keys.nbytes
            with _dispatch_ctx(t):
                with span("Session.dispatch", round=r_no):
                    if mesh and state_exec is None:
                        a_carry, wrows = self._fn(self._Xs, self._ys,
                                                  a_carry, w, kys, prt,
                                                  steps_now, lm_in)
                        w = wrows[0]
                    elif mesh:
                        state = state_exec.step(self._Xs, self._ys, state,
                                                kys, prt, steps_now, lm_in,
                                                *acc_args)
                    elif state_exec is None:
                        a_carry, w = self._fn(X, y, kys, a_carry, w,
                                              prt, steps_now, lm_in)
                    else:
                        state = state_exec.step(X, y, kys, state,
                                                prt, steps_now, lm_in,
                                                *acc_args)
                if rec_now:
                    with span("Session.record", round=r_no):
                        if state_exec is None:
                            a_rec = a_carry
                        elif mesh:
                            a_rec = state[0]
                        else:
                            a_rec = state_exec.finalize(state)[0]
                        record(t, a_rec.reshape(m) if mesh else a_rec,
                               extra)
            if guard is not None and guard.sanitize:
                guard.check_carry(
                    state if state_exec is not None else (a_carry, w),
                    f"chunk[{t}]")
            if ckpt_mgr is not None:
                k_lag += 1
                # period alignment is on the GLOBAL round cursor, so a
                # resumed leg checkpoints at the same rounds the
                # uninterrupted run would have
                if ((t0_round + t) % ck_every == 0
                        or (t == T and _final_save)):
                    from repro.runtime import fault as fault_mod
                    # the RNG chain advances lazily: one dispatch per
                    # snapshot instead of one per round (a handful of
                    # static lag values -> a handful of compiles)
                    k_cur = plan_mod.advance_root_key(k_cur, k_lag, K_root)
                    k_lag = 0
                    if state_exec is not None:
                        af, wf = state_exec.finalize(state)
                    else:
                        af, wf = a_carry, w
                    payload = {
                        "alpha": af.reshape(m) if mesh else af,
                        "w": wf,
                        "key": k_cur,
                        # the carry is donated on the next chunk step, and
                        # this payload outlives it (the write lags one
                        # period) -- copy the residual leaves out first
                        "res": jax.tree.map(
                            jnp.copy, fault_mod.ef_residuals(self, state)),
                    }
                    # snapshot metadata is JSON: materialize any deferred
                    # device scalars in the recorded history first
                    materialize_history(history)
                    meta = {
                        "version": fault_mod.PAYLOAD_VERSION,
                        "round": t0_round + t,
                        "sim_time": t0_time + t * dt,
                        "rounds_total": t0_round + T,
                        "lam": float(lam),
                        "m": int(m), "d": int(self.problem.d),
                        "plan": plan.fingerprint,
                        "local_h": h_meta,
                        "history": list(_history_prefix) + history,
                    }
                    # the write lags one period: payload leaves stay device
                    # arrays until the NEXT snapshot point, when they have
                    # long materialized -- the host transfer never stalls
                    # the async round-dispatch pipeline
                    if ckpt_pending is not None:
                        ckpt_mgr.save(*ckpt_pending)
                    ckpt_pending = (t0_round + t, payload, meta)
        with span("Session.advance_key"):
            k = plan_mod.advance_root_key(k, T, K_root)
        if ckpt_mgr is not None:
            if ckpt_pending is not None:
                ckpt_mgr.save(*ckpt_pending)
            ckpt_mgr.wait()       # surface async-save failures before exit

        if state_exec is not None:
            alpha_out, w = state_exec.finalize(state)
            if mesh:
                alpha_out = alpha_out.reshape(m)
        else:
            alpha_out = a_carry.reshape(m) if mesh else a_carry
        if not _defer_history:
            with span("Session.materialize"):
                materialize_history(history)
        sp.set_metadata(recorded=len(history), upload_bytes=upload)
        return SolveResult(alpha=alpha_out, w=w, history=history,
                           next_key=k, lam=lam)

    # ------------------------------------------------------------------
    def resume(
        self,
        checkpoint,
        *,
        rounds: Optional[int] = None,
        record_history: bool = True,
        history_every: int = 1,
        on_round: Optional[Callable[[dict], None]] = None,
        lam: Optional[float] = None,
        local_h=None,
        _final_save: bool = True,
    ) -> SolveResult:
        """Restart a checkpointed solve from its newest complete snapshot,
        bit-identically to the uninterrupted run.

        ``checkpoint`` is the directory (or
        :class:`~repro.runtime.fault.CheckpointPolicy`) a previous
        ``run(checkpoint=...)`` wrote.  The restored payload is
        backend-portable: a carry saved by a vmap session resumes on a
        pallas or mesh session of the SAME problem/topology/schedule (the
        plan fingerprint is validated) -- on mesh the flat state is
        re-sharded onto the *current* mesh, so the device count may
        differ from the saving process.  Runs the remaining rounds
        (``rounds_total - step``, or ``rounds=`` to override), continues
        checkpointing into the same directory, and returns a result whose
        history is the full concatenated series from round 0.  ``lam`` /
        ``local_h`` default to the values recorded at save time -- only
        override them with the values the original run used if you want
        bit-identity."""
        from repro.runtime import fault as fault_mod
        policy, mgr, _ = fault_mod.bind_policy(checkpoint, self.resolved)
        step = mgr.latest_step()
        if step is None:
            raise FileNotFoundError(
                f"no complete checkpoints under {policy.directory!r}")
        meta = mgr.metadata(step)
        if meta.get("plan") != self.plan.fingerprint:
            raise ValueError(
                "checkpoint was written under a different plan "
                "(topology/schedule/weighting/compression changed between "
                "save and resume); compile a matching session")
        m, d = self.problem.m, self.problem.d
        if int(meta["m"]) != m or int(meta["d"]) != d:
            raise ValueError(
                f"checkpoint is for an (m={meta['m']}, d={meta['d']}) "
                f"problem; this session has (m={m}, d={d})")
        template = fault_mod.payload_template(
            self.plan, m, d, self.problem.X.dtype)
        step, payload = mgr.restore(template, step)
        remaining = int(meta["rounds_total"]) - step if rounds is None \
            else int(rounds)
        if remaining < 0:
            raise ValueError(f"rounds must be >= 0, got {remaining}")
        lam_run = float(meta["lam"]) if lam is None else float(lam)
        h_run = meta.get("local_h") if local_h is None else local_h
        prefix = [dict(e) for e in meta.get("history", [])]
        # the warm-start anchor continues the round/time axes from the
        # restored cursor (NOT from the last recorded entry -- decimation
        # may have skipped the checkpoint round)
        anchor = {"round": step, "time": float(meta["sim_time"]),
                  "dual": float("nan"), "primal": float("nan"),
                  "gap": float("nan")}
        ws = SolveResult(
            alpha=jnp.asarray(payload["alpha"]),
            w=jnp.asarray(payload["w"]),
            history=[anchor],
            next_key=jnp.asarray(np.asarray(payload["key"], np.uint32)),
            lam=lam_run)
        out = self.run(remaining, warm_start=ws,
                       record_history=record_history,
                       history_every=history_every, on_round=on_round,
                       lam=lam_run, local_h=h_run, checkpoint=policy,
                       _ef_state=[np.asarray(r) for r in payload["res"]],
                       _history_prefix=prefix, _final_save=_final_save)
        out.history = prefix + out.history
        return out

    # ------------------------------------------------------------------
    def straggler_policy(self, *, seed: int = 0, adaptive=None, **kw):
        """The :class:`~repro.runtime.straggler.StragglerPolicy` this
        session's straggler-aware auto-schedule planned: the jointly
        optimized :class:`BoundedSkip` threshold (``resolved.skip``) with
        the :class:`~repro.core.delay.StragglerModel` the planner was
        given.  Requires a schedule compiled with
        ``DelayModel(straggler=...)``; extra keyword arguments forward to
        the policy (``warmup=``, ``k_mad=``, ...)."""
        from repro.runtime.straggler import StragglerPolicy
        r = self.resolved
        if r.skip is None or r.straggler_model is None:
            raise ValueError(
                "this session's schedule was not planned with "
                "DelayModel(straggler=StragglerModel(...)); construct a "
                "StragglerPolicy explicitly instead")
        return StragglerPolicy(model=r.straggler_model,
                               max_consecutive=int(r.skip), seed=seed,
                               adaptive=adaptive, **kw)

    # ------------------------------------------------------------------
    def sweep(
        self,
        spec=None,
        *,
        lams=None,
        seeds=None,
        schedules=None,
        local_hs=None,
        mode: str = "grid",
        continuation: bool = False,
        rounds: Optional[int] = None,
        record_history: bool = True,
        history_every: int = 1,
        checkpoint=None,
    ):
        """Run a config grid through this session and return a
        :class:`~repro.api.sweep.RunSet`.

        Pass a :class:`~repro.api.sweep.Sweep` as ``spec``, or build one
        inline from ``lams=`` / ``seeds=`` / ``schedules=`` /
        ``local_hs=`` (``mode`` is ``"grid"`` -- the cartesian product --
        or ``"zip"``; ``continuation=True`` warm-starts a regularization
        path over the lambda axis, solved in descending-lambda order).

        On the host backends a (lambda x local-H x seed) grid within one
        schedule runs as ONE vmapped device program per chunk (lambda and
        the step-mask schedule are runtime executor inputs); schedule
        axes produce distinct plans but share the lambda-free executor
        cache.  An H axis (``local_hs``: scalars or per-leaf specs,
        clamped to the compiled capacity -- see ``Schedule(h_cap=...)``)
        batches over the step-mask operand in the SAME vmapped dispatch.
        Each member is bit-identical to the corresponding standalone
        :meth:`run`."""
        from repro.api.sweep import Sweep, run_sweep
        if spec is None:
            spec = Sweep(lams=lams, seeds=seeds, schedules=schedules,
                         local_hs=local_hs, mode=mode,
                         continuation=continuation)
        elif (any(a is not None for a in (lams, seeds, schedules,
                                          local_hs))
              or mode != "grid" or continuation):
            raise ValueError(
                "pass either a Sweep spec or inline axes/options (lams=/"
                "seeds=/schedules=/local_hs=/mode=/continuation=), not "
                "both")
        return run_sweep(self, spec, rounds=rounds,
                         record_history=record_history,
                         history_every=history_every,
                         checkpoint=checkpoint)

    # ------------------------------------------------------------------
    def _start_state(self, warm_start, key, lam_run):
        X = self.problem.X
        k = None if key is None else plan_mod._raw_key(key)
        if warm_start is None:
            alpha = jnp.zeros((self.problem.m,), X.dtype)
            w = jnp.zeros((self.problem.d,), X.dtype)
        elif isinstance(warm_start, SolveResult):
            alpha, w = warm_start.alpha, warm_start.w
            if (warm_start.lam is not None
                    and float(warm_start.lam) != float(lam_run)):
                # the carried primal satisfies w = X^T a / (lam_old m);
                # under a different lambda it must be rebuilt, or every
                # subsequent coordinate step works against an inconsistent
                # w and the run converges to wrong iterates
                w = dual_mod.w_of_alpha(alpha, X, float(lam_run))
            if k is None and warm_start.next_key is not None:
                k = plan_mod._raw_key(warm_start.next_key)
        else:
            alpha, w = warm_start
        if k is None:
            k = plan_mod._raw_key(jax.random.PRNGKey(0))
        alpha = jnp.asarray(alpha)
        w = jnp.asarray(w)
        if alpha.shape != (self.problem.m,):
            raise ValueError(
                f"warm-start alpha must be ({self.problem.m},), got "
                f"{alpha.shape}")
        if w.shape != (self.problem.d,):
            raise ValueError(
                f"warm-start w must be ({self.problem.d},), got {w.shape}")
        return alpha, w, k


def _calibrate_C(problem: Problem, topology: Topology, schedule: Schedule):
    """Resolve ``DelayModel(C="auto")``: run a short host-backend pilot
    under the topology's default schedule, fit eq. (11)'s improvement
    constant from the observed per-root-round gap contractions
    (:func:`repro.core.delay.fit_C`), and return (schedule with the fitted
    C, fitted C)."""
    import dataclasses

    from repro.core.delay import fit_C
    dm = schedule.delay
    pilot_sched = Schedule(weighting=schedule.weighting)
    pilot = Session.compile(problem, topology, pilot_sched, backend="vmap")
    res = pilot.run(rounds=int(dm.pilot_rounds),
                    key=jax.random.PRNGKey(0))
    plan = pilot.plan
    # one root round of the pilot schedule, seen as eq. (11)'s star round:
    # K = root fan-out, H = total coordinate passes one leaf runs per root
    # round, delta = one coordinate's share of a leaf block (the planner's
    # own delta when the DelayModel pins it).  The clip cap is the
    # SMALLEST group size across the topology's sync levels: the planner
    # checks the same C against every level's K.
    K = len(topology.tree.children)
    h_eff = int(plan.solve_mask[:, 0].sum()) * int(plan.leaf_h[0])
    delta = (dm.delta if dm.delta is not None
             else 1.0 / max(int(plan.leaf_sizes[0]), 1))
    c_max = min(lvl.group_size for lvl in topology.sync_levels())
    C = fit_C(res.history, K=K, H=h_eff, delta=delta, c_max=c_max)
    return dataclasses.replace(
        schedule, delay=dataclasses.replace(dm, C=C)), C


def solve(
    problem: Problem,
    topology: Topology,
    schedule: Optional[Schedule] = None,
    *,
    backend: str = "vmap",
    key: Optional[Array] = None,
    rounds: Optional[int] = None,
    warm_start: Union[SolveResult, Tuple[Array, Array], None] = None,
    record_history: bool = True,
    history_every: int = 1,
    mesh=None,
    mesh_axes: Optional[Sequence[str]] = None,
    mesh_use_kernel: bool = True,
    mesh_sync: str = "psum",
    on_round: Optional[Callable[[dict], None]] = None,
    straggler=None,
    lam: Optional[float] = None,
    local_h=None,
    checkpoint=None,
) -> SolveResult:
    """One-shot convenience: ``Session.compile(...).run(...)``.  Forwards
    the full ``run`` surface -- including ``warm_start``, ``straggler``,
    ``checkpoint`` and the ``lam``/``local_h`` overrides -- so the
    one-shot path has feature parity with a session."""
    sess = Session.compile(problem, topology, schedule, backend=backend,
                           mesh=mesh, mesh_axes=mesh_axes,
                           mesh_use_kernel=mesh_use_kernel,
                           mesh_sync=mesh_sync)
    return sess.run(rounds, key=key, warm_start=warm_start,
                    record_history=record_history,
                    history_every=history_every, on_round=on_round,
                    straggler=straggler, lam=lam, local_h=local_h,
                    checkpoint=checkpoint)
