"""Session-driven LM training: the second workload on the schedule engine.

``Problem.lm(cfg, optimizer, batch=, seq=)`` + ``Session.compile(...,
backend="mesh")`` dispatch here: the same Schedule -> ResolvedSchedule ->
``compile_tree`` plan IR that drives SDCA is lowered through
``engine.plan.schedule_view`` into the method-agnostic schedule layer
(per-level periods, group sizes, per-edge codecs), and the
``"lm_treesync"`` Method (``engine.method`` / ``engine.lm``) supplies the
local step and the per-level combine.  One replica-stacked jitted step
takes the periods as a RUNTIME (L,) operand, so

  * ``run(local_h=...)`` and straggler-adaptive eq.-(12) replanning
    change an input array, never the compiled program (zero retraces);
  * ``run(straggler=StragglerPolicy(...))`` drops straggling replicas
    from the barrier via a runtime participation mask (absentees keep
    stale state and rejoin, as in the SDCA path);
  * ``run(checkpoint=...)`` / ``resume`` snapshot the exact
    ``TreeSyncState`` carry at outer-round boundaries and restart
    bit-identically (the data stream is a pure function of
    ``(seed, step)``);
  * ``sweep`` runs an (lr x seed x local_h) grid as ONE vmapped dispatch
    per step through ONE cached executor (lr is a runtime operand of the
    optimizers since PR 8).

At fixed periods the program is bit-identical to the legacy
``core.treesync.make_treesync_step`` path (tested in
``tests/test_lm_session.py``).
"""
from __future__ import annotations

import dataclasses
import time
from math import prod
from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import plan_check, trace_guard as guard_mod
from repro.api.schedule import Schedule
from repro.api.topology import Topology
from repro.core.engine import lm as lm_mod
from repro.core.engine import mesh as mesh_mod
from repro.core.engine import plan as plan_mod
from repro.core.engine.method import get_method
from repro.core.instrument import span
from repro.data.lm import lm_batch

PyTree = Any
TreeSyncState = lm_mod.TreeSyncState


@dataclasses.dataclass
class LMResult:
    """One LM run: the final replica-stacked state plus the per-step
    history (``{"step", "loss", "sec"}``; straggler runs add ``"time"``
    (simulated async clock), ``"time_sync"``, ``"participants"`` and,
    when the policy is adaptive, the executed ``"h"``)."""
    state: TreeSyncState
    history: List[dict]
    wall_s: float

    @property
    def final_loss(self) -> Optional[float]:
        return self.history[-1]["loss"] if self.history else None

    def consensus(self) -> PyTree:
        """The fully-averaged model (what you checkpoint / serve)."""
        return lm_mod.consensus_params(self.state)


@dataclasses.dataclass
class LMRunSet:
    """A fused LM sweep: per-member configs, the stacked (B, R, ...)
    final states and the batched (B, T) loss history."""
    points: List[Any]
    states: TreeSyncState            # leaves (B, R, ...)
    losses: np.ndarray               # (B, T) float32
    lrs: List[Optional[float]]

    def __len__(self) -> int:
        return len(self.points)

    @property
    def final_losses(self) -> np.ndarray:
        return self.losses[:, -1]

    def best(self) -> int:
        """Index of the member with the lowest final loss."""
        return int(np.nanargmin(self.final_losses))

    def member_state(self, i: int) -> TreeSyncState:
        return jax.tree.map(lambda t: t[i], self.states)


class LMSession:
    """Compiled LM training program: (LMProblem, Topology, Schedule) on
    the mesh backend.  Mirrors :class:`repro.api.session.Session`'s
    surface (``run`` / ``resume`` / ``sweep`` / ``cache_stats``)."""

    def __init__(self, problem, topology, resolved, plan, sview, mesh,
                 sync_axes: Tuple[str, ...]):
        self.problem = problem
        self.topology = topology
        self.resolved = resolved
        self.plan = plan
        self.sview = sview
        self.backend = "mesh"
        self._mesh = mesh
        self._sync_axes = sync_axes
        self._axes = lm_mod.present_axes(mesh, sync_axes)
        self._level_sizes = lm_mod.level_sizes_for(mesh, sync_axes)
        self._method = get_method(problem.method)
        self._guard = None          # TraceGuard when compiled strict
        self._built = set()         # executor variants already compiled
        # the LM combine compresses the outermost edge only (legacy
        # TreeSync semantics); schedule_view is bottom-up, so [-1] is the
        # up-link into the root
        comp = sview.compression
        if any(c != "none" for c in comp[:-1]):
            raise ValueError(
                f"LM training compresses the outermost (root) edge only; "
                f"schedule plans per-level codecs {comp} (bottom-up)")
        self._compression = comp[-1] if comp else "none"

    # ------------------------------------------------------------------
    @classmethod
    def compile(cls, problem, topology: Optional[Topology] = None,
                schedule: Optional[Schedule] = None, *,
                backend: str = "mesh", mesh=None,
                sync_axes: Sequence[str] = ("data", "pod"),
                strict=False,
                ) -> "LMSession":
        """Lower ``topology`` under ``schedule`` into the LM train
        program.  ``topology`` defaults to ``Topology.from_mesh(mesh)``
        (one leaf per replica, one level per present sync axis); an
        explicit topology must have the mesh's fan-outs.  ``mesh``
        defaults to a host mesh over the available devices."""
        if backend != "mesh":
            raise ValueError(
                "LM training is replica-stacked data-parallel: the replica "
                "dim is sharded over the sync axes and every combine is a "
                "mesh all-reduce; compile with backend='mesh' "
                f"(got {backend!r})")
        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh() if mesh is None else \
            mesh_mod.auto_axes(mesh)
        axes = lm_mod.present_axes(mesh, tuple(sync_axes))
        sizes = tuple(lm_mod.axis_size(mesh, a) for a in axes)  # bottom-up
        if topology is None:
            topology = Topology.from_mesh(mesh, sync_axes=tuple(sync_axes))
        schedule = schedule or Schedule()
        resolved = schedule.resolve(topology)
        plan = plan_mod.compile_tree(resolved.chunk_tree,
                                     weighting=resolved.weighting,
                                     compression=resolved.compression)
        sview = plan_mod.schedule_view(plan)
        R = max(prod(sizes), 1)
        if prod(sview.group_sizes) != R or (
                len(axes) > 0 and sview.group_sizes != sizes):
            raise ValueError(
                f"topology fan-outs {sview.group_sizes} (bottom-up) do not "
                f"match the mesh's sync-axis sizes {sizes} over {axes}: one "
                "leaf per replica, one level per mesh axis "
                "(Topology.from_mesh builds a matching tree)")
        # the structural verifier runs on every compile (TreePlan checks
        # subsume the schedule-view checks the LM program consumes)
        plan_check.verify_plan(plan)
        sess = cls(problem, topology, resolved, plan, sview, mesh,
                   tuple(sync_axes))
        sess._guard = guard_mod.as_trace_guard(strict)
        return sess

    # ------------------------------------------------------------------
    @property
    def n_replicas(self) -> int:
        return max(prod(self._level_sizes), 1)

    @property
    def periods(self) -> Tuple[int, ...]:
        """Planned per-level periods, bottom-up (leaf H first) -- what
        ``Schedule(rounds='auto')`` chose, or the topology's own."""
        return self.sview.periods

    @property
    def steps_per_round(self) -> int:
        """Local steps per outer (root) round: prod(periods)."""
        return prod(self.sview.periods)

    @property
    def level_plan(self):
        """The eq.-(12) planner output when the schedule was ``"auto"``."""
        return self.resolved.level_plan

    @property
    def default_rounds(self) -> int:
        return self.resolved.rounds

    def cache_stats(self) -> dict:
        """LM executor-cache counters (hits/misses/size)."""
        return self._method.cache_stats()

    # ------------------------------------------------------------------
    def init_state(self, key=None, *, seed: Optional[int] = None
                   ) -> TreeSyncState:
        if key is None:
            key = jax.random.PRNGKey(
                self.problem.seed if seed is None else int(seed))
        return lm_mod.init_lm_state(
            self.problem.cfg, self.problem.optimizer, key, self.n_replicas,
            compression=self._compression)

    def _executor(self, *, masked: bool = False, with_lr: bool = False,
                  batched: bool = False):
        return self._method.executor(
            cfg=self.problem.cfg, optimizer=self.problem.optimizer,
            level_sizes=self._level_sizes, compression=self._compression,
            average_opt_state=self.problem.average_opt_state,
            masked=masked, with_lr=with_lr, batched=batched)

    def _run_periods(self, local_h) -> List[int]:
        ps = list(self.sview.periods)
        if local_h is not None:
            if int(local_h) < 1:
                raise ValueError(f"local_h must be >= 1, got {local_h}")
            ps[0] = int(local_h)
        return ps

    def _batch_at(self, step: int):
        p = self.problem
        return lm_mod.split_batch(
            lm_batch(p.cfg, p.batch, p.seq, step, seed=p.seed),
            self.n_replicas)

    # ------------------------------------------------------------------
    def run(
        self,
        rounds: Optional[int] = None,
        *,
        steps: Optional[int] = None,
        key=None,
        warm_start: Optional[TreeSyncState] = None,
        local_h=None,
        lr: Optional[float] = None,
        straggler=None,
        checkpoint=None,
        record_history: bool = True,
        on_step=None,
        _history_prefix: Sequence[dict] = (),
        _final_save: bool = True,
    ) -> LMResult:
        """Run ``rounds`` outer rounds (default: the schedule's), each
        ``prod(periods)`` local steps; ``steps=`` overrides with an exact
        local-step count (the final round truncates).

        ``local_h`` overrides the leaf period for this run; under an
        adaptive ``straggler`` policy the replanned eq.-(12) H feeds the
        NEXT round's periods operand -- both are runtime inputs, so
        neither ever retraces.  ``warm_start`` continues from a previous
        result's state (the deterministic data stream continues from
        ``state.step``).  ``checkpoint`` snapshots the exact state every
        ``policy.every`` outer rounds; see :meth:`resume`.  ``lr``
        overrides the optimizer's step size (a runtime operand)."""
        p = self.problem
        R = self.n_replicas
        L = len(self._level_sizes)
        periods = self._run_periods(local_h)
        spr = prod(periods)

        if warm_start is not None:
            state = warm_start.state if isinstance(warm_start, LMResult) \
                else warm_start
            # the step executor donates its state carry; copy so the
            # caller's warm-start buffers stay valid after this run
            state = jax.tree.map(jnp.copy, state)
        else:
            state = self.init_state(key)
        start = int(state.step)
        if steps is not None:
            total = int(steps)
        else:
            T = self.resolved.rounds if rounds is None else int(rounds)
            if T < 0:
                raise ValueError(f"rounds must be >= 0, got {T}")
            total = T * spr

        ckpt_mgr, ck_every, ckpt_policy = None, 0, None
        if checkpoint is not None:
            if straggler is not None:
                raise ValueError(
                    "checkpoint= does not compose with straggler=: the "
                    "policy's sampled-delay RNG and skip counters are host "
                    "state the snapshot cannot capture, so a resumed run "
                    "would diverge; checkpoint synchronous runs only")
            from repro.runtime import fault as fault_mod
            ckpt_policy, ckpt_mgr, ck_every = fault_mod.bind_policy(
                checkpoint, self.resolved)

        masked = straggler is not None
        if masked:
            n_leaves = self.plan.n_leaves
            if n_leaves != R:
                raise ValueError(
                    f"straggler= needs one topology leaf per replica "
                    f"(got {n_leaves} leaves for {R} replicas)")
            t_lp = self.topology.leaf_t_lp()
            straggler.bind(self.topology.leaf_sync_delays(),
                           t_compute=spr * t_lp, t_lp=t_lp)
        adaptive = masked and getattr(straggler, "adaptive", None) is not None

        # strict mode: fetching a variant this session has ALREADY built
        # must hit the cache (zero budget -- a cleared cache or a drifted
        # key raises); the first fetch of a variant is budgeted one build.
        # From then on every step dispatch must hit.  (No host-sync guard
        # on the LM path: the deterministic data stream is host-generated
        # per step by design.)
        guard = self._guard

        def _retrace_ctx(budget=0):
            import contextlib
            if guard is None or not guard.error_on_retrace:
                return contextlib.nullcontext()
            return guard.retrace_region(budget)

        variant = (masked, lr is not None)
        with _retrace_ctx(0 if variant in self._built else 1):
            exec_fn = self._executor(masked=masked, with_lr=lr is not None)
        self._built.add(variant)
        periods_arr = jnp.asarray(periods[:L], jnp.int32)
        part = jnp.ones((R,), jnp.float32) if masked else None
        lr_arr = None if lr is None else jnp.asarray(lr, jnp.float32)

        history: List[dict] = []
        clock = {"async": 0.0, "sync": 0.0}
        t_start = time.time()
        i, done = start, 0
        while done < total:
            n_this = min(spr, total - done)
            final = done + n_this >= total
            extra = None
            if masked:
                st = straggler.step(final=final)
                part = jnp.asarray(st.mask, jnp.float32)
                clock["async"] += st.dt_async
                clock["sync"] += st.dt_sync
                extra = {"time": clock["async"],
                         "time_sync": clock["sync"],
                         "participants": int(st.mask.sum())}
                if adaptive:
                    extra["h"] = periods[0]
            for _ in range(n_this):
                with span("LMSession.step", step=i + 1):
                    t0 = time.time()
                    with span("LMSession.batch", step=i + 1):
                        batch = self._batch_at(i)
                    with _retrace_ctx(), \
                            span("LMSession.dispatch", step=i + 1):
                        state, metrics = exec_fn(state, batch, periods_arr,
                                                 part, lr_arr)
                    i += 1
                    done += 1
                    if record_history:
                        with span("LMSession.loss_read", step=i):
                            loss = float(metrics["loss"])
                        entry = {"step": i, "loss": loss,
                                 "sec": time.time() - t0}
                        if extra:
                            entry.update(extra)
                        history.append(entry)
                if record_history and on_step is not None:
                    on_step(entry)
            if guard is not None and guard.sanitize:
                guard.check_carry(state, f"state@step{i}")
            # eq.-(12) replanning feeds the NEXT round through the runtime
            # periods operand: a new input array, never a recompile
            if adaptive and straggler.last_h_suggest is not None:
                h_new = max(int(straggler.last_h_suggest), 1)
                if h_new != periods[0]:
                    periods[0] = h_new
                    spr = prod(periods)
                    periods_arr = jnp.asarray(periods[:L], jnp.int32)
                    straggler.retime(spr * self.topology.leaf_t_lp())
            if ckpt_mgr is not None:
                r_no = (i - start + spr - 1) // spr
                if r_no % ck_every == 0 or (final and _final_save):
                    meta = {
                        "version": 1,
                        "step": i,
                        "steps_total": start + total,
                        "periods": list(periods),
                        "plan": self.plan.fingerprint,
                        "seed": int(p.seed),
                        "lr": None if lr is None else float(lr),
                        "history": list(_history_prefix) + history,
                    }
                    ckpt_mgr.save(i, state, metadata=meta)
        if ckpt_mgr is not None:
            ckpt_mgr.wait()
        return LMResult(state=state,
                        history=list(_history_prefix) + history,
                        wall_s=time.time() - t_start)

    # ------------------------------------------------------------------
    def resume(self, checkpoint, *, steps: Optional[int] = None,
               record_history: bool = True, on_step=None) -> LMResult:
        """Restart a checkpointed run from its newest snapshot,
        bit-identically to the uninterrupted run: the restored
        ``TreeSyncState`` is the complete carry, and the data stream is a
        pure function of ``(seed, step)``, so restore + continue = never
        crashed.  Runs the remaining steps (``steps_total - step``, or
        ``steps=`` to override) and keeps checkpointing into the same
        directory; the returned history is the full concatenated
        series."""
        from repro.runtime import fault as fault_mod
        policy, mgr, _ = fault_mod.bind_policy(checkpoint, self.resolved)
        last = mgr.latest_step()
        if last is None:
            raise FileNotFoundError(
                f"no complete checkpoints under {policy.directory!r}")
        meta = mgr.metadata(last)
        if meta.get("plan") != self.plan.fingerprint:
            raise ValueError(
                "checkpoint was written under a different plan "
                "(topology/schedule/compression changed between save and "
                "resume); compile a matching session")
        if int(meta.get("seed", self.problem.seed)) != int(self.problem.seed):
            raise ValueError(
                f"checkpoint data stream has seed {meta['seed']}; this "
                f"problem uses seed {self.problem.seed}")
        step, state = mgr.restore(self.init_state(jax.random.PRNGKey(0)),
                                  last)
        remaining = int(meta["steps_total"]) - step if steps is None \
            else int(steps)
        if remaining < 0:
            raise ValueError(f"steps must be >= 0, got {remaining}")
        lr = meta.get("lr")
        periods = meta.get("periods")
        local_h = None
        if periods is not None and tuple(periods) != self.sview.periods:
            local_h = int(periods[0])
        return self.run(steps=remaining, warm_start=state, local_h=local_h,
                        lr=lr, checkpoint=policy,
                        record_history=record_history, on_step=on_step,
                        _history_prefix=[dict(e)
                                         for e in meta.get("history", [])])

    # ------------------------------------------------------------------
    def sweep(self, spec=None, *, lrs=None, seeds=None, local_hs=None,
              rounds: Optional[int] = None, steps: Optional[int] = None,
              ) -> LMRunSet:
        """Run an (lr x seed x local_h) grid as ONE vmapped dispatch per
        step through ONE cached executor: per-member state and periods
        are batched operands, the data batch is shared (seeds vary the
        INIT key; the stream belongs to the problem), and lr rides the
        optimizers' runtime-lr operand.  ``spec`` is a
        :class:`repro.api.sweep.Sweep` (axes ``lrs``/``seeds``/
        ``local_hs``; ``lams``/``schedules`` are SDCA axes and rejected
        here), or pass the axes directly."""
        from repro.api.sweep import Sweep
        if spec is None:
            spec = Sweep(lrs=lrs, seeds=seeds, local_hs=local_hs)
        if spec.lams is not None or spec.schedules is not None:
            raise ValueError(
                "LM sweeps batch lrs=, seeds=, and local_hs= (runtime "
                "operands of one executor); lams= has no LM meaning and a "
                "schedules= axis changes the compiled program -- run one "
                "sweep per schedule")
        if spec.continuation or spec.resume is not None:
            raise ValueError(
                "continuation/resume are SDCA sweep features; LM sweeps "
                "run straight grids")
        points = spec.expand(0.0)
        B = len(points)
        L = len(self._level_sizes)
        spr = prod(self.sview.periods)
        if steps is not None:
            total = int(steps)
        else:
            T = self.resolved.rounds if rounds is None else int(rounds)
            total = T * spr

        states = [self.init_state(seed=pt.seed if isinstance(
            pt.seed, (int, np.integer)) else None,
            key=None if pt.seed is None or isinstance(
                pt.seed, (int, np.integer)) else pt.seed)
            for pt in points]
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *states)
        periods_b = np.tile(np.asarray(self.sview.periods[:L], np.int32),
                            (B, 1))
        for b, pt in enumerate(points):
            if pt.local_h is not None:
                periods_b[b, 0] = int(pt.local_h)
        periods_b = jnp.asarray(periods_b)
        with_lr = spec.lrs is not None
        lr_b = jnp.asarray([pt.lr for pt in points], jnp.float32) \
            if with_lr else None

        exec_fn = self._executor(with_lr=with_lr, batched=True)
        losses = []
        for i in range(total):
            stacked, metrics = exec_fn(stacked, self._batch_at(i),
                                       periods_b, None, lr_b)
            losses.append(np.asarray(metrics["loss"], np.float32))
        return LMRunSet(points=points, states=stacked,
                        losses=np.stack(losses, axis=1) if losses
                        else np.zeros((B, 0), np.float32),
                        lrs=[pt.lr for pt in points])
