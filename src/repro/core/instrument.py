"""Instrumentation for solver runs, factored out of the solvers themselves
so the compiled engine, the legacy reference recursion, the delay-planning
tools (``repro.core.delay``) and the figure benchmarks all share one
history/timing layer.

* simulated wall-clock: the tree's own delay model (``TreeNode.solve_time``,
  the generalization of paper eq. (9)) gives the per-root-round time;
* history: a list of ``{round, time, dual, primal, gap}`` dicts wrapped in
  :class:`SolveResult` (array accessors for plotting/benchmarks);
* batched histories: the sweep layer (``repro.api.sweep``) stores a config
  batch's series as ``(B, T)`` arrays -- :func:`stack_histories` /
  :func:`history_row` convert between that schema and the per-run dict
  lists (NaN-padded where members recorded fewer rounds);
* program spans: :func:`span` marks a host stage of a run on the
  profiler's own clock (:data:`SPAN_NAMES`), and the traced bodies name
  their device stages with ``jax.named_scope`` (:data:`SCOPE_NAMES`), so
  a ``jax.profiler`` trace attributes device time and idle gaps to them.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Sequence

import jax
import numpy as np

from repro.core.tree import TreeNode

Array = jax.Array

HISTORY_FIELDS = ("round", "time", "dual", "primal", "gap")

SPAN_PREFIX = "repro:"
# host spans (each recorded as SPAN_PREFIX + name).  Session.run and
# LMSession.step enclose the others of their class; the per-round ones
# carry a ``round`` (``step``) stat
SPAN_NAMES = (
    "Session.run", "Session.start_state", "Session.operands",
    "Session.key_plan", "Session.key_upload", "Session.dispatch",
    "Session.record", "Session.advance_key", "Session.materialize",
    "LMSession.step", "LMSession.batch", "LMSession.dispatch",
    "LMSession.loss_read",
)
# device scopes (``jax.named_scope``) of the chunk programs and the LM
# step, innermost first: an op nested in several counts in the first
SCOPE_NAMES = ("codec", "level_sync", "leaf_solve", "reblock", "objective",
               "forward_backward", "optimizer", "tree_sync")


def span(name: str, **stats) -> jax.profiler.TraceAnnotation:
    """A host span ``repro:<name>`` on the profiler's clock (one of
    :data:`SPAN_NAMES`).  ``stats`` are small ints (round numbers, counts)
    recorded as the event's arguments; ``set_metadata`` on the returned
    span adds more before it closes.  With no profiler active it costs
    about a microsecond and records nothing."""
    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name, **stats)


@dataclasses.dataclass
class SolveResult:
    """A solver run: final iterates + per-root-round instrumentation.

    ``next_key`` (set by ``repro.api.Session.run``) is the root RNG chain
    state after the run, so a warm-restarted continuation reproduces the
    exact iterates of one longer run.  ``lam`` (also session-set) records
    the regularization the run used, so a warm restart under a DIFFERENT
    lambda knows to rebuild the primal (``w = X^T alpha / (lam m)``)
    instead of carrying an inconsistent ``w``."""
    alpha: Array
    w: Array
    history: List[dict]  # per root round: round, time, dual, primal, gap
    next_key: Array = None
    lam: float = None

    @property
    def times(self) -> np.ndarray:
        return np.array([h["time"] for h in self.history])

    @property
    def gaps(self) -> np.ndarray:
        return np.array([h["gap"] for h in self.history])

    @property
    def duals(self) -> np.ndarray:
        return np.array([h["dual"] for h in self.history])

    @property
    def primals(self) -> np.ndarray:
        return np.array([h["primal"] for h in self.history])

    def to_dict(self) -> dict:
        """JSON-serializable form (iterates as lists, history as-is)."""
        return {
            "alpha": np.asarray(self.alpha).tolist(),
            "w": np.asarray(self.w).tolist(),
            "history": [dict(h) for h in self.history],
            "next_key": (None if self.next_key is None
                         else np.asarray(self.next_key).tolist()),
            "lam": None if self.lam is None else float(self.lam),
        }


def per_round_time(tree: TreeNode) -> float:
    """Simulated wall-clock of ONE root round (children in parallel,
    synchronous barrier; paper eq. (9) when the tree is a star)."""
    return tree.solve_time() / max(tree.rounds, 1)


def round_times(tree: TreeNode) -> np.ndarray:
    """Times of rounds 0..T (round 0 is the start-of-run record)."""
    return np.arange(tree.rounds + 1) * per_round_time(tree)


def history_from_series(
    times: Sequence[float],
    duals: Sequence[float],
    primals: Sequence[float],
) -> List[dict]:
    """Assemble the legacy history-dict list from aligned series."""
    out = []
    for t, (tm, dv, pv) in enumerate(zip(times, duals, primals,
                                         strict=True)):
        out.append({"round": t, "time": float(tm), "dual": float(dv),
                    "primal": float(pv), "gap": float(pv) - float(dv)})
    return out


def record_round(history: List[dict], t: int, time: float, dual: float,
                 primal: float) -> None:
    """Append one legacy-format history entry (used by the reference
    recursion, which records on the host as it goes)."""
    history.append({"round": t, "time": time, "dual": dual,
                    "primal": primal, "gap": primal - dual})


# ---------------------------------------------------------------------------
# batched-history schema (the sweep layer's (B, T) representation)
# ---------------------------------------------------------------------------
def stack_histories(histories: Sequence[List[dict]]) -> Dict[str, np.ndarray]:
    """Stack B per-run history dict-lists into ``{field: (B, T_max)}``
    float arrays (one per :data:`HISTORY_FIELDS`), NaN-padding members that
    recorded fewer rounds -- the :class:`~repro.api.sweep.RunSet` history
    schema.  Extra per-entry keys (async instrumentation) are dropped."""
    B = len(histories)
    t_max = max((len(h) for h in histories), default=0)
    out = {f: np.full((B, t_max), np.nan) for f in HISTORY_FIELDS}
    for b, hist in enumerate(histories):
        for t, entry in enumerate(hist):
            for f in HISTORY_FIELDS:
                out[f][b, t] = float(entry[f])
    return out


def history_row(stacked: Dict[str, np.ndarray], b: int) -> List[dict]:
    """Reconstruct member ``b``'s history dict-list from a
    :func:`stack_histories` batch (NaN padding rows are dropped)."""
    out: List[dict] = []
    rounds = stacked["round"]
    for t in range(rounds.shape[1]):
        if not np.isfinite(rounds[b, t]):
            continue
        out.append({
            "round": int(rounds[b, t]),
            "time": float(stacked["time"][b, t]),
            "dual": float(stacked["dual"][b, t]),
            "primal": float(stacked["primal"][b, t]),
            "gap": float(stacked["gap"][b, t]),
        })
    return out
