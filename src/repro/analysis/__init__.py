"""Static-analysis layer for the schedule engine: correctness tooling
that proves the compiled program matches the plan IR (and stays matched
across refactors), instead of re-fixing cache-key and bit-identity bugs
after the fact.

Three layers (see ``docs/analysis.md``):

  * :mod:`repro.analysis.plan_check` -- structural invariant checks over
    ``TreePlan`` / ``SchedulePlan`` plus the fingerprint-soundness audit
    (every compiled-behavior field must be classified in the plan IR's
    fingerprint registry, so the PR-4/PR-6 cache-key bug class fails at
    compile time instead of shipping).
  * :mod:`repro.analysis.trace_guard` -- a strict runtime mode for
    ``Session``: unexpected executor-cache misses become errors carrying
    a structured diff of the offending cache keys, host syncs inside the
    chunk loop's dispatch region are disallowed, and an opt-in NaN/Inf
    sanitizer checks the chunk carry each round.
  * :mod:`repro.analysis.rules` -- repo-specific AST lint rules run by
    ``python -m repro.analysis``: no wall-clock / Python RNG / program
    spans inside traced bodies, no static closure capture of runtime
    operands (lambda / lr / local_h / periods), no ``jax.jit`` outside
    ``core/engine`` + ``kernels`` without a waiver, no mutable defaults
    in frozen dataclasses.
"""
from repro.analysis.plan_check import (       # noqa: F401
    AnalysisError, Finding, audit_fingerprint, check_schedule_plan,
    check_tree_plan, verify_plan)
from repro.analysis.trace_guard import (      # noqa: F401
    HostSyncError, NonFiniteError, TraceGuard, UnexpectedRetraceError,
    as_trace_guard, check_finite, no_retrace)
from repro.analysis.rules import lint_paths   # noqa: F401
