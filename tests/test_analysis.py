"""The static-analysis layer (``repro.analysis``): every layer must
demonstrably catch its seeded defect class.

The load-bearing claims:

  * the plan-IR verifier accepts every valid plan the lowering produces
    (homogeneous, heterogeneous, size-weighted, compressed) and rejects
    seeded structural defects with actionable finding codes;
  * the fingerprint is SOUND: mutating ANY registered behavior field of
    a ``TreePlan`` changes ``plan.fingerprint`` (exhaustive per-field
    property test), and dropping a field from the registry is caught by
    ``audit_fingerprint`` (the PR-4/PR-6 cache-key bug class);
  * strict mode turns a forced executor rebuild into an
    ``UnexpectedRetraceError`` with a structured key diff, while a
    well-behaved strict run stays bit-identical to the plain run;
  * the AST lint rules flag wall-clock/RNG in traced bodies, static
    closure capture of runtime operands, stray ``jax.jit``, and mutable
    defaults in frozen dataclasses -- and honor waiver comments.
"""
import dataclasses
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import (AnalysisError, NonFiniteError, TraceGuard,
                            UnexpectedRetraceError, audit_fingerprint,
                            check_finite, check_schedule_plan,
                            check_tree_plan, no_retrace, verify_plan)
from repro.analysis import rules as lint
from repro.api import Problem, Session, Topology
from repro.core import dual as D
from repro.core.engine import host as host_mod
from repro.core.engine import plan as plan_mod
from repro.core.engine.plan import SchedulePlan, compile_tree, schedule_view
from repro.core.tree import TreeNode, star
from repro.core.treesync import TreeSyncConfig
from repro.data.synthetic import gaussian_regression

LAM = 0.1


def _codes(findings):
    return {f.code for f in findings}


def _star_plan(n=4, m=6, rounds=3, h=8, **kw):
    return compile_tree(star(n, m, outer_rounds=rounds, local_steps=h), **kw)


def _hetero_plan():
    # a shallow leaf next to a deeper subtree: exercises the inactive-
    # leaf (default-zero) columns the verifier must NOT flag
    leaves = tuple(TreeNode(name=f"l{i}", rounds=2 + i, data_size=4 + i)
                   for i in range(3))
    return compile_tree(TreeNode(name="root", rounds=2, children=(
        TreeNode(name="g", rounds=2, children=leaves),
        TreeNode(name="x", rounds=3, data_size=5),
    )))


# ---------------------------------------------------------------------------
# verifier: valid plans pass
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("mk", [
    lambda: _star_plan(),
    lambda: _star_plan(compression=("int8",)),
    lambda: _star_plan(compression=("topk_0.25",)),
    _hetero_plan,
    lambda: compile_tree(star(3, 5, outer_rounds=2, local_steps=4),
                         weighting="size"),
], ids=["star", "int8", "topk", "hetero", "size-weighted"])
def test_verifier_accepts_valid_plans(mk):
    plan = mk()
    assert check_tree_plan(plan) == []
    assert audit_fingerprint(plan) == []
    verify_plan(plan)  # no raise


def test_verifier_accepts_schedule_view():
    sview = schedule_view(_star_plan())
    assert check_schedule_plan(sview) == []
    verify_plan(sview)


# ---------------------------------------------------------------------------
# fingerprint soundness: exhaustive per-field mutation
# ---------------------------------------------------------------------------
def _mutate(plan, name):
    """Return a semantically-distinct copy differing only in `name`."""
    val = getattr(plan, name)
    if isinstance(val, np.ndarray):
        arr = np.array(val, copy=True)
        flat = arr.reshape(-1)
        if arr.dtype.kind == "f":
            # masks are 0/1 -- flip; weights -- nudge
            flat[0] = 1.0 - flat[0] if flat[0] in (0.0, 1.0) \
                else flat[0] * 0.5 + 0.25
        else:
            flat[0] = flat[0] + 1
        return dataclasses.replace(plan, **{name: arr}, fingerprint="")
    if isinstance(val, str):
        return dataclasses.replace(plan, **{name: val + "?"}, fingerprint="")
    if isinstance(val, tuple):
        return dataclasses.replace(
            plan, **{name: tuple(v + 1 for v in val)}, fingerprint="")
    return dataclasses.replace(plan, **{name: val + 1}, fingerprint="")


@pytest.mark.parametrize("field", plan_mod.FINGERPRINT_ARRAY_FIELDS
                         + plan_mod.FINGERPRINT_SCALAR_FIELDS)
def test_fingerprint_changes_under_every_behavior_field(field):
    plan = _star_plan()
    probe = _mutate(plan, field)
    assert probe.fingerprint != plan.fingerprint, (
        f"mutating behavior field {field!r} left the fingerprint "
        "unchanged: two distinct plans would share a compiled executor")


def test_fingerprint_ignores_metadata():
    plan = _star_plan()
    renamed = dataclasses.replace(
        plan, leaf_names=tuple(f"r{i}" for i in range(plan.n_leaves)),
        fingerprint="")
    assert renamed.fingerprint == plan.fingerprint


def test_fingerprint_deterministic_across_recompile():
    t = star(4, 6, outer_rounds=3, local_steps=8)
    assert compile_tree(t).fingerprint == compile_tree(t).fingerprint


# ---------------------------------------------------------------------------
# seeded defect #1: a field omitted from the registry fails the audit
# ---------------------------------------------------------------------------
def test_audit_catches_unregistered_field(monkeypatch):
    monkeypatch.setattr(
        plan_mod, "FINGERPRINT_ARRAY_FIELDS",
        tuple(f for f in plan_mod.FINGERPRINT_ARRAY_FIELDS
              if f != "compress_kind"))
    findings = audit_fingerprint(None)
    assert "F202" in _codes(findings)
    assert any("compress_kind" in f.message for f in findings)


def test_audit_catches_double_classification(monkeypatch):
    monkeypatch.setattr(
        plan_mod, "METADATA_FIELDS",
        plan_mod.METADATA_FIELDS + ("solve_mask",))
    assert "F200" in _codes(audit_fingerprint(None))


def test_audit_catches_stale_registry_entry(monkeypatch):
    monkeypatch.setattr(
        plan_mod, "FINGERPRINT_SCALAR_FIELDS",
        plan_mod.FINGERPRINT_SCALAR_FIELDS + ("no_such_field",))
    assert "F201" in _codes(audit_fingerprint(None))


def test_audit_catches_dropped_field_in_payload(monkeypatch):
    # a serialization that silently drops compress_kind collides the
    # compressed and uncompressed plans -- exactly the PR-6 bug
    real = plan_mod.fingerprint_payload

    def lossy(plan):
        return real(dataclasses.replace(
            plan, compress_kind=np.zeros_like(plan.compress_kind),
            fingerprint="x"))
    monkeypatch.setattr(plan_mod, "compute_fingerprint",
                        lambda p: __import__("hashlib").sha1(
                            lossy(p)).hexdigest())
    # compile_tree is lru-cached: clear so the base plan is fingerprinted
    # by the seeded-lossy serialization too (and again after, so no plan
    # stamped with the lossy hash leaks into later tests)
    plan_mod._compile_tree_cached.cache_clear()
    try:
        plan = _star_plan(compression=("int8",))
        assert "F220" in _codes(audit_fingerprint(plan))
    finally:
        plan_mod._compile_tree_cached.cache_clear()


# ---------------------------------------------------------------------------
# adversarial invalid plans
# ---------------------------------------------------------------------------
def _replace(plan, **kw):
    return dataclasses.replace(plan, **kw)


def test_rejects_mismatched_mask_shape():
    plan = _star_plan()
    bad = _replace(plan, solve_mask=plan.solve_mask[:, :-1])
    findings = check_tree_plan(bad)
    assert "P110" in _codes(findings)
    assert any("solve_mask" in f.where for f in findings)
    with pytest.raises(AnalysisError, match="P110"):
        verify_plan(bad)


def test_rejects_nonbinary_mask():
    plan = _star_plan()
    arr = np.array(plan.solve_mask, copy=True)
    arr[0, 0] = 0.5
    assert "P111" in _codes(check_tree_plan(_replace(plan, solve_mask=arr)))


def test_rejects_out_of_range_compress_frac():
    plan = _star_plan(compression=("topk_0.25",))
    arr = np.array(plan.compress_frac, copy=True)
    arr[arr > 0] = 1.5
    findings = check_tree_plan(_replace(plan, compress_frac=arr))
    assert "P141" in _codes(findings)
    assert any("(0, 1]" in f.message for f in findings)


def test_rejects_unknown_compress_kind():
    plan = _star_plan()
    arr = np.array(plan.compress_kind, copy=True)
    arr[0, 0] = 99
    assert "P140" in _codes(check_tree_plan(_replace(plan,
                                                     compress_kind=arr)))


def test_rejects_bad_w_coeff():
    plan = _star_plan()
    assert {"P135", "P136"} & _codes(
        check_tree_plan(_replace(plan, w_coeff=plan.w_coeff * 0.5)))


def test_rejects_refresh_sync_mismatch():
    plan = _star_plan()
    assert "P120" in _codes(check_tree_plan(
        _replace(plan, refresh_mask=np.zeros_like(plan.refresh_mask))))


def test_rejects_stale_fingerprint():
    plan = _star_plan()
    arr = np.array(plan.solve_mask, copy=True)  # behavior change ...
    arr[0, :] = 1.0 - arr[0, :]
    # ... with the OLD fingerprint smuggled through
    stale = _replace(plan, solve_mask=arr, fingerprint=plan.fingerprint)
    assert "P161" in _codes(check_tree_plan(stale))


def test_rejects_bad_schedule_plan():
    sview = schedule_view(_star_plan())
    assert "S301" in _codes(check_schedule_plan(
        dataclasses.replace(sview, periods=(0,) + sview.periods[1:])))
    assert "S304" in _codes(check_schedule_plan(
        dataclasses.replace(sview, compression=("wat",))))
    assert "S305" in _codes(check_schedule_plan(
        dataclasses.replace(sview, fingerprint="")))


def test_rejects_duplicate_sync_axes():
    with pytest.raises(ValueError, match="duplicate sync_axes"):
        TreeSyncConfig(sync_axes=("data", "data"), periods=(2, 2))


def test_verify_plan_rejects_wrong_type():
    with pytest.raises(TypeError):
        verify_plan({"not": "a plan"})


# ---------------------------------------------------------------------------
# trace guard: strict sessions
# ---------------------------------------------------------------------------
def _problem_topo():
    topo = Topology.star(4, 24, rounds=4, local_steps=16)
    X, y = gaussian_regression(m=topo.m_total, d=8)
    return Problem.ridge(X, y, lam=LAM), topo


def test_strict_run_bit_identical_to_plain():
    prob, topo = _problem_topo()
    plain = Session.compile(prob, topo).run(key=jax.random.PRNGKey(0))
    strict = Session.compile(prob, topo, strict=True).run(key=jax.random.PRNGKey(0))
    np.testing.assert_array_equal(np.asarray(plain.alpha),
                                  np.asarray(strict.alpha))
    np.testing.assert_array_equal(np.asarray(plain.w),
                                  np.asarray(strict.w))


def test_strict_catches_forced_rebuild():
    # seeded defect #2: evicting the session's executor forces a rebuild
    # on the next run -- strict mode turns that silent retrace into an
    # error (and the session recovers on the run after)
    prob, topo = _problem_topo()
    sess = Session.compile(prob, topo, strict=True)
    sess.run(key=jax.random.PRNGKey(0))
    host_mod._EXEC_CACHE.clear()
    with pytest.raises(UnexpectedRetraceError, match="cache miss"):
        sess.run(key=jax.random.PRNGKey(0))
    sess.run(key=jax.random.PRNGKey(0))  # rebuilt entry is a hit again


def test_strict_false_by_default_tolerates_rebuild():
    prob, topo = _problem_topo()
    sess = Session.compile(prob, topo)
    sess.run(key=jax.random.PRNGKey(0))
    host_mod._EXEC_CACHE.clear()
    sess.run(key=jax.random.PRNGKey(0))  # no raise


def test_no_retrace_budget_and_key_diff():
    plan = _star_plan()

    def fetch():
        host_mod.get_host_executor(plan, loss=D.squared,
                                   record_history=False, backend="vmap")
    fetch()  # populate
    host_mod._EXEC_CACHE.clear()
    with pytest.raises(UnexpectedRetraceError) as ei:
        with no_retrace(budget=0):
            fetch()
    assert ei.value.misses  # structured miss entries ride along
    assert "plan_fingerprint" in str(ei.value)
    host_mod._EXEC_CACHE.clear()
    with no_retrace(budget=1):  # an explicit budget tolerates the rebuild
        fetch()
    with no_retrace(budget=0):  # and now it hits
        fetch()


def test_trace_guard_validation():
    from repro.analysis.trace_guard import as_trace_guard
    assert as_trace_guard(False) is None
    assert isinstance(as_trace_guard(True), TraceGuard)
    g = TraceGuard(miss_budget=2)
    assert as_trace_guard(g) is g
    with pytest.raises(TypeError):
        as_trace_guard("strict")


def test_check_finite_names_offender():
    tree = {"ok": jnp.ones(3), "bad": jnp.array([1.0, np.nan])}
    with pytest.raises(NonFiniteError, match="bad"):
        check_finite(tree, "chunk[3]")
    check_finite({"i": jnp.arange(3)}, "ints are skipped")


def test_cache_stats_by_backend():
    stats = host_mod.executor_cache_stats()
    assert {"vmap", "pallas", "mesh", "lm"} <= set(stats["by_backend"])
    before = dict(stats["by_backend"]["vmap"])
    prob, topo = _problem_topo()
    Session.compile(prob, topo)
    Session.compile(prob, topo)  # same config: second fetch must hit
    after = host_mod.executor_cache_stats()["by_backend"]["vmap"]
    assert after["hits"] > before["hits"]
    # totals stay consistent: sum over backends == global counters
    stats = host_mod.executor_cache_stats()
    assert stats["hits"] == sum(b["hits"]
                                for b in stats["by_backend"].values())
    assert stats["misses"] == sum(b["misses"]
                                  for b in stats["by_backend"].values())


# ---------------------------------------------------------------------------
# lint rules
# ---------------------------------------------------------------------------
def _lint(tmp_path, source, name="pkg/fixture.py"):
    f = tmp_path / name
    f.parent.mkdir(parents=True, exist_ok=True)
    f.write_text(textwrap.dedent(source))
    return lint.lint_file(str(f))


def test_lint_static_lambda_closure(tmp_path):
    # seeded defect #3: the PR-4 bug shape -- lambda baked into the trace
    findings = _lint(tmp_path, """\
        import jax

        def make_step(lam):
            @jax.jit
            def step(alpha):
                return alpha * lam
            return step
        """)
    assert [f.rule for f in findings] == ["static-operand-capture"]
    assert "lam" in findings[0].message


def test_lint_operand_as_argument_is_clean(tmp_path):
    findings = _lint(tmp_path, """\
        import jax

        @jax.jit
        def step(alpha, lam):
            return alpha * lam
        """)
    assert findings == []


def test_lint_wallclock_and_random_in_trace(tmp_path):
    findings = _lint(tmp_path, """\
        import time, random
        import jax

        @jax.jit
        def f(x):
            t0 = time.time()
            return x + random.random() + t0
        """)
    assert {"wall-clock-in-trace", "python-random-in-trace"} == \
        {f.rule for f in findings}


def test_lint_wallclock_outside_trace_is_clean(tmp_path):
    assert _lint(tmp_path, """\
        import time

        def bench(f):
            t0 = time.time()
            f()
            return time.time() - t0
        """) == []


def test_lint_span_in_trace(tmp_path):
    findings = _lint(tmp_path, """\
        import jax
        from repro.core import instrument
        from repro.core.instrument import span

        @jax.jit
        def f(x):
            with span("Session.dispatch"):
                x = x + 1
            with instrument.span("Session.record"):
                x = x * 2
            with jax.profiler.TraceAnnotation("repro:x"):
                return x

        def host(g, x):
            with span("Session.dispatch", round=1):
                return g(x)
        """)
    assert [f.rule for f in findings] == ["span-in-trace"] * 3
    assert [f.line for f in findings] == [7, 9, 11]


def test_lint_jit_location(tmp_path):
    src = """\
        import jax

        @jax.jit
        def f(x):
            return x + 1
        """
    bad = _lint(tmp_path, src, name="src/repro/launch/stray.py")
    assert [f.rule for f in bad] == ["jit-outside-engine"]
    assert _lint(tmp_path, src,
                 name="src/repro/core/engine/fine.py") == []
    assert _lint(tmp_path, src, name="tests/fine.py") == []


def test_lint_traced_via_scan_and_vmap(tmp_path):
    findings = _lint(tmp_path, """\
        import time
        import jax

        def outer(xs):
            def body(c, x):
                return c + time.time(), x
            return jax.lax.scan(body, 0.0, xs)
        """)
    assert [f.rule for f in findings] == ["wall-clock-in-trace"]


def test_lint_frozen_mutable_default(tmp_path):
    findings = _lint(tmp_path, """\
        import dataclasses

        @dataclasses.dataclass(frozen=True)
        class Cfg:
            xs: list = []
            ys: dict = dict()
        """)
    assert ([f.rule for f in findings]
            == ["mutable-default-in-frozen-dataclass"] * 2)


def test_lint_waiver_comment(tmp_path):
    findings = _lint(tmp_path, """\
        import jax

        @jax.jit  # analysis: allow(jit-outside-engine) fixture
        def f(x):
            return x + 1
        """, name="src/repro/launch/waived.py")
    assert findings == []


def test_lint_undonated_carry(tmp_path):
    """Engine jits of chunk-carry steps must donate the carry: callers
    rebind ``state = step(...)`` every chunk, so an undonated carry
    doubles the peak state footprint."""
    findings = _lint(tmp_path, """\
        import jax

        def _build():
            def step_chunk(X, y, state):
                return state
            return jax.jit(step_chunk)
        """, name="src/repro/core/engine/fine.py")
    assert [f.rule for f in findings] == ["undonated-carry"]
    # donating the carry satisfies the rule; non-carry jits are exempt
    clean = _lint(tmp_path, """\
        import jax

        def _build():
            def step_chunk(X, y, state):
                return state
            def finalize(state):
                return state
            return (jax.jit(step_chunk, donate_argnums=(2,)),
                    jax.jit(finalize))
        """, name="src/repro/core/engine/fine2.py")
    assert clean == []


def test_lint_undonated_carry_unwraps_transforms(tmp_path):
    """The rule sees through the batched/mesh wrappers: a carry step
    jitted as ``jax.jit(jax.vmap(step))`` or ``jax.jit(shard_map(step))``
    still needs donation."""
    findings = _lint(tmp_path, """\
        import jax
        from jax.experimental.shard_map import shard_map

        def _build(mesh):
            def program_state_b(state, ops):
                return state
            batched = jax.jit(jax.vmap(program_state_b))
            meshed = jax.jit(shard_map(program_state_b, mesh=mesh),
                             donate_argnums=(0,))
            return batched, meshed
        """, name="src/repro/core/engine/mesh_fixture.py")
    assert [f.rule for f in findings] == ["undonated-carry"]


def test_lint_undonated_carry_scope_and_waiver(tmp_path):
    src = """\
        import jax

        def _build():
            def step(state):
                return state
            return jax.jit(step)  # analysis: allow(undonated-carry) ok
        """
    assert _lint(tmp_path, src,
                 name="src/repro/core/engine/waived.py") == []
    # outside the engine package the carry rule does not apply
    outside = _lint(
        tmp_path,
        src.replace("  # analysis: allow(undonated-carry) ok", ""),
        name="pkg/driver.py")
    assert "undonated-carry" not in {f.rule for f in outside}


def test_lint_operand_threaded_through_helper_is_clean(tmp_path):
    """Regression: a runtime operand that reaches an inner traced body
    through a HELPER's parameter (traced caller -> helper call -> closure
    in the helper) is a tracer at every call site, not a baked constant.
    The call graph must propagate tracedness to the helper, or this shape
    false-positives as static-operand-capture."""
    findings = _lint(tmp_path, """\
        import jax

        def _scan(xs, lam):
            def body(c, x):
                return c + x * lam, x
            return jax.lax.scan(body, 0.0, xs)

        @jax.jit
        def solve(xs, lam):
            return _scan(xs, lam)
        """, name="src/repro/core/engine/helper_fixture.py")
    assert findings == []


def test_lint_shipped_tree_is_clean():
    assert lint.lint_paths(["src", "tests"]) == []


# ---------------------------------------------------------------------------
# deferred history recording under strict mode
# ---------------------------------------------------------------------------
def test_strict_run_defers_history_host_sync():
    """History recording holds device scalars inside the guarded dispatch
    region and materializes them in ONE explicit ``jax.device_get`` -- a
    strict session with ``record_history=True`` must run clean even
    though the guard forbids implicit device->host transfers (the control
    below shows an eager per-round ``float()`` would raise)."""
    import contextlib

    from repro.analysis.trace_guard import HostSyncError
    prob, topo = _problem_topo()
    sess = Session.compile(prob, topo, strict=True)
    res = sess.run(key=jax.random.PRNGKey(0))
    assert all(isinstance(h["gap"], float) for h in res.history)
    # and the materialized entries match an unguarded eager run exactly
    plain = Session.compile(prob, topo).run(key=jax.random.PRNGKey(0))
    assert [h["gap"] for h in res.history] == \
        [h["gap"] for h in plain.history]
    # control: the guard region is live (not a nullcontext).  On
    # accelerator backends an implicit float() inside it raises; on the
    # CPU backend jax's transfer guard is vacuous (device memory IS host
    # memory), so the raise can only be asserted off-CPU.
    assert not isinstance(sess._guard.dispatch_region(),
                          contextlib.nullcontext)
    if jax.default_backend() != "cpu":
        x = jnp.ones(())
        with pytest.raises(HostSyncError):
            with sess._guard.dispatch_region():
                float(x)
