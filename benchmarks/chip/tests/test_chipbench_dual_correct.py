"""``correct`` on the dual cells, at a tiny size on the CPU: the program
passes; the control (the reference in bfloat16 in the program's place)
fails; and each fault a dual cell can have, planted under the timed path,
turns ``correct`` false.  The limits are the committed ones of the dual
cells."""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

import chipbench_testutil as tu
from chipbench import dual, gen

CELLS = ("dual-epsilon-pallas", "dual-epsilon-vmap")


def _wrap_run(change):
    """A session hook replacing each warm call's result by
    ``change(previous result, result)``."""
    def hook(sess):
        orig = sess.run

        def run(rounds=None, *, warm_start=None, **kw):
            if warm_start is None:
                return orig(rounds, **kw)
            before = dataclasses.replace(
                warm_start, alpha=jnp.copy(warm_start.alpha),
                w=jnp.copy(warm_start.w),
                history=[dict(h) for h in warm_start.history])
            return change(before, orig(rounds, warm_start=warm_start, **kw))

        sess.run = run
        return sess
    return hook


def _unchanged(before, res):
    """A step that returns its state unchanged."""
    return dataclasses.replace(res, alpha=before.alpha, w=before.w)


def _half_left_out(before, res):
    """Half of the leaves' work left out (their rows keep the old duals)."""
    m = res.alpha.shape[0]
    keep = jnp.arange(m) < m // 2
    return dataclasses.replace(
        res, alpha=jnp.where(keep, res.alpha, before.alpha))


def _answer_altered(before, res):
    """The gap the loop reads, altered where it is produced."""
    hist = [dict(h) for h in res.history]
    hist[-1]["gap"] = hist[-1]["gap"] * 1.01
    return dataclasses.replace(res, history=hist)


@pytest.mark.parametrize("cell", CELLS)
def test_program_is_correct_at_tiny_size(monkeypatch, cell):
    name = tu.install(monkeypatch, tu.TINY_DUAL, limits=tu.real_limits(cell))
    res = tu.run_tiny(name)
    assert res["correct"], res["checks"]
    assert list(res["checks"]) == ["alpha_rel", "w_rel", "gap_rel"]
    assert res["attempted"] >= 2 and res["failed"] == 0


@pytest.mark.parametrize("fault", [_unchanged, _half_left_out,
                                   _answer_altered])
def test_planted_fault_is_not_correct(monkeypatch, fault):
    name = tu.install(monkeypatch, tu.TINY_DUAL,
                      limits=tu.real_limits(CELLS[0]))
    res = tu.run_tiny(name, session_hook=_wrap_run(fault))
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("seed", [3, 2**31 + 9, 2**34 + 1])
def test_control_is_not_correct(cell, seed):
    cfg = tu.TINY_DUAL
    X, y = gen.dual_data(cfg["data"], seed)
    key = gen.stream_key(seed, 1)
    snaps, sample = dual.control_calls(cfg, X, y, key, 3, 1)
    readings = dual.reference_readings(cfg, X, y, key, snaps, sample, 1)
    checks = dual.checks_of(readings, tu.real_limits(cell))
    assert not all(c.ok for c in checks), readings


def test_reference_repeats_itself_and_seeds_differ():
    cfg = tu.TINY_DUAL
    a = gen.dual_data(cfg["data"], 2**33 + 1)
    b = gen.dual_data(cfg["data"], 2**33 + 1)
    c = gen.dual_data(cfg["data"], 2**33 + 2)
    assert np.array_equal(np.asarray(a[0]), np.asarray(b[0]))
    assert not np.array_equal(np.asarray(a[0]), np.asarray(c[0]))
    assert gen.base_key(2**40).shape == (2,)
