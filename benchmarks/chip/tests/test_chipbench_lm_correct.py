"""``correct`` on the LM cell, at a tiny size on the CPU: the program
passes; the control (the reference with float8 matmul inputs in the
program's place) fails; and each fault a training cell can have, planted
in the step under the timed path, turns ``correct`` false.  The limits
are the committed ones of ``lm-danube-4l``."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

import chipbench_testutil as tu
from chipbench import lm

CELL = "lm-danube-4l"


def _wrap_step(change):
    """A session hook passing every step through ``change``."""
    def hook(sess):
        orig = sess._executor

        def executor(**kw):
            fn = orig(**kw)

            def step(state, batch, periods, part, lr):
                return change(fn, state, batch, periods, part, lr)
            return step

        sess._executor = executor
        return sess
    return hook


def _unchanged(fn, state, batch, *rest):
    """A step that returns its state unchanged."""
    _, metrics = fn(jax.tree.map(jnp.copy, state), batch, *rest)
    return state, metrics


def _half_batch(fn, state, batch, *rest):
    """Half of the batch left out, the mean taken over the rest."""
    half = {k: v[:, : v.shape[1] // 2] for k, v in batch.items()}
    return fn(state, half, *rest)


def _loss_altered(fn, state, batch, *rest):
    """The loss, altered where it is produced."""
    state, metrics = fn(state, batch, *rest)
    return state, dict(metrics, loss=metrics["loss"] * 1.01)


def _run(monkeypatch, hook=None):
    name = tu.install(monkeypatch, tu.tiny_lm_config(), mix=tu.TINY_LM_MIX,
                      limits=tu.real_limits(CELL),
                      traffic="steps_back_to_back")
    return tu.run_tiny(name, seconds=0.5, session_hook=hook)


def test_program_is_correct_at_tiny_size(monkeypatch):
    res = _run(monkeypatch)
    assert res["correct"], res["checks"]
    assert list(res["checks"]) == ["window_repeats_setup", "loss_rel",
                                   "grad_norm_rel", "change_norm_rel"]


@pytest.mark.parametrize("fault", [_unchanged, _half_batch, _loss_altered])
def test_planted_fault_is_not_correct(monkeypatch, fault):
    res = _run(monkeypatch, _wrap_step(fault))
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("seed", [4, 2**31 + 3, 2**33 + 11])
def test_control_is_not_correct(seed):
    cfg, mix = tu.tiny_lm_config(), tu.TINY_LM_MIX
    ref = lm.reference_run(cfg, mix, seed, 3)
    ctl = lm.reference_run(cfg, mix, seed, 3, q=jnp.float8_e4m3fn)
    got = lm.readings({"losses": ctl[0], "grad_norms": ctl[1],
                       "change_norms": ctl[2]}, ref)
    limits = tu.real_limits(CELL)
    assert any(v > limits[n] for n, v in got.items()), got
