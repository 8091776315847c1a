"""Driver of the tree-network dual solve cells.

Set-up makes the data from the seed on the device, compiles the session
and drives it through its first ``check_calls`` calls from the seed (a
cold start, then warm restarts: every shape the window uses).  The window
continues that same session call by call: one
``Session.run(rounds_per_call, warm_start=prev)`` per call, reading the
duality gap before the next call, as a user who stops at a gap target
does (the traffic mix sets ``rounds_per_call``).

``correct`` compares, with the plain reference of ``dual_ref``:

* the first ``check_calls`` calls from the seed (alpha, w and gap), and
* one call of the window drawn from the seed, recomputed from the
  program's own state before it (as a served answer is checked against
  the reference run over the served prompt).
"""
from __future__ import annotations

import gc
import time
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import dual_ref, gen
from chipbench.harness import (Check, CompileCounter, Outcome, Run,
                                memory_peak_bytes)


def lam_of(cfg: dict) -> float:
    lam, m, d = cfg["lam"], cfg["data"]["m"], cfg["data"]["d"]
    return d / m if lam == "d/m" else float(lam)


def tree_shape(topo: dict):
    """(branching top-down, rounds per depth, H) of a two-level tree."""
    if topo["kind"] != "two_level":
        raise ValueError(f"unknown topology kind {topo['kind']!r}")
    branching = (int(topo["n_groups"]), int(topo["workers_per_group"]))
    return branching, (1, int(topo["group_rounds"])), int(topo["local_steps"])


def build_session(cfg: dict, X, y):
    """The program under test, as the configuration states it."""
    from repro.api import Problem, Session, Topology
    t = cfg["topology"]
    problem = Problem.svm(X, y, lam=lam_of(cfg), smoothing=cfg["smoothing"])
    topo = Topology.two_level(
        int(t["n_groups"]), int(t["workers_per_group"]),
        int(t["m_per_worker"]), group_rounds=int(t["group_rounds"]),
        local_steps=int(t["local_steps"]))
    kw = dict(cfg.get("session", {}))
    return Session.compile(problem, topo, **kw)


def _host(res) -> dict:
    return {"alpha": np.asarray(res.alpha), "w": np.asarray(res.w),
            "gap": float(res.history[-1]["gap"])}


# ---------------------------------------------------------------------------
# the comparison
# ---------------------------------------------------------------------------
def _ref_kw(cfg: dict, m: int, dtype=jnp.float32) -> dict:
    branching, rounds, H = tree_shape(cfg["topology"])
    return dict(branching=branching, rounds=rounds, H=H,
                lm=float(lam_of(cfg)) * m, g=float(cfg["smoothing"]),
                dtype=dtype)


def _advance(key, n: int, k_root: int):
    for _ in range(n):
        key = dual_ref.next_root_key(key, k_root)
    return key


def _ref_call(X, y, a, w, key, n_rounds: int, kw: dict):
    """``n_rounds`` root rounds of the reference; returns (a, w, key)."""
    for _ in range(n_rounds):
        a, w = dual_ref.root_round(X, y, a, w, key, **kw)
        key = dual_ref.next_root_key(key, kw["branching"][0])
    return a, w, key


def reference_readings(cfg: dict, X, y, key, snaps: List[dict],
                       sample: Optional[dict], per_call: int
                       ) -> Dict[str, float]:
    """Worst relative gaps between the program's calls and the
    reference's: the calls of ``snaps`` from the seed's key, and the
    sampled call ``sample['t']`` from the program's state before it.  Each
    call is ``per_call`` root rounds."""
    lam, g = lam_of(cfg), float(cfg["smoothing"])
    m, d = X.shape
    kw = _ref_kw(cfg, m)
    worst = {"alpha_rel": 0.0, "w_rel": 0.0, "gap_rel": 0.0}

    def compare(got: dict, a_ref, w_ref):
        gap_ref = float(dual_ref.duality_gap(X, y, a_ref, lam, g=g))
        a_ref, w_ref = np.asarray(a_ref), np.asarray(w_ref)
        for name, have, want in (("alpha", got["alpha"], a_ref),
                                 ("w", got["w"], w_ref)):
            rel = float(np.max(np.abs(have.astype(np.float64) - want))
                        / max(np.max(np.abs(want)), 1e-30))
            worst[f"{name}_rel"] = max(worst[f"{name}_rel"], rel)
        rel = abs(got["gap"] - gap_ref) / max(abs(gap_ref), 1e-30)
        worst["gap_rel"] = max(worst["gap_rel"], rel)

    a = jnp.zeros((m,), jnp.float32)
    w = jnp.zeros((d,), jnp.float32)
    k = key
    for got in snaps:
        a, w, k = _ref_call(X, y, a, w, k, per_call, kw)
        compare(got, a, w)
    if sample is not None:
        k = _advance(key, (sample["t"] - 1) * per_call, kw["branching"][0])
        a, w, _ = _ref_call(X, y, jnp.asarray(sample["prev_alpha"]),
                            jnp.asarray(sample["prev_w"]), k, per_call, kw)
        compare(sample, a, w)
    # a NaN anywhere is a failed comparison, never a pass
    return {n: (v if np.isfinite(v) else float("inf"))
            for n, v in worst.items()}


def control_calls(cfg: dict, X, y, key, n: int, per_call: int,
                  dtype=jnp.bfloat16):
    """The reference in the program's place, one precision below the
    configuration's: ``n`` calls from the seed, then one more as the
    window's sample (the control's stand-ins for ``snaps``/``sample``)."""
    lam, g = lam_of(cfg), float(cfg["smoothing"])
    m, d = X.shape
    kw = _ref_kw(cfg, m, dtype)
    a = jnp.zeros((m,), jnp.float32)
    w = jnp.zeros((d,), jnp.float32)
    k, snaps, prev = key, [], None
    for _ in range(n + 1):
        prev = (np.asarray(a), np.asarray(w))
        a, w, k = _ref_call(X, y, a, w, k, per_call, kw)
        gap = float(dual_ref.duality_gap(X, y, a, lam, g=g, dtype=dtype))
        snaps.append({"alpha": np.asarray(a), "w": np.asarray(w), "gap": gap})
    sample = dict(snaps.pop(), t=n + 1, prev_alpha=prev[0], prev_w=prev[1])
    return snaps, sample


def checks_of(readings: Dict[str, float], limits: Dict[str, float]
              ) -> List[Check]:
    return [Check(n, readings[n], float(limits[n])) for n in readings]


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------
def run(r: Run, devices, counter: CompileCounter,
        session_hook: Optional[Callable] = None) -> Outcome:
    cfg, mix = r.config, r.mix
    n_check = int(mix["check_calls"])
    per_call = int(mix["rounds_per_call"])
    X, y = gen.dual_data(cfg["data"], r.seed)
    jax.block_until_ready((X, y))
    r.phase("data")
    sess = build_session(cfg, X, y)
    if session_hook is not None:
        sess = session_hook(sess)
    r.phase("session_compile")
    key = gen.stream_key(r.seed, 1)

    def call(warm):
        with r.span("Session.run"):
            if warm is None:
                return sess.run(per_call, key=key, history_every=per_call)
            return sess.run(per_call, warm_start=warm,
                            history_every=per_call)

    # calls 1..n_check from the seed: the cold start compiles (or loads
    # the cache), the warm restarts warm the window's own call
    res = call(None)
    snaps = [_host(res)]
    r.phase("first_call")
    t0 = time.perf_counter()
    for _ in range(n_check - 1):
        res = call(res)
        snaps.append(_host(res))
    est = (time.perf_counter() - t0) / max(n_check - 1, 1)
    jax.block_until_ready((jnp.copy(res.alpha), jnp.copy(res.w)))
    r.phase("warm_calls")
    r.end_setup()

    # the window's call to check, drawn from the seed
    n_est = max(2, int(r.seconds / max(est, 1e-3)))
    rng = np.random.default_rng(gen.program_seed(r.seed))
    t_sample = n_check + 1 + int(rng.integers(0, max(1, n_est // 2)))
    t, gaps, prev, cur = n_check, [], None, None
    with counter.counting(), r.window():
        t_start = time.perf_counter()
        while True:
            if t + 1 == t_sample:
                prev = (jnp.copy(res.alpha), jnp.copy(res.w))
            res = call(res)
            gaps.append(res.history[-1]["gap"])
            t += 1
            if t == t_sample:
                cur = (jnp.copy(res.alpha), jnp.copy(res.w), gaps[-1])
            if time.perf_counter() - t_start >= r.seconds and t >= t_sample:
                break
        jax.block_until_ready((res.alpha, res.w))
        window_s = time.perf_counter() - t_start
    calls = t - n_check
    rounds = calls * per_call
    peak = memory_peak_bytes(devices)
    sample = {"t": t_sample, "prev_alpha": np.asarray(prev[0]),
              "prev_w": np.asarray(prev[1]), "alpha": np.asarray(cur[0]),
              "w": np.asarray(cur[1]), "gap": float(cur[2])}
    r.log(f"window: {rounds} root rounds in {window_s!r} s; gap at the "
          f"close {gaps[-1]!r}; compilations in the window "
          f"{counter.count}; checked call {t_sample}")
    del sess, res, prev, cur
    gc.collect()

    readings = reference_readings(cfg, X, y, key, snaps, sample, per_call)
    failed = sum(1 for g in gaps if not np.isfinite(g))
    return Outcome(
        end_to_end={"round_ms": 1e3 * window_s / rounds,
                    "setup_s": r.setup_s},
        checks=checks_of(readings, r.limits),
        attempted=calls, failed=failed, memory_peak_bytes=peak,
        counts={"rounds": rounds, "calls": calls, "window_s": window_s,
                "compiles_in_window": counter.count,
                "m": int(X.shape[0]), "d": int(X.shape[1]),
                "topology": cfg["topology"],
                "backend": cfg.get("session", {}).get("backend", "vmap")})
