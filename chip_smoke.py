"""Smoke run of the system's main paths on a TPU chip.

    python chip_smoke.py               # one chip: dual solve + LM phase
    python chip_smoke.py --four-chip   # four chips: mesh dual solve only

One chip (the default):

1. Dual solve at the epsilon shape (PASCAL large-scale challenge): dense
   f32, m = 401,408 rows (400,000 rounded up to whole 8-row multiples per
   leaf block), d = 2,000, smoothed hinge, generated from ``--seed``.  The
   tree is ``Topology.two_level(4, 128, 784)``: 512 leaf blocks of
   784 x 2,000 on the chip.  ``Session.compile`` + ``Session.run`` on the
   ``vmap`` backend (XLA leaf solves) and on the ``pallas`` backend (the
   compiled Mosaic leaf kernel) with the same key, until the duality gap
   is <= 1e-3 or the round budget runs out.  The two backends' iterates
   must agree within 1e-4 of their scale, and the gap must fall
   monotonically.
2. TreeSync LM training through ``repro.launch.train.train`` at
   h2o-danube-1.8b's published widths, cut to 4 layers (fp32 params and
   AdamW state fit in 16 GB), seq 2048, on a (1, 1) mesh; every loss must
   be finite.

``--four-chip`` runs only the dual solve on ``backend="mesh"`` over
``Topology.two_level(2, 2, 100352)`` (level 1 across the four chips),
once per ``mesh_sync`` lowering (``psum``, ``reduce_scatter``), against
the ``vmap`` backend on one device of the same process.

Each phase prints one JSON line.  The last line of standard output is
``{"ok": true, "device": {...}}`` with the device as JAX reports it; the
script exits non-zero, with no such line, when the default device is not
a TPU or any check fails.  Everything runs in this one process.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.api import Problem, Session, Topology  # noqa: E402
from repro.compat import enable_compile_cache, on_tpu  # noqa: E402
from repro.data.synthetic import gaussian_classification  # noqa: E402

# the tree: GROUPS x LEAVES_PER_GROUP leaf blocks of LEAF_ROWS rows
GROUPS, LEAVES_PER_GROUP, LEAF_ROWS = 4, 128, 784
EPSILON_D = 2_000
GAP_TARGET = 1e-3
ROUND_BUDGET = 10
LOCAL_STEPS = 784          # one pass over a 784-row leaf block per sync
BACKEND_RTOL = 1e-4        # engine claim: backends agree to ~1e-4 rtol
LM_LAYERS, LM_SEQ, LM_BATCH, LM_STEPS = 4, 2048, 1, 6


def emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


def fail(msg: str) -> None:
    raise SystemExit(f"chip_smoke: FAILED: {msg}")


def check(cond: bool, msg: str) -> None:
    if not cond:
        fail(msg)


def block(res) -> None:
    jax.block_until_ready((res.alpha, res.w))


def solve(problem, topology, key, rounds, **compile_kw) -> dict:
    """Compile, warm up (two rounds: a cold start and a warm restart, so
    every input layout the timed loop passes is compiled), then time a
    fresh run of up to ``rounds`` root rounds that stops at
    ``GAP_TARGET``."""
    t0 = time.perf_counter()
    sess = Session.compile(problem, topology, **compile_kw)
    block(sess.run(1, warm_start=sess.run(1, key=key)))
    warmup_s = time.perf_counter() - t0
    print(f"[{compile_kw}] warm-up (compile + 2 rounds) {warmup_s:.3f}s",
          file=sys.stderr, flush=True)
    # warm restarts continue the RNG chain: round by round is bit-identical
    # to one long run, and lets the loop stop at the target
    t0 = time.perf_counter()
    res = sess.run(1, key=key)
    gaps = [h["gap"] for h in res.history]
    while gaps[-1] > GAP_TARGET and len(gaps) <= rounds:
        res = sess.run(1, warm_start=res)
        gaps.append(res.history[-1]["gap"])
        print(f"[{compile_kw}] round {len(gaps) - 1}: gap {gaps[-1]:.6e} "
              f"at {time.perf_counter() - t0:.3f}s", file=sys.stderr,
              flush=True)
    block(res)
    run_s = time.perf_counter() - t0
    check(all(np.isfinite(gaps)), f"non-finite gap {gaps}")
    check(all(b <= a for a, b in zip(gaps, gaps[1:])),
          f"gap did not fall monotonically: {gaps}")
    check(res.alpha.shape == (problem.m,) and res.w.shape == (problem.d,),
          "wrong iterate shapes")
    check(bool(np.isfinite(np.asarray(res.w)).all()), "non-finite w")
    return {"res": res, "warmup_s": warmup_s, "run_s": run_s,
            "rounds": len(gaps) - 1, "gaps": gaps}


def compare(ref: dict, other: dict) -> dict:
    """Largest |delta| of the iterates, absolute and over the reference's
    max-norm; fails beyond ``BACKEND_RTOL``."""
    out = {}
    for name in ("alpha", "w"):
        a = np.asarray(getattr(ref["res"], name), np.float64)
        b = np.asarray(getattr(other["res"], name), np.float64)
        delta = float(np.max(np.abs(a - b)))
        scale = float(np.max(np.abs(a)))
        out[f"max_abs_d{name}"] = delta
        out[f"max_rel_d{name}"] = delta / scale
        check(delta <= BACKEND_RTOL * scale,
              f"{name} differs by {delta} (scale {scale})")
    check(ref["rounds"] == other["rounds"], "round counts differ")
    return out


def epsilon_problem(seed: int):
    m = GROUPS * LEAVES_PER_GROUP * LEAF_ROWS
    X, y = gaussian_classification(m=m, d=EPSILON_D,
                                   key=jax.random.PRNGKey(seed))
    # lambda = 1/m on unit-norm rows; the rows have ||x||^2 ~ d
    return Problem.svm(X, y, lam=EPSILON_D / m, smoothing=1.0)


def summary(tag: str, run: dict) -> dict:
    return {"warmup_s": run["warmup_s"], "run_s": run["run_s"],
            "rounds": run["rounds"], "final_gap": run["gaps"][-1],
            "gaps": run["gaps"], "backend": tag}


def dual_phase(seed: int) -> None:
    problem = epsilon_problem(seed)
    topology = Topology.two_level(GROUPS, LEAVES_PER_GROUP, LEAF_ROWS,
                                  local_steps=LOCAL_STEPS)
    key = jax.random.PRNGKey(seed + 1)
    runs = {b: solve(problem, topology, key, ROUND_BUDGET, backend=b)
            for b in ("vmap", "pallas")}
    emit({"phase": "dual", "m": problem.m, "d": problem.d,
          "loss": problem.loss.name, "lam": problem.lam,
          "leaves": GROUPS * LEAVES_PER_GROUP, "leaf_rows": LEAF_ROWS,
          "local_steps": LOCAL_STEPS,
          "round_budget": ROUND_BUDGET, "gap_target": GAP_TARGET,
          "vmap": summary("vmap", runs["vmap"]),
          "pallas": summary("pallas", runs["pallas"]),
          "pallas_vs_vmap": compare(runs["vmap"], runs["pallas"])})


def lm_phase(seed: int, cfg=None) -> None:
    """``cfg`` defaults to h2o-danube-1.8b cut to ``LM_LAYERS`` layers."""
    from repro.configs.h2o_danube_1_8b import FULL
    from repro.core.engine.mesh import make_mesh
    from repro.launch.train import train
    if cfg is None:
        cfg = dataclasses.replace(FULL, num_layers=LM_LAYERS)
    t0 = time.perf_counter()
    out = train(cfg, steps=LM_STEPS, batch=LM_BATCH, seq=LM_SEQ,
                mesh=make_mesh((1, 1), ("data", "model")), log_every=1,
                seed=seed)
    wall_s = time.perf_counter() - t0
    hist = out["history"]
    losses = [h["loss"] for h in hist]
    check(len(hist) == LM_STEPS, f"ran {len(hist)} of {LM_STEPS} steps")
    check(all(np.isfinite(losses)), f"non-finite loss {losses}")
    steady = [h["sec"] for h in hist[1:]]
    emit({"phase": "lm", "model": cfg.name,
          "reduced": {"num_layers": [FULL.num_layers, cfg.num_layers]},
          "params_m": cfg.param_count() / 1e6, "seq": LM_SEQ,
          "batch": LM_BATCH, "steps": LM_STEPS,
          "first_step_s": hist[0]["sec"],
          "median_step_s": float(np.median(steady)),
          "tokens_per_s": LM_BATCH * LM_SEQ / float(np.median(steady)),
          "losses": losses, "wall_s": wall_s})


def four_chip_phase(seed: int) -> None:
    check(len(jax.devices()) >= 4,
          f"--four-chip needs 4 devices, found {len(jax.devices())}")
    problem = epsilon_problem(seed)
    topology = Topology.two_level(2, 2, problem.m // 4,
                                  local_steps=LOCAL_STEPS)
    key = jax.random.PRNGKey(seed + 1)
    ref = solve(problem, topology, key, ROUND_BUDGET, backend="vmap")
    record = {"phase": "dual_four_chip", "m": problem.m, "d": problem.d,
              "leaves": 4, "leaf_rows": problem.m // 4,
              "vmap": summary("vmap", ref)}
    for sync in ("psum", "reduce_scatter"):
        run = solve(problem, topology, key, ROUND_BUDGET, backend="mesh",
                    mesh_use_kernel=False, mesh_sync=sync)
        record[sync] = summary(f"mesh/{sync}", run)
        record[f"{sync}_vs_vmap"] = compare(ref, run)
        del run
        gc.collect()
    emit(record)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the mesh dual solve across 4 chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu" or not on_tpu():
        fail(f"no TPU: JAX's default device is {dev.platform} "
             f"({dev.device_kind})")
    emit({"phase": "device", "platform": dev.platform,
          "kind": dev.device_kind, "count": len(jax.devices()),
          "compile_cache": enable_compile_cache()})
    if args.four_chip:
        four_chip_phase(args.seed)
    else:
        dual_phase(args.seed)
        gc.collect()            # drop the 3.2 GB problem before the LM
        lm_phase(args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
