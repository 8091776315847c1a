"""Driver of the TreeSync LM training cells.

The program is built as ``launch/train.train`` builds it: ``Problem.lm`` +
``Topology.from_mesh`` + ``Session.compile(backend="mesh")`` on the
configuration's mesh.  ``LMSession.run(warm_start=...)`` copies the whole
state (params and AdamW moments, ~5 GB at four danube layers), which two
copies of would not fit one chip, so every call here starts from the seed:

* set-up: ``run(steps=1)`` compiles the step and gives the first gradient
  as the optimizer got it (AdamW's first moment after one step);
  ``run(steps=check_steps)`` gives the first losses and the parameters'
  change, and times the step;
* window: one ``run(steps=S)`` from the seed, ``S`` sized from that step
  time, the loss read on the host every step (as ``train()`` does).  The
  window's clock starts when step 1 has returned, so it holds steps 2..S:
  no initialisation and no compilation.  Its first ``check_steps`` losses
  must repeat set-up's exactly, which ties the checked steps to the
  window's own call.

``correct`` compares set-up's readings with the configuration's plain
reference run from the same seed, once the window is over and the
program's state is freed.  The configuration names its reference module,
``chipbench/<name>.py``, under ``reference`` (``lm_ref``, the dense
decoder, where it names none); the module makes the weights and the loss,
and counts the work that ``lm_mfu`` reads (``ctx["reference"]``).  The
AdamW step, the token stream and the leaf norms are shared by every
reference (``lm_ref``, ``gen``).
"""
from __future__ import annotations

import gc
import math
import time
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import gen, harness, lm_ref
from chipbench.harness import (Check, CompileCounter, Outcome, Run,
                               memory_peak_bytes)

DEFAULT_REFERENCE = "lm_ref"


def reference_of(cfg: dict):
    """The plain reference module the configuration names."""
    return harness.load_reference(cfg.get("reference", DEFAULT_REFERENCE))


def build_session(cfg: dict, mix: dict, seed: int):
    from repro.api import Problem, Session, Topology
    from repro.configs.base import ModelConfig
    from repro.core.engine.lm import present_axes
    from repro.core.engine.mesh import make_mesh
    from repro.optim import get_optimizer

    mcfg = ModelConfig(**cfg["model"])
    mesh_cfg = cfg["mesh"]
    mesh = make_mesh(tuple(mesh_cfg["shape"]), tuple(mesh_cfg["axes"]))
    opt = cfg["optimizer"]
    optimizer = get_optimizer(mcfg, lr=float(opt["lr"]),
                              weight_decay=float(opt["weight_decay"]))
    prob = Problem.lm(mcfg, optimizer, batch=int(mix["batch"]),
                      seq=int(mix["seq"]), seed=gen.program_seed(seed))
    sync_axes = tuple(cfg["sync_axes"])
    periods = list(cfg["periods"])
    L = max(len(present_axes(mesh, sync_axes)), 1)
    ps = (periods + [periods[-1]] * L)[:L]
    topo = Topology.from_mesh(mesh, sync_axes=sync_axes, periods=ps)
    return Session.compile(prob, topo, backend="mesh", mesh=mesh)


def _replica0(tree):
    return jax.tree.map(lambda t: t[0], tree)


def worst_leaf_gap(prog: Dict[str, float], ref: Dict[str, float],
                   keep=None) -> float:
    """max over leaves of | |prog| - |ref| | / max(|ref|, median |ref|)."""
    names = [n for n in ref if keep is None or keep(n)]
    med = float(np.median([ref[n] for n in names]))
    worst = 0.0
    for n in names:
        if n not in prog:
            return float("inf")
        worst = max(worst, abs(prog[n] - ref[n]) / max(ref[n], med, 1e-30))
    return worst if np.isfinite(worst) else float("inf")


def _opt_tuple(cfg: dict):
    o = cfg["optimizer"]
    return (float(o["lr"]), float(o["b1"]), float(o["b2"]), float(o["eps"]),
            float(o["weight_decay"]), float(o["grad_clip"]))


def reference_run(cfg: dict, mix: dict, seed: int, n_steps: int, q=None):
    """The reference from the seed: the first ``n_steps`` losses, the
    first clipped gradient's leaf norms and the change of each leaf after
    ``n_steps`` steps."""
    model, ref = cfg["model"], reference_of(cfg)
    with jax.default_matmul_precision("highest"):
        p0 = jax.jit(lambda: ref.init(model, seed))()
        params = jax.tree.map(jnp.copy, p0)
        state = lm_ref.adamw_init(params)
        losses, g1 = [], None
        for step in range(n_steps):
            batch = gen.lm_batch(model["vocab_size"], int(mix["batch"]),
                                 int(mix["seq"]), step, seed)
            params, state, loss, g = lm_ref.train_step(
                params, state, batch, loss=ref.loss_fn, opt=_opt_tuple(cfg),
                q=q, model_items=tuple(sorted(model.items())))
            losses.append(float(loss))
            if g1 is None:
                g1 = lm_ref.leaf_norms(g)
            del g
        change = lm_ref.diff_norms(params, p0)
    return losses, g1, change


def readings(prog: dict, ref: tuple) -> Dict[str, float]:
    losses, g1, change = ref
    loss_rel = max(abs(a - b) / abs(b) for a, b in zip(
        prog["losses"], losses, strict=True))
    med = float(np.median(list(g1.values())))
    # leaves whose reference gradient is nought to rounding move under Adam
    # by round-off alone: left out of the change by this rule, not by name
    moving = {n for n, v in g1.items() if v >= 1e-3 * med}
    return {
        "loss_rel": loss_rel if np.isfinite(loss_rel) else float("inf"),
        "grad_norm_rel": worst_leaf_gap(prog["grad_norms"], g1),
        "change_norm_rel": worst_leaf_gap(prog["change_norms"], change,
                                          keep=lambda n: n in moving),
    }


def run(r: Run, devices, counter: CompileCounter,
        session_hook: Optional[Callable] = None) -> Outcome:
    cfg, mix = r.config, r.mix
    n_check = int(mix["check_steps"])
    b1 = float(cfg["optimizer"]["b1"])
    tokens_per_step = int(mix["batch"]) * int(mix["seq"])
    sess = build_session(cfg, mix, r.seed)
    if session_hook is not None:
        sess = session_hook(sess)
    r.phase("session_compile")

    # step 1 from the seed: compiles the step, gives the first gradient
    with r.span("LMSession.run"):
        res = sess.run(steps=1)
    mu = _replica0(res.state.opt_state["mu"])
    grad_norms = lm_ref.leaf_norms(jax.tree.map(lambda m: m / (1.0 - b1),
                                                mu))
    del res, mu
    r.phase("first_step")
    # the first check_steps steps from the seed: losses and the change
    with r.span("LMSession.run"):
        res = sess.run(steps=n_check)
    losses = [h["loss"] for h in res.history]
    step_s = float(np.median([h["sec"] for h in res.history[1:]]))
    p0 = _replica0(sess.init_state().params)
    change_norms = lm_ref.diff_norms(_replica0(res.state.params), p0)
    del res, p0
    gc.collect()
    r.phase("check_steps")
    r.end_setup()

    n_steps = 1 + max(2, math.ceil(r.seconds / max(step_s, 1e-3)))
    clock = {}

    def on_step(entry):
        if entry["step"] == 1:
            r.start_window()
            clock["t0"] = time.perf_counter()

    with counter.counting():
        with r.span("LMSession.run"):
            res = sess.run(steps=n_steps, on_step=on_step)
        jax.block_until_ready(res.state.params)
        window_s = time.perf_counter() - clock["t0"]
        r.stop_window()
    steps = n_steps - 1
    win_losses = [h["loss"] for h in res.history]
    peak = memory_peak_bytes(devices)
    r.log(f"window: {steps} steps in {window_s!r} s (set-up step "
          f"{step_s!r} s); losses {win_losses[:n_check]} ... "
          f"{win_losses[-1]!r}; compilations in the window {counter.count}")
    del sess, res
    gc.collect()

    repeat = max(abs(a - b) for a, b in zip(
        win_losses[:n_check], losses, strict=True))
    ref = reference_run(cfg, mix, r.seed, n_check)
    got = readings({"losses": losses, "grad_norms": grad_norms,
                    "change_norms": change_norms}, ref)
    r.log(f"reference losses {ref[0]}")
    checks = [Check("window_repeats_setup", float(repeat),
                    float(r.limits["window_repeats_setup"]))]
    checks += [Check(n, v, float(r.limits[n])) for n, v in got.items()]
    failed = sum(1 for v in win_losses if not np.isfinite(v))
    return Outcome(
        end_to_end={"tokens_per_s": steps * tokens_per_step / window_s,
                    "setup_s": r.setup_s},
        checks=checks, attempted=steps, failed=failed,
        memory_peak_bytes=peak, reference=reference_of(cfg),
        counts={"steps": steps, "window_s": window_s,
                "tokens": steps * tokens_per_step,
                "compiles_in_window": counter.count,
                "model": cfg["model"], "batch": int(mix["batch"]),
                "seq": int(mix["seq"])})
