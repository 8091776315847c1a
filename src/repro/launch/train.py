"""Training driver: a thin CLI over the Session-driven LM program.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-32b --smoke \
        --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt

Everything here is plumbing: ``Problem.lm`` + ``Session.compile`` build
the replica-stacked train program (``repro.api.lm.LMSession``), the
unified ``CheckpointPolicy``/``resume`` path handles restart (one code
path, any periods), ``--sync`` is just ``periods=(1, ...)`` on the SAME
program (with SGD bit-identical to plain DP -- tested), and ``--adapt-h``
attaches a straggler policy whose eq.-(12) replanning feeds the runtime
periods operand.
"""
from __future__ import annotations

import argparse
import warnings
from typing import Any, Dict, Optional, Sequence

from repro.api import CheckpointPolicy, Problem, Session, Topology
from repro.compat import enable_compile_cache
from repro.configs.registry import ARCHS
from repro.launch.mesh import make_host_mesh
from repro.optim import get_optimizer


def train(cfg, *, steps: int, batch: int, seq: int, mesh=None,
          mode: Optional[str] = None, sync: bool = False,
          periods: Sequence[int] = (4,),
          ckpt_dir: Optional[str] = None, ckpt_every: int = 50,
          lr: float = 3e-4, adapt_h: bool = False,
          log_every: int = 10, seed: int = 0) -> Dict[str, Any]:
    """Train ``cfg`` for ``steps`` optimizer steps; returns
    ``{"history", "final_loss", "wall_s"}`` (history entries
    ``{"step", "loss", "sec"}``, as before).

    ``mode=`` is a deprecated shim: ``mode="sync"`` means ``sync=True``
    (all periods 1 -- every step a full barrier), ``mode="treesync"`` the
    default schedule.  ``ckpt_every`` is in optimizer steps; snapshots
    land on outer-round boundaries."""
    if mode is not None:
        warnings.warn(
            "train(mode=...) is deprecated: both modes are ONE program "
            "now -- use sync=True (periods all 1) or periods=",
            DeprecationWarning, stacklevel=2)
        if mode not in ("treesync", "sync"):
            raise ValueError(f"unknown mode {mode!r}")
        sync = mode == "sync"

    mesh = mesh or make_host_mesh()
    opt = get_optimizer(cfg, lr=lr)
    prob = Problem.lm(cfg, opt, batch=batch, seq=seq, seed=seed)

    # fit the period list to the mesh's present sync axes (pad with the
    # last value / truncate), then lower the tree once
    from repro.core.engine.lm import present_axes
    axes = present_axes(mesh, ("data", "pod"))
    L = max(len(axes), 1)
    ps = [1] * L if sync else (
        list(periods) + [periods[-1]] * (L - len(periods)))[:L]
    topo = Topology.from_mesh(mesh, sync_axes=("data", "pod"), periods=ps)
    sess = Session.compile(prob, topo, backend="mesh", mesh=mesh)
    spr = sess.steps_per_round

    def on_step(entry):
        if entry["step"] % log_every == 0:
            print(f"[train] step {entry['step']}: loss={entry['loss']:.4f} "
                  f"{entry['sec']*1e3:.0f}ms", flush=True)

    straggler = None
    if adapt_h:
        if ckpt_dir:
            raise ValueError("--adapt-h does not compose with --ckpt-dir "
                             "(straggler-adaptive runs are not "
                             "checkpointable); pick one")
        from repro.runtime.straggler import AdaptiveSchedule, StragglerPolicy
        straggler = StragglerPolicy(seed=seed, adaptive=AdaptiveSchedule())

    if ckpt_dir:
        policy = CheckpointPolicy(directory=ckpt_dir, keep=3,
                                  every=max(1, int(ckpt_every) // spr))
        last = policy.manager().latest_step()
        if last is not None:
            # continue toward THIS call's step target; report only the
            # newly run steps (the prefix is the previous run's history)
            res = sess.resume(policy, steps=max(steps - last, 0),
                              on_step=on_step)
            print(f"[train] resumed from step {last}; "
                  f"ran to step {int(res.state.step)}")
            history = [e for e in res.history if e["step"] > last]
        else:
            res = sess.run(steps=steps, checkpoint=policy, on_step=on_step)
            history = res.history
    else:
        res = sess.run(steps=steps, straggler=straggler, on_step=on_step)
        history = res.history

    return {"history": history, "final_loss": res.final_loss,
            "wall_s": res.wall_s}


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list(ARCHS))
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--sync", action="store_true",
                    help="all periods 1: every step a full barrier "
                         "(the star special case; DP-equivalent)")
    ap.add_argument("--mode", default=None, choices=["treesync", "sync"],
                    help="deprecated: use --sync / --periods")
    ap.add_argument("--periods", type=int, nargs="+", default=[4])
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--adapt-h", action="store_true")
    args = ap.parse_args()

    mod = ARCHS[args.arch]
    cfg = mod.SMOKE if args.smoke else mod.FULL
    out = train(cfg, steps=args.steps, batch=args.batch, seq=args.seq,
                mode=args.mode, sync=args.sync, periods=args.periods,
                lr=args.lr, ckpt_dir=args.ckpt_dir,
                ckpt_every=args.ckpt_every, adapt_h=args.adapt_h)
    print(f"[train] done: final loss {out['final_loss']:.4f} "
          f"in {out['wall_s']:.1f}s")


if __name__ == "__main__":
    main()
