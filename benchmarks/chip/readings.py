"""The readings that a cell's correctness limits are set from, in one
process (the benchmark's own runs never run this).

    python3 benchmarks/chip/readings.py --workload <name> \
        --seeds 1 2 3 ... --control-seeds 7 8 9 --seconds 2

For each ``--seeds`` seed: one whole run of the cell (set-up, a short
window of ``--seconds``, the comparison with the reference) and its
numbers.  For each ``--control-seeds`` seed: the control, that is the
reference put in the program's place one precision below the
configuration's (dual cells: bfloat16 data and arithmetic for float32;
LM cells: float8 e4m3 matmul inputs for bfloat16 activations), compared by
the same code.  Prints one JSON line per reading and, last, the largest
program reading and the smallest control reading of each number.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import run as bench_run  # noqa: E402
from chipbench import harness  # noqa: E402


def control_readings(config: dict, mix: dict, seed: int) -> dict:
    import jax.numpy as jnp
    if config["driver"] == "dual":
        from chipbench import dual, gen
        X, y = gen.dual_data(config["data"], seed)
        key = gen.stream_key(seed, 1)
        per_call = int(mix["rounds_per_call"])
        snaps, sample = dual.control_calls(config, X, y, key,
                                           int(mix["check_calls"]), per_call)
        out = dual.reference_readings(config, X, y, key, snaps, sample,
                                      per_call)
        del X, y
    else:
        from chipbench import lm
        n = int(mix["check_steps"])
        ref = lm.reference_run(config, mix, seed, n)
        ctl = lm.reference_run(config, mix, seed, n, q=jnp.float8_e4m3fn)
        out = lm.readings({"losses": ctl[0], "grad_norms": ctl[1],
                           "change_norms": ctl[2]}, ref)
    gc.collect()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)
    prog, ctl = {}, {}
    for seed in args.seeds:
        t0 = time.perf_counter()
        res = bench_run.execute(bench_run.parse(
            ["--workload", args.workload, "--seed", str(seed), "--seconds",
             str(args.seconds), "--trace", "0"]), t_start=t0)
        vals = {n: c["value"] for n, c in res["checks"].items()}
        for n, v in vals.items():
            prog[n] = max(prog.get(n, 0.0), v)
        print(json.dumps({"kind": "program", "seed": seed,
                          "correct": res["correct"], "readings": vals,
                          "metrics": res["metrics"]}), flush=True)
        gc.collect()
    if args.control_seeds:
        wl, config, mix, _, _, _ = bench_run.prepare(bench_run.parse(
            ["--workload", args.workload, "--seed", "0", "--seconds", "1"]))
        harness.program_path()
        harness.setup_cache()
        for seed in args.control_seeds:
            vals = control_readings(config, mix, seed)
            for n, v in vals.items():
                ctl[n] = min(ctl.get(n, float("inf")), v)
            print(json.dumps({"kind": "control", "seed": seed,
                              "readings": vals}), flush=True)
    print(json.dumps({"workload": args.workload, "program_max": prog,
                      "control_min": ctl}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
