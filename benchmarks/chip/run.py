"""Run one benchmark cell once, on the chips of this machine.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

The cell, its configuration, traffic mix, limits and per-layer metrics are
found by name (``BENCHMARK.json`` at the checkout's root, and the files
under ``benchmarks/chip/``).  Set-up (data or weights from the seed,
compilation or the persistent cache at ``.jax_cache/``, warm-up) is timed
as ``setup_s``; then the window runs for ``--seconds``.  ``--trace 0``
reports the cell's end-to-end metrics, ``--trace 1`` profiles the window
and reports its per-layer metrics, ``device.busy_s``/``window_s`` and a
``breakdown``.  After the window the run compares what the window's
program produced with the plain reference and prints every number
compared beside its limit, on standard error and under ``checks`` in the
result, the last line of standard output.

Without a TPU, or with fewer chips than the cell asks for, it prints no
result and exits with code 2.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from chipbench import harness  # noqa: E402

DRIVERS = ("dual", "lm")


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def prepare(args):
    """Everything that needs no chip: the cell, its files and metrics."""
    bench = harness.load_benchmark()
    wl = harness.find_workload(bench, args.workload)
    config = harness.load_config(bench, wl["config"])
    if config.get("driver") not in DRIVERS:
        raise harness.BenchError(
            f"configuration {wl['config']!r} names no driver of {DRIVERS}")
    if "reference" in config:
        harness.load_reference(config["reference"])
    mix = harness.load_mix(wl["traffic"])
    limits = harness.load_limits(wl["name"])
    metrics = harness.cell_metrics(bench, wl["name"], per_layer=bool(
        args.trace))
    readers = {m["name"]: harness.load_metric(m["name"])
               for m in metrics} if args.trace else {}
    return wl, config, mix, limits, metrics, readers


def execute(args, *, t_start=T_PROCESS, devices=None, peaks=None,
            session_hook=None) -> dict:
    """One run; returns the result object.  ``devices``/``peaks`` are
    found on the chip when not given."""
    wl, config, mix, limits, metrics, readers = prepare(args)
    harness.program_path()
    harness.setup_cache()
    if devices is None:
        devices = harness.require_chips(int(wl["chips"]))
    dev0 = devices[0]
    if peaks is None:
        peaks = harness.load_peaks(dev0.device_kind)
    counter = harness.CompileCounter()
    r = harness.Run(workload=wl, config=config, mix=mix, limits=limits,
                    seed=args.seed, seconds=args.seconds,
                    trace=bool(args.trace), t_start=t_start, peaks=peaks)
    r.phase("process_start")
    if config["driver"] == "dual":
        from chipbench import dual as driver
    else:
        from chipbench import lm as driver
    out = driver.run(r, devices, counter, session_hook=session_hook)

    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(devices),
              "memory_peak_bytes": out.memory_peak_bytes}
    breakdown = None
    if args.trace:
        from chipbench import xplane
        summary = xplane.summarize(xplane.load(r.trace_dir),
                                   n_devices=len(devices))
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        ctx = {"trace": summary, "counts": out.counts, "config": config,
               "mix": mix, "peaks": peaks, "workload": wl,
               "reference": out.reference}
        values = {}
        for m in metrics:
            v = readers[m["name"]](ctx)
            if v is not None:
                values[m["name"]] = {"value": v, "unit": m["unit"]}
        breakdown = summary.breakdown()
    else:
        values = {m["name"]: {"value": out.end_to_end[m["name"]],
                              "unit": m["unit"]} for m in metrics}
    correct = all(c.ok for c in out.checks) and out.failed == 0 \
        and out.counts.get("compiles_in_window", 0) == 0
    for c in out.checks:
        print(f"[chipbench] check {c.name}: {c.value!r} (limit "
              f"{c.limit!r}) {'ok' if c.ok else 'FAILED'}", file=sys.stderr)
    return json.loads(harness.result_line(
        correct=correct, attempted=out.attempted, failed=out.failed,
        metrics=values, device=device, checks=out.checks,
        breakdown=breakdown))


def main(argv=None) -> int:
    args = parse(argv)
    try:
        result = execute(args)
    except harness.BenchError as e:
        print(f"[chipbench] no result: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
