"""The trace reducer: busy share, per-op and per-program times, and idle
gaps labelled by the host span open during each, on a hand-made event set
and on a small trace recorded on a TPU v5e chip."""
from __future__ import annotations

import gzip
import json
from pathlib import Path

import pytest

import chipbench_testutil  # noqa: F401  (puts the benchmark on the path)
from chipbench import xplane

DATA = Path(__file__).with_name("data")

# one chip, window [0, 100] ns: ops at [10, 30], [25, 40] (overlapping),
# [60, 70] and [95, 120] (clipped to 100); host spans "run" [0, 50] and
# "read" [40, 80] inside it
HAND = {
    "window": [0, 100],
    "spans": [["bench:run", 0, 50], ["bench:read", 40, 40],
              ["bench:late", 200, 10]],
    "devices": [{
        "name": "/device:TPU:0",
        "ops": [["fusion.1", 10, 20, "", "jit_solve_fn"],
                ["sdca_kernel", 25, 15, "custom-call", "jit_solve_fn"],
                ["fusion.1", 60, 10, "", "jit__objective"],
                ["copy.2", 95, 25, "", "jit_other"]],
        "programs": [["jit_solve_fn(1)", 10, 30], ["jit__objective(2)", 60, 10],
                     ["jit_other(3)", 95, 25]],
    }],
}


def test_busy_share_is_the_union_of_op_intervals():
    s = xplane.summarize(HAND)
    # busy [10, 40] + [60, 70] + [95, 100] = 45 of 100
    assert s.window_s == pytest.approx(100e-9)
    assert s.busy_s == pytest.approx(45e-9)
    assert s.idle_share == pytest.approx(0.55)


def test_per_op_and_per_program_times():
    s = xplane.summarize(HAND)
    assert s.op_s(lambda o: o[0] == "fusion.1") == pytest.approx(30e-9)
    assert s.op_s(lambda o: "sdca" in o[0]) == pytest.approx(15e-9)
    assert s.op_s(lambda o: o[0] == "copy.2") == pytest.approx(5e-9)
    assert s.program_s(lambda n: "solve_fn" in n) == pytest.approx(30e-9)
    assert s.program_count(lambda n: "_objective" in n) == 1


def test_gaps_are_labelled_by_the_innermost_host_span():
    s = xplane.summarize(HAND)
    gaps = [(g[0], round(g[1] * 1e9), g[2]) for g in s.gaps()]
    # [0, 10] under "run"; [40, 60] midpoint 50 under "read" (the shorter);
    # [70, 95] midpoint 82.5 outside every span
    assert gaps == [(0, 10, "run"), (40, 20, "read"), (70, 25, "idle")]
    b = s.breakdown()
    assert b["idle_gaps"][0] == ["idle", pytest.approx(25e-9)]
    assert b["device_ops"][0] == ["fusion.1 () in jit_solve_fn",
                                  pytest.approx(20e-9)]


def test_breakdown_counts_self_time_of_nested_ops():
    nested = json.loads(json.dumps(HAND))
    nested["devices"][0]["ops"] = [["while.1", 0, 100, "while", "p(1)"],
                                   ["kernel", 10, 60, "tpu_custom_call",
                                    "p(1)"]]
    ops = dict(xplane.summarize(nested).breakdown()["device_ops"])
    assert ops["kernel (tpu_custom_call) in p"] == pytest.approx(60e-9)
    assert ops["while.1 (while) in p"] == pytest.approx(40e-9)


def test_busy_time_averages_over_chips():
    two = json.loads(json.dumps(HAND))
    two["devices"].append({"name": "/device:TPU:1", "ops": [
        ["all-reduce.1", 0, 100, "", "p"]], "programs": []})
    s = xplane.summarize(two)
    assert s.busy_s == pytest.approx((45e-9 + 100e-9) / 2)
    assert s.op_s(lambda o: "all-reduce" in o[0], busiest=True) == \
        pytest.approx(100e-9)
    assert xplane.summarize(two, n_devices=1).busy_s == pytest.approx(45e-9)


def _recorded():
    path = DATA / "pallas_window.json.gz"
    if not path.exists():
        pytest.fail(f"missing recorded trace {path}")
    return json.loads(gzip.decompress(path.read_bytes()))


def test_recorded_chip_trace_reduces():
    rec = _recorded()
    s = xplane.summarize(rec["events"])
    want = rec["expected"]
    # the expectation was counted on a 10 ns grid, apart from the reducer
    assert s.busy_s == pytest.approx(want["busy_s"], rel=1e-4)
    assert s.window_s == pytest.approx(want["window_s"], rel=1e-9)
    assert 0.0 < s.idle_share < 1.0
    kernel = s.op_s(lambda o: want["kernel"] in o[0] or want["kernel"] in o[3])
    assert kernel == pytest.approx(want["kernel_s"], rel=1e-9)
    labels = {g[2] for g in s.gaps()}
    assert labels <= set(want["labels"])
