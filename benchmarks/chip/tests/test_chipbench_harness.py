"""The harness finds every piece of a cell by name and refuses an unknown
one; the committed ``BENCHMARK.json`` keeps to its own shape; and the
command prints no result and exits non-zero on a machine without a TPU."""
from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

import chipbench_testutil as tu
from chipbench import harness

BENCH = harness.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.mark.parametrize("wl", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_finds_its_pieces_by_name(wl):
    w = harness.find_workload(BENCH, wl)
    config = harness.load_config(BENCH, w["config"])
    assert config["driver"] in tu.bench_run.DRIVERS
    mix = harness.load_mix(w["traffic"])
    limits = harness.load_limits(wl)
    assert limits and all(v >= 0 for v in limits.values())
    assert mix
    for per_layer in (False, True):
        metrics = harness.cell_metrics(BENCH, wl, per_layer)
        assert metrics, (wl, per_layer)
        if per_layer:
            for m in metrics:
                assert callable(harness.load_metric(m["name"]))
    e2e = {m["name"] for m in harness.cell_metrics(BENCH, wl, False)}
    assert "setup_s" in e2e and len(e2e) >= 2


@pytest.mark.parametrize("finder,name", [
    (lambda n: harness.find_workload(BENCH, n), "no-such-cell"),
    (lambda n: harness.load_config(BENCH, n), "no-such-config"),
    (harness.load_mix, "no-such-mix"),
    (harness.load_limits, "no-such-cell"),
    (harness.load_metric, "no_such_metric"),
    (harness.load_reference, "no_such_reference"),
    (harness.load_peaks, "TPU v0 imaginary"),
])
def test_unknown_names_are_refused(finder, name):
    with pytest.raises(harness.BenchError):
        finder(name)


def test_benchmark_json_keeps_its_shape():
    assert BENCH["command"] == ["python3", "benchmarks/chip/run.py"]
    assert BENCH["paths"] == ["benchmarks/chip"]
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for c in BENCH["configs"]:
        assert c["file"].startswith("benchmarks/chip/configs/")
        assert all(NAME.match(k) for k in c["reduced"])
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        for wl in m["workloads"]:
            moved = [x for x in BENCH["end_to_end"] if x["name"] == m["moves"]]
            assert wl in moved[0].get("workloads", [wl])
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 2)


def test_roofline_and_mfu_metrics_name_their_kind():
    for m in BENCH["per_layer"]:
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"


def _run_cmd(cwd, wl):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", wl,
         "--seed", str(2**31 + 1), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_without_a_tpu_prints_nothing_and_fails():
    wl = BENCH["workloads"][0]["name"]
    out = _run_cmd(tu.ROOT, wl)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr


def test_command_with_only_the_benchmark_files_fails(tmp_path):
    shutil.copy(tu.ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(tu.BENCH_DIR, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _run_cmd(tmp_path, BENCH["workloads"][0]["name"])
    assert out.returncode != 0
    assert out.stdout.strip() == ""


def test_result_line_puts_the_checks_last():
    line = harness.result_line(
        correct=True, attempted=3, failed=0,
        metrics={"round_ms": {"value": 1.5, "unit": "ms"}},
        device={"platform": "tpu"},
        checks=[harness.Check("alpha_rel", 1e-7, 1e-5)])
    obj = json.loads(line)
    assert list(obj)[-1] == "checks"
    assert obj["checks"]["alpha_rel"] == {"value": 1e-7, "limit": 1e-5}
