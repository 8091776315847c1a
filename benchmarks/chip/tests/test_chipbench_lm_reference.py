"""An LM configuration names its plain reference by file: the driver judges
the cell against that file (a copy of ``lm_ref`` passes, one altered in a
single operation fails), ``prepare`` refuses a name with no file or a file
without the reference's functions, the default reference is today's dense
decoder, and a new LM cell needs new files only."""
from __future__ import annotations

import hashlib
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chipbench_testutil as tu
from chipbench import gen, harness, lm, lm_ref

CELL = "lm-danube-4l"
LM_REF_SRC = (tu.BENCH_DIR / "chipbench" / "lm_ref.py").read_text()
ONE_PLUS = "(1.0 + scale)"


def _place_reference(monkeypatch, tmp_path: Path, name: str, src: str):
    """Serve ``chipbench/<name>.py`` holding ``src`` from ``tmp_path``."""
    (tmp_path / "chipbench").mkdir(exist_ok=True)
    (tmp_path / "chipbench" / f"{name}.py").write_text(src)
    monkeypatch.setattr(harness, "BENCH_DIR", tmp_path)


def _altered_norm_gain() -> str:
    """``lm_ref`` with the norm gain taken without its ``1 +``."""
    assert LM_REF_SRC.count(ONE_PLUS) == 1
    return LM_REF_SRC.replace(ONE_PLUS, "scale")


def _tiny_cell(monkeypatch, reference: str = None) -> str:
    cfg = tu.tiny_lm_config()
    if reference is not None:
        cfg["reference"] = reference
    return tu.install(monkeypatch, cfg, mix=tu.TINY_LM_MIX,
                      limits=tu.real_limits(CELL),
                      traffic="steps_back_to_back")


SOURCES = {
    "lm_ref_copy": lambda: LM_REF_SRC,
    "lm_ref_gain": _altered_norm_gain,
    "lm_ref_uncounted": lambda: LM_REF_SRC.replace(
        "flops_per_token = work.lm_flops_per_token", ""),
}


@pytest.mark.parametrize("name,want", [
    ("lm_ref", True),               # the package's own file
    ("lm_ref_copy", True),          # a file found by name elsewhere
    ("lm_ref_gain", False),
])
def test_the_named_reference_decides_correct(monkeypatch, tmp_path, name,
                                             want):
    wl = _tiny_cell(monkeypatch, name)
    if name in SOURCES:
        _place_reference(monkeypatch, tmp_path, name, SOURCES[name]())
    res = tu.run_tiny(wl, seconds=0.5)
    assert res["correct"] is want, res["checks"]


@pytest.mark.parametrize("name", ["no_such_reference", "lm_ref_uncounted"])
def test_prepare_refuses_a_reference_it_cannot_use(monkeypatch, tmp_path,
                                                   name):
    wl = _tiny_cell(monkeypatch, name)
    if name in SOURCES:
        _place_reference(monkeypatch, tmp_path, name, SOURCES[name]())
    with pytest.raises(harness.BenchError, match=name):
        tu.bench_run.prepare(tu.bench_run.parse(
            ["--workload", wl, "--seed", "1", "--seconds", "1"]))


def test_without_a_reference_key_the_reference_is_the_dense_decoder():
    """``reference_run`` with no ``reference`` key is bit for bit the
    composition of ``gen.lm_init`` and ``lm_ref``'s loss and step."""
    cfg, mix, seed = tu.tiny_lm_config(), tu.TINY_LM_MIX, 2**32 + 9
    assert "reference" not in cfg and lm.reference_of(cfg) is lm_ref
    got = lm.reference_run(cfg, mix, seed, 3)

    model = cfg["model"]
    with jax.default_matmul_precision("highest"):
        p0 = jax.jit(lambda: gen.lm_init(model, seed))()
        params = jax.tree.map(jnp.copy, p0)
        state = lm_ref.adamw_init(params)
        losses, g1 = [], None
        for step in range(3):
            batch = gen.lm_batch(model["vocab_size"], mix["batch"],
                                 mix["seq"], step, seed)
            params, state, loss, g = lm_ref.train_step(
                params, state, batch, loss=lm_ref.loss_fn,
                opt=lm._opt_tuple(cfg), q=None,
                model_items=tuple(sorted(model.items())))
            losses.append(float(loss))
            if g1 is None:
                g1 = lm_ref.leaf_norms(g)
        change = lm_ref.diff_norms(params, p0)
    assert got[0] == losses
    assert got[1] == g1 and got[2] == change
    assert np.all(np.isfinite(losses))


# ---------------------------------------------------------------------------
# a new LM architecture as new files only
# ---------------------------------------------------------------------------
NEW_REF = '''"""Stand-in reference of a new architecture."""
from chipbench import lm_ref

init = lm_ref.init
loss_fn = lm_ref.loss_fn


def flops_per_token(model, seq):
    return 2.0 * lm_ref.flops_per_token(model, seq)
'''
NEW_METRIC = '''"""Device ms per step of the expert FFN nested in forward_backward."""
from chipbench import scopes


def read(ctx):
    return scopes.segment_ms_per(ctx, ("moe",), "steps")
'''
PREPARE = '''
import json, sys
sys.path.insert(0, "benchmarks/chip")
import run
from chipbench import lm
out = {}
for trace in ("0", "1"):
    wl, config, mix, limits, metrics, readers = run.prepare(run.parse(
        ["--workload", "lm-new-arch", "--seed", "3", "--seconds", "1",
         "--trace", trace]))
    ref = lm.reference_of(config)
    out[trace] = {
        "config": config["name"], "mix": mix, "limits": limits,
        "metrics": [m["name"] for m in metrics],
        "readers": sorted(n for n, f in readers.items() if callable(f)),
        "reference": ref.__file__,
        "flops": ref.flops_per_token(config["model"], mix["seq"]),
        "dense_flops": lm.lm_ref.flops_per_token(config["model"],
                                                 mix["seq"])}
print(json.dumps(out))
'''


def _digests(root: Path) -> dict:
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes())
            .hexdigest() for p in sorted(root.rglob("*")) if p.is_file()}


def test_a_new_lm_cell_takes_new_files_only(tmp_path):
    """A copy of the benchmark gains a configuration naming its reference,
    that reference, a mix, limits and a metric reading a nested scope,
    each a new file, and entries in ``BENCHMARK.json``; the copy's own,
    unchanged ``run.prepare`` finds every piece."""
    bench_dir = tmp_path / "benchmarks" / "chip"
    shutil.copytree(tu.BENCH_DIR, bench_dir, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    before = _digests(bench_dir)
    bench = json.loads((tu.ROOT / "BENCHMARK.json").read_text())

    cfg = tu.tiny_lm_config()
    cfg.update(name="new-arch", reference="new_arch_ref")
    (bench_dir / "configs" / "new-arch.json").write_text(json.dumps(cfg))
    (bench_dir / "chipbench" / "new_arch_ref.py").write_text(NEW_REF)
    (bench_dir / "mixes" / "new_mix.json").write_text(
        json.dumps(tu.TINY_LM_MIX))
    limits = {"limits": tu.real_limits(CELL)}
    (bench_dir / "limits" / "lm-new-arch.json").write_text(
        json.dumps(limits))
    (bench_dir / "metrics" / "moe_ms.py").write_text(NEW_METRIC)

    bench["configs"].append({
        "name": "new-arch", "source": "https://example.org/new-arch",
        "file": "benchmarks/chip/configs/new-arch.json", "reduced": [],
        "why": "a block the dense decoder lacks"})
    bench["workloads"].append({
        "name": "lm-new-arch", "config": "new-arch", "traffic": "new_mix",
        "chips": 1, "why": "the new block's step"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", []):
            m["workloads"].append("lm-new-arch")
    lm_layer = [m["name"] for m in bench["per_layer"]
                if CELL in m["workloads"]]
    bench["per_layer"].append({
        "name": "moe_ms", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "LM step",
        "moves": "tokens_per_s", "workloads": ["lm-new-arch"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    after = _digests(bench_dir)
    assert {k: after[k] for k in before} == before     # nothing edited
    assert len(after) == len(before) + 5

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    proc = subprocess.run([sys.executable, "-c", PREPARE], cwd=tmp_path,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    assert got["0"]["metrics"] == ["tokens_per_s", "setup_s"]
    assert got["0"]["readers"] == []
    assert got["1"]["metrics"] == lm_layer + ["moe_ms"]
    assert got["1"]["readers"] == sorted(lm_layer + ["moe_ms"])
    for g in got.values():
        assert g["config"] == "new-arch" and g["mix"] == tu.TINY_LM_MIX
        assert g["limits"] == limits["limits"]
        assert Path(g["reference"]).resolve() == \
            (bench_dir / "chipbench" / "new_arch_ref.py").resolve()
        assert g["flops"] == 2.0 * g["dense_flops"]
