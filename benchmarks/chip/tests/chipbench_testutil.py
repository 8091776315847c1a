"""Shared set-up of the benchmark's CPU tests: the harness and the run
command driven at a tiny size on JAX's CPU devices, with the look for a
chip skipped."""
from __future__ import annotations

import copy
import json
import os
import sys
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parents[1]
for p in (str(BENCH_DIR), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import run as bench_run  # noqa: E402
from chipbench import harness  # noqa: E402

TINY_DUAL = {
    "name": "tiny-dual", "driver": "dual",
    "data": {"generator": "gaussian_classification", "m": 2 * 4 * 16,
             "d": 32, "margin": 0.5},
    "loss": "smooth_hinge", "smoothing": 1.0, "lam": "d/m",
    "topology": {"kind": "two_level", "n_groups": 2, "workers_per_group": 4,
                 "m_per_worker": 16, "group_rounds": 2, "local_steps": 16},
    "session": {"backend": "vmap"},
}

TINY_MODEL = dict(num_layers=2, d_model=64, num_heads=4, num_kv_heads=2,
                  d_ff=128, vocab_size=128, window=16, q_chunk_size=32,
                  logits_chunk=32, name="tiny-lm")


def tiny_lm_config() -> dict:
    cfg = json.loads((BENCH_DIR / "configs" / "h2o-danube-1.8b-4l.json")
                     .read_text())
    cfg["model"].update(TINY_MODEL)
    return cfg


TINY_LM_MIX = {"batch": 2, "seq": 64, "check_steps": 3}


def real_limits(workload: str) -> dict:
    return harness.load_limits(workload)


def install(monkeypatch, config: dict, *, mix: dict = None,
            limits: dict = None, traffic: str = "round_by_round") -> str:
    """Serve one tiny cell named ``tiny`` through the harness's finders."""
    cfg = copy.deepcopy(config)
    bench = {
        "configs": [{"name": cfg["name"], "file": "unused"}],
        "workloads": [{"name": "tiny", "config": cfg["name"],
                       "traffic": traffic, "chips": 1}],
        "end_to_end": [{"name": "round_ms", "unit": "ms",
                        "workloads": ["tiny"] if cfg["driver"] == "dual"
                        else []},
                       {"name": "tokens_per_s", "unit": "tokens/s",
                        "workloads": ["tiny"] if cfg["driver"] == "lm"
                        else []},
                       {"name": "setup_s", "unit": "s"}],
        "per_layer": [],
    }
    monkeypatch.setattr(harness, "load_benchmark", lambda root=None: bench)
    monkeypatch.setattr(harness, "load_config",
                        lambda b, n, root=None: cfg)
    if mix is not None:
        monkeypatch.setattr(harness, "load_mix",
                            lambda n, bench_dir=None: mix)
    if limits is not None:
        monkeypatch.setattr(harness, "load_limits",
                            lambda w, bench_dir=None: limits)
    # no persistent cache writes from the tests
    monkeypatch.setattr(harness, "setup_cache", lambda: None)
    return "tiny"


def run_tiny(workload: str, *, seed: int = 2**33 + 5, seconds: float = 0.3,
             session_hook=None) -> dict:
    import jax
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    args = bench_run.parse(["--workload", workload, "--seed", str(seed),
                            "--seconds", str(seconds), "--trace", "0"])
    return bench_run.execute(args, devices=jax.devices()[:1], peaks={},
                             session_hook=session_hook)
