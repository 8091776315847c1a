"""The work counts behind ``sdca_roofline``, ``round_mfu`` and ``lm_mfu``
match hand counts at small shapes, and ``lm_mfu`` reads its count from
the cell's reference."""
from __future__ import annotations

import json

import pytest

import chipbench_testutil as tu
from chipbench import harness, work

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_sdca_leaf_call_by_hand():
    # m_b=8, d=4, H=3: X 32 floats, y/xsq/alpha read 24, alpha written 8,
    # w read 4, dw written 4 -> 72 floats; 4 d per step -> 48 FLOPs
    got = work.sdca_leaf_call(8, 4, 3)
    assert got == {"bytes": 72 * 4.0, "flops": 48.0}


def test_sdca_round_counts_every_leaf_every_group_round():
    topo = {"n_groups": 2, "workers_per_group": 3, "m_per_worker": 8,
            "group_rounds": 2, "local_steps": 3}
    got = work.sdca_round(topo, 4)
    assert got == {"bytes": 12 * 72 * 4.0, "flops": 12 * 48.0}


def test_epsilon_round_is_bytes_bound_near_eight_ms():
    topo = {"n_groups": 4, "workers_per_group": 128, "m_per_worker": 784,
            "group_rounds": 2, "local_steps": 784}
    r = work.roofline_s(work.sdca_round(topo, 2000), PEAKS)
    assert r["bound"] == "bytes"
    assert r["seconds"] == pytest.approx(7.84e-3, rel=0.01)


def test_dual_round_flops_adds_the_gap_passes():
    topo = {"n_groups": 1, "workers_per_group": 2, "m_per_worker": 8,
            "group_rounds": 1, "local_steps": 3}
    assert work.dual_round_flops(16, 4, topo) == 2 * 48 + 4 * 16 * 4


def test_lm_flops_per_token_by_hand():
    # d=8, 2 heads (hd 4), 1 KV head, d_ff 16, vocab 10, 1 layer:
    # q 8*8 + k,v 2*8*4 + o 8*8 = 192; MLP 3*8*16 = 384; head 80 -> 656
    model = {"d_model": 8, "num_heads": 2, "num_kv_heads": 1, "d_ff": 16,
             "vocab_size": 10, "num_layers": 1, "window": None}
    assert work.lm_matmul_params(model) == 656
    # seq 4, causal: keys attended 1+2+3+4 -> mean 2.5; 12 * 1 * 8 * 2.5
    assert work.lm_flops_per_token(model, 4) == 6 * 656 + 240.0
    # a window of 2 caps the keys at 1, 2, 2, 2 -> mean 1.75
    model["window"] = 2
    assert work.lm_flops_per_token(model, 4) == 6 * 656 + 12 * 8 * 1.75


def test_danube_4l_flops_per_token():
    model = {"d_model": 2560, "num_heads": 32, "num_kv_heads": 8,
             "d_ff": 6912, "vocab_size": 32000, "num_layers": 4,
             "window": 4096}
    n = work.lm_matmul_params(model)
    assert n == 4 * (2560 * 2560 * 2 + 2 * 2560 * 640 + 3 * 2560 * 6912) \
        + 2560 * 32000
    assert work.lm_flops_per_token(model, 2048) == pytest.approx(
        6 * n + 12 * 4 * 2560 * 1024.5)


def test_lm_mfu_reads_the_danube_count_of_its_reference():
    """``lm_mfu`` on danube's counts through the default reference reads,
    to the last digit, what it read when it called ``work`` itself."""
    cfg = json.loads((tu.BENCH_DIR / "configs" / "h2o-danube-1.8b-4l.json")
                     .read_text())
    ctx = {"counts": {"model": cfg["model"], "seq": 2048,
                      "tokens": 117 * 2048, "window_s": 19.9689},
           "peaks": harness.load_peaks("TPU v5 lite"),
           "workload": {"chips": 1},
           "reference": harness.load_reference("lm_ref")}
    assert harness.load_metric("lm_mfu")(ctx) == 13.91600059367443
