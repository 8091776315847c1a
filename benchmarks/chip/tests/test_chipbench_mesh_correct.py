"""``correct`` on the four-chip dual cell, at a tiny size on four virtual
CPU devices (a child process, since the test run itself has one device):
the program passes, and with the exchange between chips left out (every
``psum`` returning the chip's own part) it fails."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

import chipbench_testutil as tu

CHILD = r"""
import json, sys
sys.path.insert(0, {tests!r})
import chipbench_testutil as tu
import jax
if {fault!r} == "no_exchange":
    jax.lax.psum = lambda x, axis_name, **kw: x
cfg = dict(tu.TINY_DUAL, session={{"backend": "mesh", "mesh_sync": "psum",
                                   "mesh_use_kernel": False}})
cfg["data"] = dict(cfg["data"], m=128)
cfg["topology"] = dict(cfg["topology"], n_groups=2, workers_per_group=2,
                       m_per_worker=32)


class Patch:
    def setattr(self, obj, name, value):
        setattr(obj, name, value)


name = tu.install(Patch(), cfg, limits=tu.real_limits("dual-epsilon-pallas"))
args = tu.bench_run.parse(["--workload", name, "--seed", "2147483659",
                           "--seconds", "0.3", "--trace", "0"])
res = tu.bench_run.execute(args, devices=jax.devices()[:4], peaks={{}})
print(json.dumps(res))
"""


def _child(fault: str) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = CHILD.format(tests=str(tu.BENCH_DIR / "tests"), fault=fault)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("fault,correct", [("none", True),
                                           ("no_exchange", False)])
def test_mesh_cell_correct_only_with_the_exchange(fault, correct):
    res = _child(fault)
    assert res["device"]["count"] == 4
    assert res["correct"] is correct, res["checks"]
