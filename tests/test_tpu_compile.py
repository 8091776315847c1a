"""Ahead-of-time v5e compiles of the Pallas kernels at real widths.

The TPU compiler is installed even where no chip is attached: a described
``v5e:2x2`` topology lets Mosaic accept or refuse each kernel exactly as
the chip's compiler would (block tiling, SMEM/VMEM limits, unsupported
primitives).  Nothing runs; each test asserts the compiled program holds
the kernel (``tpu_custom_call``).  The topology is described inside a
fixture, never at import, because only one process may load the TPU
library at a time.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import dual as dual_mod
from repro.kernels.flash_attention.kernel import flash_attention_kernel
from repro.kernels.rglru.kernel import rglru_scan_kernel
from repro.kernels.sdca.kernel import sdca_block_kernel


@pytest.fixture(scope="module")
def topo():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        from jax.experimental import topologies
        try:
            yield topologies.get_topology_desc(platform="tpu",
                                               topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this environment
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    sh = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)
    return make


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("per_leaf_w,masked", [(True, True), (False, False)])
def test_sdca_kernel_compiles_at_smoke_leaf_block(spec, per_leaf_w, masked):
    """chip_smoke's leaf blocks: 512 leaves of 784 x 2,000 f32, H = 784."""
    K, m_b, d, H = 512, 784, 2000, 784
    loss = dual_mod.LOSSES["smooth_hinge_1"]

    def f(X, y, a, w, idx, mk, lm):
        return sdca_block_kernel(X, y, a, w, idx, loss=loss, lm=lm,
                                 step_mask=mk if masked else None,
                                 interpret=False)

    text = _compiled_text(
        f, spec((K, m_b, d)), spec((K, m_b)), spec((K, m_b)),
        spec((K, d) if per_leaf_w else (d,)), spec((K, H), jnp.int32),
        spec((K, H)), spec(()))
    assert "tpu_custom_call" in text


def test_flash_attention_compiles_at_danube_heads(spec):
    """h2o-danube-1.8b heads: 32 query / 8 kv heads of 128, seq 2048,
    window 4096, bf16."""
    def f(q, k, v):
        return flash_attention_kernel(q, k, v, causal=True, window=4096,
                                      interpret=False)

    text = _compiled_text(f, spec((1, 2048, 32, 128), jnp.bfloat16),
                          spec((1, 2048, 8, 128), jnp.bfloat16),
                          spec((1, 2048, 8, 128), jnp.bfloat16))
    assert "tpu_custom_call" in text


def test_rglru_compiles_at_width_2560(spec):
    def f(a, b, h0):
        return rglru_scan_kernel(a, b, h0, interpret=False)

    text = _compiled_text(f, spec((1, 2048, 2560)), spec((1, 2048, 2560)),
                          spec((1, 2560)))
    assert "tpu_custom_call" in text


def test_host_chunk_program_names_the_kernel(spec, monkeypatch):
    """The pallas chunk program, compiled for the chip: its one
    ``tpu_custom_call`` is the kernel named ``sdca``, under the
    ``leaf_solve`` scope of the jitted ``solve_fn`` (what the device
    trace's ``tf_op`` shows)."""
    from repro.api import Problem, Session, Topology
    from repro.core.engine import host as host_mod
    from repro.core.engine import plan as plan_mod

    m_b, d = 16, 128
    X = jnp.zeros((8 * m_b, d))
    y = jnp.ones((8 * m_b,))
    monkeypatch.setattr(host_mod, "on_tpu", lambda: True)
    host_mod._EXEC_CACHE.clear()
    sess = Session.compile(
        Problem.svm(X, y, lam=0.1, smoothing=1.0),
        Topology.two_level(2, 4, m_b, group_rounds=2, local_steps=16),
        backend="pallas")
    plan = sess.plan
    S, n, h = plan.n_ticks, plan.n_leaves, plan.h_max
    text = sess._fn.lower(
        spec((8 * m_b, d)), spec((8 * m_b,)), spec((S, n, 2), jnp.uint32),
        spec((8 * m_b,)), spec((d,)), spec((S, n)), spec((S, n, h)),
        spec(())).compile().as_text()
    host_mod._EXEC_CACHE.clear()
    _assert_one_sdca_call(text)


def _assert_one_sdca_call(text: str, pieces: bool = False) -> None:
    """The compiled chunk program holds one ``tpu_custom_call``: the kernel
    named ``sdca``, under the ``leaf_solve`` scope of ``solve_fn`` (with
    ``pieces``, inside the scan over pieces of the steps there)."""
    import re

    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert len(calls) == 1
    assert re.match(r"\s*(ROOT )?%sdca(\.\d+)? = ", calls[0]), calls[0][:200]
    scan = "while/body/closed_call/" if pieces else ""
    assert re.search(r'op_name="jit\(solve_fn\)/[^"]*/leaf_solve/' + scan
                     + 'sdca/', calls[0])


V5E_HBM_BYTES = 16 * 2**30


def _epsilon_chunk_program(spec, monkeypatch, H, fleet=None):
    """The ``dual-epsilon-pallas`` chunk program (512 leaves of 784 x 2,000
    f32, per-leaf w and the runtime step mask) at H local steps, compiled
    for the chip; with ``fleet``, the batched executor of that many sweep
    members."""
    from repro.api import Schedule, Topology
    from repro.core.engine import host as host_mod
    from repro.core.engine import plan as plan_mod

    K, m_b, d = 512, 784, 2000
    resolved = Schedule().resolve(
        Topology.two_level(4, 128, m_b, group_rounds=2, local_steps=H))
    plan = plan_mod.compile_tree(resolved.chunk_tree,
                                 weighting=resolved.weighting,
                                 compression=resolved.compression)
    assert plan.n_leaves == K and plan.h_max == H
    monkeypatch.setattr(host_mod, "on_tpu", lambda: True)
    host_mod._EXEC_CACHE.clear()
    fn = host_mod.get_host_executor(
        plan, loss=dual_mod.LOSSES["smooth_hinge_1"], record_history=False,
        backend="pallas", batched=fleet is not None)
    S, m = plan.n_ticks, K * m_b
    B = () if fleet is None else (fleet,)
    compiled = fn.lower(
        spec((m, d)), spec((m,)), spec(B + (S, K, 2), jnp.uint32),
        spec(B + (m,)), spec(B + (d,)), spec((S, K)), spec(B + (S, K, H)),
        spec(B)).compile()
    host_mod._EXEC_CACHE.clear()
    return compiled


def test_host_chunk_program_at_epsilon_shape(spec, monkeypatch):
    """The ``dual-epsilon-pallas`` chunk program at its real shape (512
    leaves of 784 x 2,000 f32, H = 784, per-leaf w and the runtime step
    mask), compiled for the chip: the kernel is still its one
    ``tpu_custom_call``, and one program's blocks fit the kernel's VMEM
    limit."""
    from repro.kernels.sdca.kernel import (
        VMEM_LIMIT_BYTES, kernel_bytes, step_plan)

    assert kernel_bytes(512, 784, 2000, 784)[0] < VMEM_LIMIT_BYTES
    _assert_one_sdca_call(
        _epsilon_chunk_program(spec, monkeypatch, 784).as_text(),
        pieces=step_plan(512, 784, 2000, 784)[0] > 1)


@pytest.mark.parametrize("epochs,fleet", [(4, None), (1, 4)])
def test_host_chunk_program_fits_hbm(spec, monkeypatch, epochs, fleet):
    """The kernel's gathered rows stay within ``ROW_GATHER_BYTES`` a call:
    at epsilon's widths the chunk program of four epochs of local steps (H
    = 4 m_b), and the batched program of a four-member sweep fleet, still
    fit one v5e chip's HBM, with the kernel the program's one
    ``tpu_custom_call``."""
    compiled = _epsilon_chunk_program(spec, monkeypatch, epochs * 784, fleet)
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total < V5E_HBM_BYTES, total
    _assert_one_sdca_call(compiled.as_text(), pieces=True)
