"""Device-level TreeDualMethod (shard_map + psum + Pallas leaf kernel)."""
import jax
import numpy as np
import pytest

from repro.core import dual as dual_mod
from repro.core.treedual_mesh import mesh_tree_dual_solve
from repro.data.synthetic import gaussian_regression

LAM = 0.1


@pytest.fixture(scope="module")
def data():
    return gaussian_regression(m=256, d=32)


def _gap(alpha, X, y):
    loss = dual_mod.LOSSES["squared"]
    return float(dual_mod.duality_gap(alpha, X, y, loss, LAM))


def test_star_on_mesh_converges(data):
    X, y = data
    n = len(jax.devices())
    mesh = jax.make_mesh((n,), ("data",))
    loss = dual_mod.LOSSES["squared"]
    alpha, w = mesh_tree_dual_solve(
        X, y, mesh, loss=loss, lam=LAM, axes=("data",), rounds=(40,),
        local_steps=256)
    g = _gap(alpha, X, y)
    assert g < 1e-3, g
    # w-consistency: w == A alpha
    w_ref = dual_mod.w_of_alpha(alpha, X, LAM)
    np.testing.assert_allclose(np.asarray(w), np.asarray(w_ref),
                               rtol=1e-4, atol=1e-5)


def test_two_level_tree_on_mesh(data):
    X, y = data
    n = len(jax.devices())
    if n < 4:
        pytest.skip("needs 4 devices for a 2x2 tree")
    mesh = jax.make_mesh((2, n // 2), ("pod", "data"))
    loss = dual_mod.LOSSES["squared"]
    alpha, w = mesh_tree_dual_solve(
        X, y, mesh, loss=loss, lam=LAM, axes=("data", "pod"),
        rounds=(3, 12), local_steps=256)
    g = _gap(alpha, X, y)
    assert g < 1e-3, g
    w_ref = dual_mod.w_of_alpha(alpha, X, LAM)
    np.testing.assert_allclose(np.asarray(w), np.asarray(w_ref),
                               rtol=1e-4, atol=1e-5)


def test_mesh_matches_host_reference_quality(data):
    """The mesh program and the host-recursion program solve the same
    problem to comparable suboptimality under equal total local steps."""
    from repro.core.treedual import cocoa_star_solve
    X, y = data
    n = len(jax.devices())
    mesh = jax.make_mesh((n,), ("data",))
    loss = dual_mod.LOSSES["squared"]
    alpha_m, _ = mesh_tree_dual_solve(
        X, y, mesh, loss=loss, lam=LAM, axes=("data",), rounds=(20,),
        local_steps=128)
    res = cocoa_star_solve(X, y, n, loss=loss, lam=LAM, outer_rounds=20,
                           local_steps=128, key=jax.random.PRNGKey(7))
    g_mesh, g_host = _gap(alpha_m, X, y), res.gaps[-1]
    assert g_mesh < 5 * g_host + 1e-5, (g_mesh, g_host)
    assert g_host < 5 * g_mesh + 1e-5, (g_mesh, g_host)


def test_kernel_vs_ref_leaf_same_result(data):
    """use_kernel=False (pure-jnp leaves) and True agree bit-for-bit given
    the same replayed per-solve keys (engine key_plan)."""
    X, y = data
    n = len(jax.devices())
    mesh = jax.make_mesh((n,), ("data",))
    loss = dual_mod.LOSSES["squared"]
    kw = dict(loss=loss, lam=LAM, axes=("data",), rounds=(3,),
              local_steps=64, key=jax.random.PRNGKey(3))
    a1, w1 = mesh_tree_dual_solve(X, y, mesh, use_kernel=True, **kw)
    a2, w2 = mesh_tree_dual_solve(X, y, mesh, use_kernel=False, **kw)
    np.testing.assert_allclose(np.asarray(a1), np.asarray(a2),
                               rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(np.asarray(w1), np.asarray(w2),
                               rtol=1e-6, atol=1e-7)


_MULTI_DEVICE_CHILD = """
import jax, numpy as np
from repro.api import Problem, Session, Topology
from repro.data.synthetic import gaussian_classification
assert len(jax.devices()) == 4, jax.devices()
X, y = gaussian_classification(m=512, d=96, key=jax.random.PRNGKey(0))
prob = Problem.svm(X, y, lam=0.05)
topo = Topology.two_level(2, 2, 128, local_steps=64)
key = jax.random.PRNGKey(1)
ref = Session.compile(prob, topo, backend="vmap").run(4, key=key)
res = Session.compile(prob, topo, backend="mesh", mesh_use_kernel=False,
                      mesh_sync={sync!r}).run(4, key=key)
np.savez({out!r}, alpha=np.asarray(res.alpha), w=np.asarray(res.w),
         ref_alpha=np.asarray(ref.alpha), ref_w=np.asarray(ref.w))
"""


@pytest.mark.parametrize("sync", ["psum", "reduce_scatter"])
def test_mesh_backend_across_four_devices_matches_vmap(sync, tmp_path):
    """The multi-device mesh path (the four-chip layout: tree level 1
    across devices) on 4 virtual CPU devices in a child process: default
    ``jax.make_mesh`` axes are Explicit in JAX 0.9, so the engine must
    build Auto meshes for its sharded outputs to index.  psum is
    bit-identical to the host backend; reduce_scatter agrees to f32
    rounding."""
    import os
    import subprocess
    import sys
    out = tmp_path / "out.npz"
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-c",
         _MULTI_DEVICE_CHILD.format(sync=sync, out=str(out))],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    with np.load(out) as z:
        if sync == "psum":
            np.testing.assert_array_equal(z["alpha"], z["ref_alpha"])
            np.testing.assert_array_equal(z["w"], z["ref_w"])
        else:
            np.testing.assert_allclose(z["alpha"], z["ref_alpha"],
                                       rtol=1e-5, atol=1e-6)
            np.testing.assert_allclose(z["w"], z["ref_w"],
                                       rtol=1e-5, atol=1e-6)
