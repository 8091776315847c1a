"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode
(deliverable c; the v5e compiles are in test_tpu_compile.py)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dual as dual_mod
from repro.kernels.flash_attention.kernel import flash_attention_kernel
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.sdca.kernel import sdca_block_kernel
from repro.kernels.sdca.ref import sdca_block_ref
from repro.kernels.sdca.ops import sdca_block_solve


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------
def _qkv(key, B, S, H, KV, D, dtype):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, S, H, D), dtype)
    k = jax.random.normal(kk, (B, S, KV, D), dtype)
    v = jax.random.normal(kv, (B, S, KV, D), dtype)
    return q, k, v


@pytest.mark.parametrize("B,S,H,KV,D,bq,bk", [
    (1, 128, 2, 2, 32, 64, 64),     # MHA
    (2, 256, 4, 2, 64, 128, 128),   # GQA 2:1
    (1, 256, 8, 1, 64, 64, 128),    # MQA
    (1, 64, 2, 2, 128, 32, 16),     # small blocks, big head_dim
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_causal_shapes_dtypes(B, S, H, KV, D, bq, bk, dtype):
    q, k, v = _qkv(jax.random.PRNGKey(0), B, S, H, KV, D, dtype)
    out = flash_attention_kernel(q, k, v, causal=True, block_q=bq,
                                 block_k=bk, interpret=True)
    ref = attention_ref(q, k, v, causal=True)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("window", [16, 64, 100])
def test_flash_sliding_window(window):
    q, k, v = _qkv(jax.random.PRNGKey(1), 2, 256, 4, 4, 32, jnp.float32)
    out = flash_attention_kernel(q, k, v, causal=True, window=window,
                                 block_q=64, block_k=64, interpret=True)
    ref = attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_noncausal():
    q, k, v = _qkv(jax.random.PRNGKey(2), 1, 128, 2, 2, 32, jnp.float32)
    out = flash_attention_kernel(q, k, v, causal=False, block_q=64,
                                 block_k=64, interpret=True)
    ref = attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_band_pruning_matches_full_scan():
    """Loop-bound pruning (the TPU adaptation) must not change results:
    compare a heavily-windowed case against block_k == S (no pruning)."""
    q, k, v = _qkv(jax.random.PRNGKey(3), 1, 256, 2, 2, 32, jnp.float32)
    pruned = flash_attention_kernel(q, k, v, causal=True, window=32,
                                    block_q=32, block_k=32, interpret=True)
    unpruned = flash_attention_kernel(q, k, v, causal=True, window=32,
                                      block_q=32, block_k=256,
                                      interpret=True)
    np.testing.assert_allclose(np.asarray(pruned), np.asarray(unpruned),
                               rtol=1e-5, atol=1e-5)


def test_flash_vs_model_attention_path():
    """The model's attention (attention_impl='flash') equals the XLA path."""
    import dataclasses
    from repro.configs.registry import ARCHS
    from repro.models import attention as attn_mod
    from repro.models.transformer import init_params

    cfg = dataclasses.replace(ARCHS["qwen3-32b"].SMOKE, q_chunk_size=32)
    params = init_params(cfg, jax.random.PRNGKey(0))
    blk = jax.tree.map(lambda t: t[0], params["blocks"])["sub0"]["mix"]
    x = 0.1 * jax.random.normal(jax.random.PRNGKey(1), (2, 64, cfg.d_model),
                                jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(64, dtype=jnp.int32), (2, 64))
    ref = attn_mod.attention_train(blk, cfg, x, pos)
    out = attn_mod.attention_flash(blk, cfg, x, pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=5e-3, atol=5e-3)


# ---------------------------------------------------------------------------
# blocked SDCA
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("loss_name", ["squared", "smooth_hinge_1", "hinge"])
@pytest.mark.parametrize("K,m_b,d,H", [
    (2, 32, 16, 64), (4, 64, 8, 128), (1, 128, 32, 256),
    # K not a multiple of the 8 leaves a vreg packs: padded leaf slots
    (3, 16, 8, 48), (9, 16, 8, 48), (13, 8, 16, 40),
    # whole packs of 8
    (8, 16, 8, 32), (16, 8, 8, 32),
    # H = 8 m_b: every coordinate recurs, each draw sees its updated alpha
    (4, 8, 16, 64)])
def test_sdca_kernel_matches_ref(loss_name, K, m_b, d, H):
    loss = dual_mod.LOSSES[loss_name]
    key = jax.random.PRNGKey(0)
    kx, ky, ka, kw, ki = jax.random.split(key, 5)
    X = jax.random.normal(kx, (K, m_b, d))
    y = (jnp.sign(jax.random.normal(ky, (K, m_b))) if loss.gamma != 1.0
         else jax.random.normal(ky, (K, m_b)))
    alpha = 0.1 * jax.random.normal(ka, (K, m_b))
    if loss_name != "squared":   # hinge-family feasibility: alpha*y in [0,1]
        alpha = jnp.abs(alpha) * y
    lam, m_total = 0.1, K * m_b
    w = jax.random.normal(kw, (d,)) * 0.1
    idx = jax.random.randint(ki, (K, H), 0, m_b)

    da_k, dw_k = sdca_block_kernel(X, y, alpha, w, idx, loss=loss,
                                   lm=lam * m_total, interpret=True)
    da_r, dw_r = sdca_block_ref(X, y, alpha, w, idx, loss=loss,
                                lm=lam * m_total)
    np.testing.assert_allclose(np.asarray(da_k), np.asarray(da_r),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(dw_k), np.asarray(dw_r),
                               rtol=1e-5, atol=1e-5)


def test_sdca_kernel_matches_sequential_local_sdca():
    """K=1 kernel == the core-layer sequential Procedure P (same PRNG)."""
    loss = dual_mod.LOSSES["squared"]
    key = jax.random.PRNGKey(3)
    kx, ky, kw, ki = jax.random.split(key, 4)
    m_b, d, H = 64, 16, 128
    X = jax.random.normal(kx, (m_b, d))
    y = jax.random.normal(ky, (m_b,))
    alpha = jnp.zeros((m_b,))
    w = jnp.zeros((d,))
    lam = 0.1
    idx = jax.random.randint(ki, (1, H), 0, m_b)

    da_k, dw_k = sdca_block_kernel(X[None], y[None], alpha[None], w, idx,
                                   loss=loss, lm=lam * m_b, interpret=True)

    # replicate the same coordinate sequence through the core path
    def run_seq():
        a_c, w_c = alpha, w
        lm = lam * m_b
        xsq = jnp.sum(X * X, axis=1) / lm
        for h in range(H):
            i = int(idx[0, h])
            wx = jnp.dot(w_c, X[i])
            dlt = loss.coord_delta(wx, a_c[i], y[i], xsq[i])
            a_c = a_c.at[i].add(dlt)
            w_c = w_c + (dlt / lm) * X[i]
        return a_c - alpha, w_c - w

    da_s, dw_s = run_seq()
    np.testing.assert_allclose(np.asarray(da_k[0]), np.asarray(da_s),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(dw_k[0]), np.asarray(dw_s),
                               rtol=1e-5, atol=1e-6)


def test_sdca_solve_increases_dual_and_converges():
    """Repeated kernel rounds drive the duality gap toward 0 (CoCoA on
    ridge regression, K=4 workers)."""
    from repro.data.synthetic import gaussian_regression
    loss = dual_mod.LOSSES["squared"]
    K, lam = 4, 0.1
    X, y = gaussian_regression(m=256, d=32)
    m = X.shape[0]
    Xb = X.reshape(K, m // K, -1)
    yb = y.reshape(K, m // K)
    alpha = jnp.zeros((K, m // K))
    w = jnp.zeros((X.shape[1],))
    key = jax.random.PRNGKey(0)
    gaps = []
    for _t in range(30):
        key, k = jax.random.split(key)
        alpha, w, _ = sdca_block_solve(Xb, yb, alpha, w, k, loss=loss,
                                       lam=lam, m_total=m, num_steps=256)
        gap = float(dual_mod.duality_gap(alpha.reshape(-1), X, y, loss, lam))
        gaps.append(gap)
    assert gaps[-1] < 2e-3 * gaps[0], gaps[:3] + gaps[-3:]
    assert gaps[-1] < gaps[len(gaps) // 2]  # still descending late
    # w stays consistent with alpha: w == A alpha
    w_check = dual_mod.w_of_alpha(alpha.reshape(-1), X, lam)
    np.testing.assert_allclose(np.asarray(w), np.asarray(w_check),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("per_leaf_w,masked", [(True, True), (False, False),
                                               (True, "idle_leaf")])
@pytest.mark.parametrize("K,m_b,d,H", [
    (3, 40, 16, 96), (2, 64, 2000, 100),
    (9, 16, 16, 48), (13, 24, 16, 64),         # packs of 8 plus a remainder
    (8, 16, 16, 32), (16, 8, 16, 32),          # whole packs
    (5, 8, 16, 64),                            # H = 8 m_b: every draw recurs
    # one leaf a program: alpha in (8, 128) tiles, two tiles at m_b 1,500
    (1, 40, 16, 96), (1, 1500, 16, 200), (1, 8, 16, 64)])
def test_sdca_kernel_bit_identical_to_ref(K, m_b, d, H, per_leaf_w, masked):
    """Interpret mode: the kernel's iterates equal the oracle's bit for bit
    (smoke width d = 2,000 included); only WHERE they live differs.  An
    ``idle_leaf`` has an all-zero step mask and comes back unchanged."""
    _assert_kernel_bits_equal_ref(K, m_b, d, H, per_leaf_w, masked)


@pytest.mark.parametrize("K", [1, 5])
@pytest.mark.parametrize("budget", ["ROW_CHUNK_BYTES", "ROW_GATHER_BYTES"])
def test_sdca_kernel_bit_identical_with_padded_steps(monkeypatch, K, budget):
    """A prime H split into chunks of streamed rows, or into pieces of
    gathered rows (one call each), leaves padded steps at the end (index
    m_b, step mask 0): they change nothing, bit for bit."""
    from repro.kernels.sdca import kernel as kmod
    m_b, d, H = 24, 16, 97
    monkeypatch.setattr(kmod, budget, 10 * 4096 if budget ==
                        "ROW_CHUNK_BYTES" else (20_000 if K == 1 else 100_000))
    pieces, Hc, C = kmod.step_plan(K, m_b, d, H)
    assert pieces * C * Hc > H and (pieces > 1 or C > 1)
    _assert_kernel_bits_equal_ref(K, m_b, d, H, True, True)


@pytest.mark.parametrize("K,m_b,want", [
    (1, 784, (1, 1, 1)), (2, 784, (1, 1, 2)),   # one leaf a program
    (4, 2048, (4, 4, 4)), (4, 8192, (1, 1, 4)),  # the select outgrows P = 4
    (8, 8192, (8, 8, 8)), (12, 16, (8, 16, 16)),
    (512, 784, (8, 32, 512))])                   # dual-epsilon-pallas
def test_leaf_packing_follows_step_costs(K, m_b, want):
    """Leaves are packed only where a packed step, whose one-hot select
    grows with m_b, is cheaper per leaf than a single leaf's step (the
    shapes on each side were timed on a v5e)."""
    from repro.kernels.sdca.kernel import leaf_packing
    assert leaf_packing(K, m_b) == want


def _assert_kernel_bits_equal_ref(K, m_b, d, H, per_leaf_w, masked):
    loss = dual_mod.LOSSES["smooth_hinge_1"]
    kx, ky, ka, kw, ki, km = jax.random.split(jax.random.PRNGKey(4), 6)
    X = jax.random.normal(kx, (K, m_b, d))
    y = jnp.sign(jax.random.normal(ky, (K, m_b)))
    alpha = 0.5 * jnp.abs(0.1 * jax.random.normal(ka, (K, m_b))) * y
    w = 0.1 * jax.random.normal(kw, (K, d) if per_leaf_w else (d,))
    idx = jax.random.randint(ki, (K, H), 0, m_b)
    mask = (jax.random.uniform(km, (K, H)) > 0.3).astype(jnp.float32) \
        if masked else None
    if masked == "idle_leaf":
        mask = mask.at[K // 2].set(0.0)
    lm = jnp.float32(0.01 * K * m_b)
    da_k, dw_k = jax.jit(lambda *a: sdca_block_kernel(
        *a, loss=loss, lm=lm, step_mask=mask, interpret=True))(
        X, y, alpha, w, idx)
    da_r, dw_r = jax.jit(lambda *a: sdca_block_ref(
        *a, loss=loss, lm=lm, step_mask=mask))(X, y, alpha, w, idx)
    np.testing.assert_array_equal(np.asarray(da_k), np.asarray(da_r))
    np.testing.assert_array_equal(np.asarray(dw_k), np.asarray(dw_r))
    if masked == "idle_leaf":
        assert not np.any(np.asarray(da_k)[K // 2])
        assert not np.any(np.asarray(dw_k)[K // 2])


def test_sdca_kernel_streams_block_over_old_vmem_cap():
    """A 8,192 x 4,096 leaf block (128 MiB, over the VMEM limit were it
    resident) runs: the kernel streams the drawn rows, so only a chunk of
    them and the (R, d) / (R, m_b) blocks take VMEM."""
    from repro.kernels.sdca.kernel import (
        SMEM_LIMIT_BYTES, VMEM_LIMIT_BYTES, kernel_bytes)
    K, m_b, d, H = 1, 8192, 4096, 16
    assert 4 * m_b * d > VMEM_LIMIT_BYTES
    vmem, smem = kernel_bytes(K, m_b, d, H)
    assert vmem < VMEM_LIMIT_BYTES and smem < SMEM_LIMIT_BYTES
    loss = dual_mod.LOSSES["squared"]
    kx, ky, kw, ki = jax.random.split(jax.random.PRNGKey(5), 4)
    X = jax.random.normal(kx, (K, m_b, d))
    y = jax.random.normal(ky, (K, m_b))
    alpha = jnp.zeros((K, m_b))
    w = 0.01 * jax.random.normal(kw, (d,))
    idx = jax.random.randint(ki, (K, H), 0, m_b)
    lm = 0.1 * m_b
    da_k, dw_k = sdca_block_kernel(X, y, alpha, w, idx, loss=loss, lm=lm,
                                   interpret=True)
    da_r, dw_r = sdca_block_ref(X, y, alpha, w, idx, loss=loss, lm=lm)
    assert np.count_nonzero(np.asarray(da_k)) > 0
    np.testing.assert_allclose(np.asarray(da_k), np.asarray(da_r),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(dw_k), np.asarray(dw_r),
                               rtol=1e-5, atol=1e-6)


def test_sdca_kernel_refuses_block_over_vmem_limit():
    """Leaves whose (R, d) w blocks cannot fit VMEM (a million features)
    raise, naming their bytes -- they are never routed to the
    reference."""
    from repro.kernels.sdca.kernel import VMEM_LIMIT_BYTES, kernel_bytes
    K, m_b, d, H = 8, 8, 2**20, 16
    need, _ = kernel_bytes(K, m_b, d, H)
    assert need > VMEM_LIMIT_BYTES
    shapes = (jax.ShapeDtypeStruct((K, m_b, d), jnp.float32),
              jax.ShapeDtypeStruct((K, m_b), jnp.float32),
              jax.ShapeDtypeStruct((K, m_b), jnp.float32),
              jax.ShapeDtypeStruct((d,), jnp.float32),
              jax.ShapeDtypeStruct((K, H), jnp.int32))
    with pytest.raises(ValueError, match=str(need)):
        jax.eval_shape(lambda *a: sdca_block_kernel(
            *a, loss=dual_mod.LOSSES["squared"], lm=1.0, interpret=True),
            *shapes)
