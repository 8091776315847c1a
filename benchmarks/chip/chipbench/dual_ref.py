"""Plain reference of one root round of tree-network dual coordinate ascent.

Written from the method's description (Algorithms 1-3 of the source paper,
the smoothed hinge of Shalev-Shwartz & Zhang 2013), in straightforward
``jax.numpy``, importing nothing of the program:

    for each internal node, for each of its rounds:
        key, k_1..k_K = split(key, 1 + K)
        every child k solves from the node's (alpha, w) with key k_k
        alpha_[k] += (alpha'_[k] - alpha_[k]) / K
        w         += sum_k (w'_k - w) / K
    a leaf runs H coordinate steps, coordinate i ~ randint(key, (H,), 0, m_b):
        q = (1 - y_i w.x_i - g alpha_i y_i) / (||x_i||^2/(lam m) + g) + alpha_i y_i
        delta = y_i clip(q, 0, 1) - alpha_i
        alpha_i += delta;  w += delta x_i / (lam m)

The tree is level-homogeneous: ``branching`` children per node, top-down,
with ``rounds`` rounds per node at each depth (the root's is one: a round
of the check is one root round).  The root key chain is
``k_t = split(k_{t-1}, 1 + K_root)[0]``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def smooth_hinge_value(a, y, g):
    z = 1.0 - y * a
    return jnp.where(z <= 0.0, 0.0, jnp.where(z >= g, z - g / 2.0,
                                              z * z / (2.0 * g)))


def smooth_hinge_conj_neg(alpha, y, g):
    ay = alpha * y
    return -ay + (g / 2.0) * ay * ay


def _leaf(X, y, a, w, key, *, H, lm, g):
    """H sequential coordinate steps on one leaf block; returns the new
    (alpha block, w)."""
    m_b = X.shape[0]
    idx = jax.random.randint(key, (H,), 0, m_b)
    xsq = jnp.sum(X * X, axis=1) / lm

    def body(h, carry):
        a_c, w_c = carry
        i = idx[h]
        x = X[i]
        wx = jnp.sum(w_c * x)
        yi, ai = y[i], a_c[i]
        q = (1.0 - yi * wx - g * ai * yi) / (xsq[i] + g) + ai * yi
        delta = yi * jnp.clip(q, 0.0, 1.0) - ai
        return a_c.at[i].add(delta), w_c + (delta / lm) * x

    return jax.lax.fori_loop(0, H, body, (a, w))


def _node(depth, X, y, a, w, key, *, branching, rounds, H, lm, g):
    """Solve the subtree whose leaves are the leading axis of X/y/a
    ((n, m_b, d), (n, m_b), (n, m_b)); returns (a, w)."""
    if depth == len(branching):
        a1, w1 = _leaf(X[0], y[0], a[0], w, key, H=H, lm=lm, g=g)
        return a1[None], w1
    K = branching[depth]
    n, m_b, d = X.shape
    Xk = X.reshape(K, n // K, m_b, d)
    yk = y.reshape(K, n // K, m_b)
    child = functools.partial(_node, depth + 1, branching=branching,
                              rounds=rounds, H=H, lm=lm, g=g)
    for _ in range(rounds[depth]):
        ks = jax.random.split(key, 1 + K)
        key = ks[0]
        ak = a.reshape(K, n // K, m_b)
        a_new, w_new = jax.vmap(child, in_axes=(0, 0, 0, None, 0))(
            Xk, yk, ak, w, ks[1:])
        a = (ak + (a_new - ak) / K).reshape(n, m_b)
        w = w + jnp.sum(w_new - w[None, :], axis=0) / K
    return a, w


@functools.partial(jax.jit, static_argnames=(
    "branching", "rounds", "H", "lm", "g", "dtype"))
def root_round(X, y, alpha, w, key, *, branching, rounds, H, lm, g,
               dtype=jnp.float32):
    """One root round from (alpha (m,), w (d,)) with root key ``key``."""
    n = 1
    for b in branching:
        n *= b
    m, d = X.shape
    Xb = X.astype(dtype).reshape(n, m // n, d)
    yb = y.astype(dtype).reshape(n, m // n)
    a, w = _node(0, Xb, yb, alpha.astype(dtype).reshape(n, m // n),
                 w.astype(dtype), key, branching=branching, rounds=rounds,
                 H=H, lm=jnp.asarray(lm, dtype), g=jnp.asarray(g, dtype))
    return a.reshape(m).astype(jnp.float32), w.astype(jnp.float32)


def next_root_key(key, k_root: int):
    return jax.random.split(key, 1 + k_root)[0]


@functools.partial(jax.jit, static_argnames=("g", "dtype"))
def duality_gap(X, y, alpha, lam, *, g, dtype=jnp.float32):
    """P(w(alpha)) - D(alpha), with w(alpha) = X^T alpha / (lam m)."""
    m = X.shape[0]
    Xc, yc, ac = X.astype(dtype), y.astype(dtype), alpha.astype(dtype)
    lam = jnp.asarray(lam, jnp.float32)
    hi = jax.lax.Precision.HIGHEST
    w = jnp.dot(ac, Xc, precision=hi,
                preferred_element_type=jnp.float32) / (lam * m)
    margins = jnp.dot(Xc, w.astype(dtype), precision=hi,
                      preferred_element_type=jnp.float32)
    reg = 0.5 * lam * jnp.sum(w * w)
    primal = reg + jnp.mean(smooth_hinge_value(margins, y, g))
    dual = -reg - jnp.mean(smooth_hinge_conj_neg(ac.astype(jnp.float32),
                                                 yc.astype(jnp.float32), g))
    return primal - dual
