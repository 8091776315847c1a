"""Plain reference of the LM cells' training step, importing nothing of
the program: a dense decoder (RMSNorm with a (1 + scale) gain, rotary
embeddings on the two halves of each head, grouped-query causal attention
inside a sliding window, SwiGLU MLP, untied output head, mean next-token
cross-entropy) and AdamW (global-norm clipping at 1, decoupled weight
decay on matrices only), in float32 at the ``highest`` matmul precision.

It is the reference of an LM configuration without a ``reference`` key,
and provides what ``harness.load_reference`` asks of one: ``init`` (the
dense decoder's weights, ``gen.lm_init``), ``loss_fn`` and
``flops_per_token`` (``work.lm_flops_per_token``).  The parts every
architecture shares stay here for the others to use: ``adamw_init``,
``train_step`` (which takes the architecture's loss), ``leaf_norms`` and
``diff_norms``.

``q`` lets the same code run one precision below the configuration (the
control): every matmul input is rounded to that dtype first.
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import gen, work

HI = jax.lax.Precision.HIGHEST

init = gen.lm_init
flops_per_token = work.lm_flops_per_token


def _round(x, dtype):
    return x if dtype is None else x.astype(dtype).astype(jnp.float32)


def _mm(spec, a, b, q):
    return jnp.einsum(spec, _round(a, q), _round(b, q), precision=HI,
                      preferred_element_type=jnp.float32)


def _rms(x, scale, eps=1e-6):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale)


def _rope(x, theta):
    """x: (B, S, N, hd); rotate the two halves of each head."""
    S, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freq      # (S, half)
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(p, x, model, q):
    B, S, D = x.shape
    H, KV = model["num_heads"], model["num_kv_heads"]
    hd, rep = D // H, H // KV
    h = _rms(x, p["ln1"])
    qh = _mm("bsd,de->bse", h, p["mix"]["wq"], q).reshape(B, S, H, hd)
    kh = _mm("bsd,de->bse", h, p["mix"]["wk"], q).reshape(B, S, KV, hd)
    vh = _mm("bsd,de->bse", h, p["mix"]["wv"], q).reshape(B, S, KV, hd)
    theta = float(model.get("rope_theta", 10_000.0))
    qh, kh = _rope(qh, theta), _rope(kh, theta)
    qg = qh.reshape(B, S, KV, rep, hd)
    s = _mm("bqgrd,bkgd->bgrqk", qg, kh, q) * hd ** -0.5
    pos = jnp.arange(S)
    allowed = pos[:, None] >= pos[None, :]
    if model.get("window"):
        allowed &= (pos[:, None] - pos[None, :]) < int(model["window"])
    s = jnp.where(allowed, s, -jnp.inf)
    probs = jax.nn.softmax(s, axis=-1)
    o = _mm("bgrqk,bkgd->bqgrd", probs, vh, q).reshape(B, S, H * hd)
    x = x + _mm("bse,ed->bsd", o, p["mix"]["wo"], q)
    h2 = _rms(x, p["ln2"])
    f = p["ffn"]
    a = jax.nn.silu(_mm("bsd,df->bsf", h2, f["w_gate"], q)) * _mm(
        "bsd,df->bsf", h2, f["w_up"], q)
    return x + _mm("bsf,fd->bsd", a, f["w_down"], q)


def loss_fn(params, batch, model, q=None):
    x = params["embed"][batch["tokens"]]

    @jax.checkpoint
    def body(x, p):
        return _layer(p, x, model, q), None

    x, _ = jax.lax.scan(body, x, params["blocks"]["sub0"])
    h = _rms(x, params["final_ln"])
    logits = _mm("bsd,dv->bsv", h, params["unembed"], q)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, batch["labels"][..., None], -1)[..., 0]
    return jnp.mean(lse - ll)


def adamw_init(params):
    z = jax.tree.map(jnp.zeros_like, params)
    return {"step": jnp.zeros((), jnp.int32), "mu": z,
            "nu": jax.tree.map(jnp.zeros_like, params)}


def _decayed(path) -> bool:
    """Matrices decay; norm gains (per layer: 1-D) do not."""
    name = jax.tree_util.keystr(path)
    return "ln" not in name.rsplit("[", 1)[-1]


@functools.partial(jax.jit,
                   static_argnames=("loss", "opt", "q", "model_items"),
                   donate_argnums=(0, 1))
def train_step(params, state, batch, *, loss, opt, q, model_items):
    """One AdamW step of ``loss(params, batch, model, q)``; returns
    (params, state, loss value, clipped grads)."""
    model = dict(model_items)
    lr, b1, b2, eps, wd, clip = opt
    value, g = jax.value_and_grad(loss)(params, batch, model, q)
    gnorm = jnp.sqrt(sum(jnp.sum(t * t) for t in jax.tree.leaves(g)) + 1e-16)
    g = jax.tree.map(lambda t: t * jnp.minimum(1.0, clip / gnorm), g)
    step = state["step"] + 1
    bc1 = 1.0 - b1 ** step.astype(jnp.float32)
    bc2 = 1.0 - b2 ** step.astype(jnp.float32)
    mu = jax.tree.map(lambda m, t: b1 * m + (1 - b1) * t, state["mu"], g)
    nu = jax.tree.map(lambda n, t: b2 * n + (1 - b2) * t * t, state["nu"], g)

    def upd(path, p, m, n):
        d = wd if _decayed(path) else 0.0
        return p - lr * ((m / bc1) / (jnp.sqrt(n / bc2) + eps) + d * p)

    params = jax.tree_util.tree_map_with_path(upd, params, mu, nu)
    return params, {"step": step, "mu": mu, "nu": nu}, value, g


# ---------------------------------------------------------------------------
# per-leaf norms: the stacked layer arrays count one leaf per layer
# ---------------------------------------------------------------------------
def leaf_norms(tree) -> Dict[str, float]:
    out = {}
    for path, t in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = jax.tree_util.keystr(path)
        if name.startswith("['blocks']"):
            per = jnp.sqrt(jnp.sum(jnp.square(t.astype(jnp.float32)),
                                   axis=tuple(range(1, t.ndim))))
            for i, v in enumerate(np.asarray(per)):
                out[f"{name}[{i}]"] = float(v)
        else:
            out[name] = float(jnp.sqrt(jnp.sum(jnp.square(
                t.astype(jnp.float32)))))
    return out


def diff_norms(a, b) -> Dict[str, float]:
    return leaf_norms(jax.tree.map(lambda x, y: x - y, a, b))
