"""Inputs made from ``--seed``: keys, the dual cells' data, and the LM
cells' initial weights and token stream.

Everything here is the benchmark's own copy.  The LM weight init and the
token stream follow the program's published recipe (``init_params`` and
``lm_batch`` of the program this benchmark measures) operation for
operation, so that the reference can rebuild, from the seed alone, the
exact weights and batches the program trains on without taking them from
the program.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def base_key(seed: int) -> jax.Array:
    """A PRNG key from any whole seed, 64-bit ones included."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    lo, hi = seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF
    return jax.random.fold_in(jax.random.PRNGKey(lo), hi)


def stream_key(seed: int, stream: int) -> jax.Array:
    """Independent key streams of one seed (0: data, 1: solver, 2: sample)."""
    return jax.random.fold_in(base_key(seed), stream)


def program_seed(seed: int) -> int:
    """The 31-bit seed handed to program entry points that take an int."""
    return int(seed) % (2**31 - 1)


# ---------------------------------------------------------------------------
# dual cells: a dense Gaussian classification problem, made in one jitted
# call on the device
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("m", "d", "margin"))
def gaussian_classification(key, *, m: int, d: int, margin: float):
    """Rows iid N(0, 1); labels in {-1, +1} from a planted direction plus
    Gaussian label noise of scale ``margin``."""
    kx, kw, kn = jax.random.split(key, 3)
    X = jax.random.normal(kx, (m, d), jnp.float32)
    w_star = jax.random.normal(kw, (d,), jnp.float32) / jnp.sqrt(d)
    score = X @ w_star + margin * jax.random.normal(kn, (m,), jnp.float32)
    y = jnp.where(score >= 0, 1.0, -1.0).astype(jnp.float32)
    return X, y


GENERATORS = {"gaussian_classification": gaussian_classification}


def dual_data(data_cfg: dict, seed: int):
    gen = GENERATORS[data_cfg["generator"]]
    return gen(stream_key(seed, 0), m=int(data_cfg["m"]),
               d=int(data_cfg["d"]), margin=float(data_cfg["margin"]))


# ---------------------------------------------------------------------------
# LM cells: weights and batches as the program makes them from its seed
# ---------------------------------------------------------------------------
def _dense(key, shape, scale=None):
    fan_in = shape[0] if len(shape) >= 2 else shape[-1]
    s = scale if scale is not None else fan_in ** -0.5
    return jax.random.normal(key, shape) * s


def _layer_init(key, model: dict) -> dict:
    d, h, kv = model["d_model"], model["num_heads"], model["num_kv_heads"]
    hd, f = d // h, model["d_ff"]
    k1, k2 = jax.random.split(key)
    ka = jax.random.split(k1, 6)
    kf = jax.random.split(k2, 3)
    return {
        "ln1": jnp.zeros((d,), jnp.float32),
        "mix": {"wq": _dense(ka[0], (d, h * hd)),
                "wk": _dense(ka[1], (d, kv * hd)),
                "wv": _dense(ka[2], (d, kv * hd)),
                "wo": _dense(ka[3], (h * hd, d))},
        "ln2": jnp.zeros((d,), jnp.float32),
        "ffn": {"w_gate": _dense(kf[0], (d, f)),
                "w_up": _dense(kf[1], (d, f)),
                "w_down": _dense(kf[2], (f, d))},
    }


def lm_init(model: dict, seed: int) -> dict:
    """float32 weights of a dense GQA/SwiGLU decoder, one stacked block
    group of ``num_layers`` layers (the program's layout)."""
    key = jax.random.PRNGKey(program_seed(seed))
    k_emb, k_blocks, _k_tail, k_un = jax.random.split(key, 4)
    d, V, L = model["d_model"], model["vocab_size"], model["num_layers"]

    def block(bk):
        return {"sub0": _layer_init(jax.random.split(bk, 1)[0], model)}

    return {
        "embed": _dense(k_emb, (V, d), scale=0.02),
        "final_ln": jnp.zeros((d,), jnp.float32),
        "blocks": jax.vmap(block)(jax.random.split(k_blocks, L)),
        "unembed": _dense(k_un, (d, V)),
    }


def lm_batch(vocab: int, batch: int, seq: int, step: int, seed: int) -> dict:
    """Batch ``step`` of the synthetic token stream (Zipf unigrams with
    period-8 motifs on half of the rows)."""
    key = jax.random.fold_in(jax.random.PRNGKey(program_seed(seed)), step)
    kz, km, _kpos, kmask = jax.random.split(key, 4)
    logits = -jnp.log(jnp.arange(vocab, dtype=jnp.float32) + 10.0)
    toks = jax.random.categorical(kz, logits, shape=(batch, seq + 1))
    motif = jax.random.randint(km, (batch, 8), 0, vocab)
    tiled = jnp.tile(motif, (1, (seq + 1) // 8 + 1))[:, : seq + 1]
    use_motif = jax.random.bernoulli(kmask, 0.5, (batch, 1))
    toks = jnp.where(use_motif, tiled, toks)
    return {"tokens": toks[:, :-1].astype(jnp.int32),
            "labels": toks[:, 1:].astype(jnp.int32)}
