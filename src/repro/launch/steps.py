"""jit-able step functions + ShapeDtypeStruct input specs for every
(architecture x shape) cell. These are what the dry-run lowers and what the
real train/serve drivers run.

  train_4k     -> train_step(params, opt_state, batch)
  prefill_32k  -> prefill_step(params, batch)           (builds the cache)
  decode_32k   -> serve_step(params, cache, tokens)     (one new token)
  long_500k    -> serve_step with a 512k-token cache    (sub-quadratic only)
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.configs.shapes import ShapeSpec
from repro.core.engine.mesh import auto_axes
from repro.launch import sharding as sh
from repro.launch.mesh import data_axes
from repro.models import transformer
from repro.optim import Optimizer, get_optimizer

PyTree = Any


# ---------------------------------------------------------------------------
# input specs (ShapeDtypeStructs -- no allocation; dry-run stand-ins)
# ---------------------------------------------------------------------------
def input_specs(cfg: ModelConfig, shape: ShapeSpec) -> Dict[str, Any]:
    """Model-input stand-ins for one shape cell.

    [audio]/[vlm] backbones take precomputed frame/patch embeddings for
    full-sequence passes (the modality frontend is a stub per assignment);
    decode always feeds tokens through the text embedding table.
    """
    B, S = shape.global_batch, shape.seq_len
    ii32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32)
    if shape.kind == "decode":
        return {"tokens": ii32((B, 1))}
    batch: Dict[str, Any] = {}
    if cfg.input_mode == "embeddings":
        batch["embeds"] = jax.ShapeDtypeStruct((B, S, cfg.d_model),
                                               jnp.bfloat16)
    else:
        batch["tokens"] = ii32((B, S))
    if shape.kind == "train":
        batch["labels"] = ii32((B, S))
    return batch


def params_shape(cfg: ModelConfig) -> PyTree:
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    return jax.eval_shape(lambda k: transformer.init_params(cfg, k), key)


def cache_shape(cfg: ModelConfig, batch: int, max_len: int) -> PyTree:
    return jax.eval_shape(
        lambda: transformer.init_cache(cfg, batch, max_len))


def opt_shape(cfg: ModelConfig, optimizer: Optimizer) -> PyTree:
    return jax.eval_shape(optimizer.init, params_shape(cfg))


# ---------------------------------------------------------------------------
# step functions
# ---------------------------------------------------------------------------
def _shard_scope(shard_ctx):
    """Context entered INSIDE the traced step so model-level
    `constrain(...)` calls resolve; no-op when shard_ctx is None."""
    import contextlib
    if shard_ctx is None:
        return contextlib.nullcontext()
    from repro.models.shardctx import activation_sharding
    return activation_sharding(*shard_ctx)


def make_train_step(cfg: ModelConfig, optimizer: Optional[Optimizer] = None,
                    shard_ctx=None, microbatches: int = 1,
                    unroll_microbatches: bool = False) -> Callable:
    """microbatches > 1 = gradient accumulation: the global batch is split
    along dim 0 and grads are averaged across sequential microbatch passes.
    Activation working set (incl. remat-saved layer inputs) shrinks by the
    microbatch factor; FLOPs are unchanged. unroll_microbatches=True emits
    the accumulation loop unrolled (analysis-grade HLO for the dry-run)."""
    optimizer = optimizer or get_optimizer(cfg)

    def grads_of(params, mb):
        def loss_fn(p):
            total, metrics = transformer.forward_train(cfg, p, mb)
            return total, metrics

        (_, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        return grads, metrics

    def train_step(params, opt_state, batch):
        with _shard_scope(shard_ctx):
            if microbatches == 1:
                grads, metrics = grads_of(params, batch)
            else:
                mbs = {k: v.reshape((microbatches,
                                     v.shape[0] // microbatches)
                                    + v.shape[1:])
                       for k, v in batch.items()}
                if unroll_microbatches:
                    acc, metrics = grads_of(
                        params, {k: v[0] for k, v in mbs.items()})
                    for i in range(1, microbatches):
                        g_i, m_i = grads_of(
                            params, {k: v[i] for k, v in mbs.items()})
                        acc = jax.tree.map(jnp.add, acc, g_i)
                        metrics = jax.tree.map(jnp.add, metrics, m_i)
                else:
                    def body(carry, mb):
                        acc, mets = carry
                        g_i, m_i = grads_of(params, mb)
                        return (jax.tree.map(jnp.add, acc, g_i),
                                jax.tree.map(jnp.add, mets, m_i)), None

                    g0, m0 = grads_of(params,
                                      {k: v[0] for k, v in mbs.items()})
                    (acc, metrics), _ = jax.lax.scan(
                        body, (g0, m0),
                        {k: v[1:] for k, v in mbs.items()})
                grads = jax.tree.map(lambda g: g / microbatches, acc)
                metrics = jax.tree.map(lambda m: m / microbatches, metrics)
            params, opt_state = optimizer.update(params, grads, opt_state)
            return params, opt_state, metrics

    return train_step


def make_prefill_step(cfg: ModelConfig, max_len: Optional[int] = None,
                      shard_ctx=None) -> Callable:
    def prefill_step(params, batch):
        with _shard_scope(shard_ctx):
            return transformer.prefill(cfg, params, batch, max_len=max_len)

    return prefill_step


def make_serve_step(cfg: ModelConfig, shard_ctx=None) -> Callable:
    """One decode step: greedy next token + updated cache."""

    def serve_step(params, cache, tokens):
        with _shard_scope(shard_ctx):
            logits, cache = transformer.decode_step(cfg, params, cache,
                                                    tokens)
            next_tokens = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
            return next_tokens, cache

    return serve_step


# ---------------------------------------------------------------------------
# fully-sharded jit wrappers for one (cfg x shape x mesh) cell
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class CellProgram:
    """Everything needed to lower/compile/run one cell."""
    kind: str
    jitted: Any                 # jax.jit-wrapped fn (shardings applied)
    arg_shapes: Tuple[Any, ...]  # ShapeDtypeStructs (lower(*arg_shapes))
    in_shardings: Tuple[Any, ...]
    notes: Dict[str, Any]

    def lower(self):
        return self.jitted.lower(*self.arg_shapes)


def build_cell(cfg: ModelConfig, shape: ShapeSpec, mesh: Mesh,
               rules: sh.AxisRules = sh.DEFAULT_RULES,
               optimizer: Optional[Optimizer] = None,
               microbatches: int = 1) -> CellProgram:
    """Construct the jitted step + shardings + abstract inputs for a cell."""
    mesh = auto_axes(mesh)
    pshape = params_shape(cfg)
    pspecs = sh.param_specs(cfg, pshape, mesh, rules)
    psh = sh.to_named(pspecs, mesh)
    batch = input_specs(cfg, shape)
    bspecs = sh.batch_specs(cfg, mesh, batch)
    bsh = {k: NamedSharding(mesh, s) for k, s in bspecs.items()}
    notes: Dict[str, Any] = {"mesh": dict(mesh.shape)}

    shard_ctx = (mesh, rules)
    if shape.kind == "train":
        optimizer = optimizer or get_optimizer(cfg)
        oshape = jax.eval_shape(optimizer.init, pshape)
        ospecs = sh.opt_state_specs(cfg, oshape, pshape, mesh, rules)
        osh = sh.to_named(ospecs, mesh)
        step = make_train_step(
            cfg, optimizer, shard_ctx=shard_ctx, microbatches=microbatches,
            # scans under-count in cost_analysis; unroll when analyzing
            unroll_microbatches=not cfg.scan_layers)
        metrics_sh = NamedSharding(mesh, P())
        jitted = jax.jit(  # analysis: allow(jit-outside-engine) CellProgram owns its one jitted step; cached on the program object
            step,
            in_shardings=(psh, osh, bsh),
            out_shardings=(psh, osh, metrics_sh),
            donate_argnums=(0, 1),
        )
        return CellProgram("train", jitted, (pshape, oshape, batch),
                           (psh, osh, bsh), notes)

    if shape.kind == "prefill":
        step = make_prefill_step(cfg, max_len=shape.seq_len,
                                 shard_ctx=shard_ctx)
        cshape = cache_shape(cfg, shape.global_batch, shape.seq_len)
        csh = sh.to_named(sh.cache_specs(cfg, cshape, mesh, rules), mesh)
        logits_sh = NamedSharding(
            mesh, P(sh._batch_axes(mesh, rules, shape.global_batch), None))
        jitted = jax.jit(step, in_shardings=(psh, bsh),  # analysis: allow(jit-outside-engine) CellProgram owns its one jitted step; cached on the program object
                         out_shardings=(logits_sh, csh))
        return CellProgram("prefill", jitted, (pshape, batch),
                           (psh, bsh), notes)

    # decode: one new token against a seq_len-deep cache
    step = make_serve_step(cfg, shard_ctx=shard_ctx)
    cshape = cache_shape(cfg, shape.global_batch, shape.seq_len)
    csh = sh.to_named(sh.cache_specs(cfg, cshape, mesh, rules), mesh)
    tok_sh = NamedSharding(
        mesh, P(sh._batch_axes(mesh, rules, shape.global_batch), None))
    jitted = jax.jit(step, in_shardings=(psh, csh, tok_sh),  # analysis: allow(jit-outside-engine) CellProgram owns its one jitted step; cached on the program object
                     out_shardings=(tok_sh, csh), donate_argnums=(1,))
    return CellProgram("decode", jitted, (pshape, cshape, batch["tokens"]),
                       (psh, csh, tok_sh), notes)


def cell_is_supported(cfg: ModelConfig, shape: ShapeSpec) -> Tuple[bool, str]:
    """long_500k needs sub-quadratic sequence mixing (see DESIGN.md §5)."""
    if shape.name == "long_500k" and not cfg.sub_quadratic:
        return False, ("full-attention arch: 512k dense-KV decode skipped "
                       "(DESIGN.md §5 Arch-applicability)")
    return True, ""
