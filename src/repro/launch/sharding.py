"""Sharding rules: map every parameter / activation / cache tensor onto the
production mesh.

Scheme (MaxText-flavored 2D "FSDP x TP"):
  * "model" axis  -- tensor parallelism: attention heads, FFN hidden dim,
    MoE expert dim, vocab dim, recurrent channel dim.
  * "data" axis   -- batch parallelism for activations AND fully-sharded
    (FSDP/ZeRO-3) parameter+optimizer-state storage along d_model.
  * "pod" axis    -- pure data parallelism across pods (params replicated);
    this is the slow link that the paper's TreeSync schedule optimizes.

Every rule is *guarded by divisibility*: an axis is applied to a tensor dim
only if the dim divides evenly (and, for attention-head dims, only if the
head count itself divides, so shards stay head-aligned). Otherwise that dim
falls back to replication -- recorded by `explain_shardings` so the roofline
report can show what was left on the table.

Logical-axis indirection (`AxisRules`) lets the perf loop re-map logical axes
(e.g. ffn -> ("data","model") for 2D sharding) without touching the rules.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core.engine.mesh import auto_axes
from repro.launch.mesh import axis_size, data_axes

PyTree = Any

MeshAxes = Optional[Tuple[str, ...]]  # value of one logical axis


@dataclasses.dataclass(frozen=True)
class AxisRules:
    """logical axis name -> mesh axes (None = replicate)."""
    embed: MeshAxes = ("data",)        # d_model dim of weights (FSDP)
    heads: MeshAxes = ("model",)       # fused q-heads dim
    kv_heads: MeshAxes = ("model",)    # fused kv-heads dim
    ffn: MeshAxes = ("model",)         # MLP hidden
    vocab_in: MeshAxes = ("model",)    # embedding-table vocab dim
    vocab_out: MeshAxes = ("model",)   # unembedding vocab dim
    expert: MeshAxes = ("model",)      # MoE expert dim
    ffn_moe: MeshAxes = None           # per-expert hidden (after expert split)
    lru: MeshAxes = ("model",)         # RG-LRU channel dim
    rwkv_out: MeshAxes = ("model",)    # RWKV projection output dim
    layers: MeshAxes = None            # stacked-layer dim of scanned blocks
    # activations
    act_batch: MeshAxes = ("pod", "data")  # filtered per-mesh automatically
    act_seq: MeshAxes = None           # sequence dim (sequence parallelism)
    act_embed: MeshAxes = None         # activation d_model dim
    act_heads: MeshAxes = ("model",)   # activation heads dim
    # kv-cache
    cache_batch: MeshAxes = ("pod", "data")
    cache_seq: MeshAxes = ("model",)   # context slots (decode memory)
    cache_heads: MeshAxes = None
    # ZeRO-1: optimizer state gets an extra shard axis beyond its param's
    # (used with embed=None: params replicated over "data", states sharded)
    zero1: MeshAxes = None

    def get(self, name: str) -> MeshAxes:
        return getattr(self, name)


DEFAULT_RULES = AxisRules()


# ---------------------------------------------------------------------------
# parameter rules: leaf name -> logical axes of its trailing dims.
# Leading (stacked-layer) dims get the `layers` logical axis (default: none).
# ---------------------------------------------------------------------------
_PARAM_LOGICAL: Dict[str, Tuple[Optional[str], ...]] = {
    # top level
    "embed": ("vocab_in", "embed"),
    "unembed": ("embed", "vocab_out"),
    # attention
    "wq": ("embed", "heads"),
    "wk": ("embed", "kv_heads"),
    "wv": ("embed", "kv_heads"),
    "wo": ("heads", "embed"),
    "bq": ("heads",),
    "bk": ("kv_heads",),
    "bv": ("kv_heads",),
    # dense MLP
    "w_gate": ("embed", "ffn"),
    "w_up": ("embed", "ffn"),
    "w_down": ("ffn", "embed"),
    # MoE (3-D expert-stacked weights override the dense names by ndim)
    "router": ("embed", None),
    # RG-LRU
    "w_in": ("embed", "lru"),
    "conv": (None, "lru"),
    "w_a": ("embed", "lru"),
    "w_x": ("embed", "lru"),
    "w_out": ("lru", "embed"),
    # RWKV6
    "wr": ("embed", "rwkv_out"),
    "wg": ("embed", "rwkv_out"),
    "mix_lora_a": ("embed", None),
    "cm_wk": ("embed", "ffn"),
    "cm_wv": ("ffn", "embed"),
    "cm_wr": ("embed", "rwkv_out"),
}
# names resolved by surrounding context
_MOE_LOGICAL: Dict[str, Tuple[Optional[str], ...]] = {
    "w_gate": ("expert", "embed", "ffn_moe"),
    "w_up": ("expert", "embed", "ffn_moe"),
    "w_down": ("expert", "ffn_moe", "embed"),
}
_RWKV_SHARED = {"wk": ("embed", "rwkv_out"), "wv": ("embed", "rwkv_out"),
                "wo": ("rwkv_out", "embed")}


def _head_counts(cfg: ModelConfig) -> Dict[str, int]:
    return {"heads": max(cfg.num_heads, 1), "kv_heads": max(cfg.num_kv_heads, 1)}


def _resolve(
    logical: Sequence[Optional[str]],
    shape: Tuple[int, ...],
    mesh: Mesh,
    rules: AxisRules,
    cfg: ModelConfig,
    dropped: Optional[list] = None,
    path: str = "",
) -> P:
    """Turn trailing-dim logical axes into a full PartitionSpec with guards."""
    n_lead = len(shape) - len(logical)
    spec: list = []
    lead_axes = rules.get("layers")
    for i in range(n_lead):
        spec.append(None if not lead_axes else _fit(
            shape[i], lead_axes, mesh, set(), None))
    used: set = {a for s in spec if s
                 for a in (s if isinstance(s, tuple) else (s,))}
    heads = _head_counts(cfg)
    for dim, name in zip(shape[n_lead:], logical, strict=False):
        if name is None:
            spec.append(None)
            continue
        axes = rules.get(name)
        if axes is None:
            spec.append(None)
            continue
        head_align = heads.get(name)
        got = _fit(dim, axes, mesh, used, head_align)
        if got is None and dropped is not None:
            dropped.append((path, name, dim, axes))
        spec.append(got)
        if got:
            used.update(got if isinstance(got, tuple) else (got,))
    return P(*spec)


def _fit(dim: int, axes: Tuple[str, ...], mesh: Mesh, used: set,
         head_align: Optional[int]):
    """Largest prefix of `axes` that evenly divides `dim` (and head count)."""
    ok = []
    prod = 1
    for a in axes:
        if a not in mesh.axis_names or a in used:
            continue
        n = axis_size(mesh, a)
        if n == 1:
            continue
        if dim % (prod * n) != 0:
            break
        if head_align is not None and head_align % (prod * n) != 0:
            break
        ok.append(a)
        prod *= n
    if not ok:
        return None
    return tuple(ok) if len(ok) > 1 else ok[0]


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------
def param_specs(cfg: ModelConfig, params_shape: PyTree, mesh: Mesh,
                rules: AxisRules = DEFAULT_RULES,
                dropped: Optional[list] = None) -> PyTree:
    """PartitionSpec pytree matching `params_shape` (a ShapeDtypeStruct tree)."""

    def visit(path, leaf):
        keys = [getattr(k, "key", getattr(k, "idx", None)) for k in path]
        name = keys[-1]
        pstr = "/".join(str(k) for k in keys)
        in_moe = cfg.is_moe and "ffn" in keys and "dense" not in keys
        if in_moe and name in _MOE_LOGICAL:
            logical = _MOE_LOGICAL[name]
        elif cfg.is_rwkv and name in _RWKV_SHARED:
            logical = _RWKV_SHARED[name]
        elif name in _PARAM_LOGICAL:
            logical = _PARAM_LOGICAL[name]
        else:
            # norms, biases, scalars, loras: replicate trailing dims
            logical = tuple(None for _ in leaf.shape)
        # guard: logical longer than shape (e.g. unstacked smoke shapes)
        logical = logical[-len(leaf.shape):] if leaf.shape else ()
        return _resolve(logical, leaf.shape, mesh, rules, cfg, dropped, pstr)

    return jax.tree_util.tree_map_with_path(visit, params_shape)


def to_named(spec_tree: PyTree, mesh: Mesh) -> PyTree:
    mesh = auto_axes(mesh)
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))


def param_shardings(cfg: ModelConfig, params_shape: PyTree, mesh: Mesh,
                    rules: AxisRules = DEFAULT_RULES,
                    dropped: Optional[list] = None) -> PyTree:
    return to_named(param_specs(cfg, params_shape, mesh, rules, dropped), mesh)


def opt_state_specs(cfg: ModelConfig, opt_shape: PyTree, params_shape: PyTree,
                    mesh: Mesh, rules: AxisRules = DEFAULT_RULES) -> PyTree:
    """Optimizer-state specs: moments inherit their parameter's spec;
    Adafactor factored vectors inherit the spec minus the reduced dim;
    scalars replicate."""
    pspecs = param_specs(cfg, params_shape, mesh, rules)
    flat_p = {tuple(_keystr(k) for k in path): spec
              for path, spec in _flat_with_path(pspecs)}
    flat_shapes = {tuple(_keystr(k) for k in path): l.shape
                   for path, l in _flat_with_path(params_shape)}

    def zero1_extend(spec: P, shape) -> P:
        """Add the zero1 axes to the first unsharded, divisible dim."""
        z = rules.get("zero1")
        if not z:
            return spec
        out = list(spec) + [None] * (len(shape) - len(spec))
        used = {a for s in out if s
                for a in (s if isinstance(s, tuple) else (s,))}
        for i, (dim, s) in enumerate(zip(shape, out, strict=False)):
            if s is not None:
                continue
            got = _fit(dim, z, mesh, used, None)
            if got is not None:
                out[i] = got
                return P(*out)
        return P(*out)

    def visit(path, leaf):
        keys = tuple(_keystr(k) for k in path)
        if not leaf.shape:
            return P()
        # strip the state-kind prefix ('mu'/'nu'/'v'/'mom') to find the param
        for start in range(len(keys)):
            cand = keys[start + 1:]
            if cand in flat_p:
                spec, pshape = flat_p[cand], flat_shapes[cand]
                if leaf.shape == pshape:
                    return zero1_extend(spec, leaf.shape)
                if keys[-1] == "vr" and leaf.shape == pshape[:-1]:
                    return zero1_extend(P(*spec[:-1]), leaf.shape)
                if keys[-1] == "vc" and leaf.shape == pshape[:-2] + pshape[-1:]:
                    return zero1_extend(P(*(spec[:-2] + spec[-1:])),
                                        leaf.shape)
        # vr/vc live one level deeper than the param name
        for start in range(len(keys)):
            cand = keys[start + 1:-1]
            if cand in flat_p:
                spec, pshape = flat_p[cand], flat_shapes[cand]
                if leaf.shape == pshape:
                    return zero1_extend(spec, leaf.shape)
                if keys[-1] == "vr" and leaf.shape == pshape[:-1]:
                    return zero1_extend(P(*spec[:-1]), leaf.shape)
                if keys[-1] == "vc" and leaf.shape == pshape[:-2] + pshape[-1:]:
                    return zero1_extend(P(*(spec[:-2] + spec[-1:])),
                                        leaf.shape)
        return P(*(None for _ in leaf.shape))

    return jax.tree_util.tree_map_with_path(visit, opt_shape)


def _keystr(k):
    return getattr(k, "key", getattr(k, "idx", None))


def _flat_with_path(tree):
    return jax.tree_util.tree_flatten_with_path(tree)[0]


# ---------------------------------------------------------------------------
# activations / batches / caches
# ---------------------------------------------------------------------------
def _batch_axes(mesh: Mesh, rules: AxisRules, b: int,
                which: str = "act_batch") -> MeshAxes:
    axes = tuple(a for a in (rules.get(which) or ()) if a in mesh.axis_names)
    got = _fit(b, axes, mesh, set(), None)
    return got


def batch_specs(cfg: ModelConfig, mesh: Mesh, batch_shape: Dict[str, Any],
                rules: AxisRules = DEFAULT_RULES) -> Dict[str, P]:
    """PartitionSpecs for a train/prefill/decode input batch dict."""
    out = {}
    for k, v in batch_shape.items():
        b_ax = _batch_axes(mesh, rules, v.shape[0])
        trailing = [None] * (len(v.shape) - 1)
        if k == "embeds" and len(v.shape) == 3:
            trailing = [rules.get("act_seq") and _fit(
                v.shape[1], rules.get("act_seq"), mesh, set(), None), None]
        out[k] = P(b_ax, *trailing)
    return out


def cache_specs(cfg: ModelConfig, cache_shape: PyTree, mesh: Mesh,
                rules: AxisRules = DEFAULT_RULES) -> PyTree:
    """Decode-cache specs. Attention caches (stacked: (L, B, n, kv, hd)):
    batch over data axes, context slots over `cache_seq`; recurrent states
    (L, B, W)/(L, B, H, N, N): batch over data, channel/head over model."""

    def visit(path, leaf):
        keys = [_keystr(k) for k in path]
        name = keys[-1]
        shape = leaf.shape
        if not shape:
            return P()
        stacked = "blocks" in keys  # leading L dim present
        lead = 1 if stacked else 0
        spec: list = [None] * len(shape)
        if name in ("k", "v"):
            spec[lead] = _batch_axes(mesh, rules, shape[lead], "cache_batch")
            cs = rules.get("cache_seq")
            if cs:
                spec[lead + 1] = _fit(shape[lead + 1], cs, mesh, set(), None)
            ch = rules.get("cache_heads")
            if ch:
                spec[lead + 2] = _fit(shape[lead + 2], ch, mesh, set(),
                                      cfg.num_kv_heads)
        elif name == "slot_pos":
            cs = rules.get("cache_seq")
            if cs:
                spec[lead] = _fit(shape[lead], cs, mesh, set(), None)
        elif name in ("h", "conv", "wkv", "tm_prev", "cm_prev"):
            spec[lead] = _batch_axes(mesh, rules, shape[lead], "cache_batch")
            # trailing channel dim over model when divisible
            got = _fit(shape[-1], ("model",), mesh, set(), None)
            if name == "wkv" and len(shape) > lead + 1:
                # (L, B, H, N, N): shard heads
                spec[lead + 1] = _fit(shape[lead + 1], ("model",), mesh,
                                      set(), None)
            elif got is not None and len(shape) - 1 > lead:
                spec[-1] = got
        return P(*spec)

    return jax.tree_util.tree_map_with_path(visit, cache_shape)


def explain_shardings(cfg: ModelConfig, params_shape: PyTree, mesh: Mesh,
                      rules: AxisRules = DEFAULT_RULES) -> Dict[str, Any]:
    """Report what was sharded and what fell back to replication."""
    dropped: list = []
    specs = param_specs(cfg, params_shape, mesh, rules, dropped)
    total = 0
    sharded = 0
    for (_path, leaf), (_, spec) in zip(
            _flat_with_path(params_shape), _flat_with_path(specs),
            strict=True):
        n = 1
        for d in leaf.shape:
            n *= d
        total += n
        denom = 1
        for s in spec:
            for a in (s if isinstance(s, tuple) else (s,)) if s else ():
                denom *= axis_size(mesh, a)
        sharded += n // denom
    return {
        "params_total": total,
        "params_per_device_max": sharded,
        "replicated_fallbacks": [
            {"path": p, "logical": n, "dim": d, "axes": list(a)}
            for p, n, d, a in dropped
        ],
    }
