"""Sharding trees for full train/serve state (params + optimizer + caches).

The dry-run lowers ``train_step``/``serve_step`` against ShapeDtypeStruct
stand-ins; every input leaf needs an explicit NamedSharding or the 400B
configs would lower as fully replicated and trivially "OOM". Param shardings
come from the ParamSpec logical axes; optimizer-state leaves mirror their
parameter's axes (int8-moment scale tensors have the same rank, so the same
axes apply — the divisibility guard replicates any block-count dim that no
longer divides); cache leaves get the serving layout (batch on ``data``,
cache sequence on ``model`` — the baseline; §Perf iterates on this).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig, RunConfig
from ..models import init_caches, model_spec
from ..models.transformer import plan_groups
from ..train.train_step import init_train_state
from .sharding import shape_structs, sharding_for

__all__ = [
    "abstract_train_state",
    "train_state_sharding",
    "abstract_caches",
    "cache_sharding",
    "batch_sharding",
    "with_sharding",
]

BATCH_AXES = {
    "tokens": ("batch", "seq"),
    "labels": ("batch", "seq"),
    "loss_mask": ("batch", "seq"),
    "embeds": ("batch", "seq", None),
    "positions": (None, "batch", "seq"),
}


def _path_str(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)


def abstract_train_state(cfg: ModelConfig, rc: RunConfig):
    params_abs = shape_structs(model_spec(cfg), jnp.dtype(rc.param_dtype))
    return jax.eval_shape(lambda p: init_train_state(cfg, rc, p), params_abs)


def train_state_sharding(cfg: ModelConfig, rc: RunConfig, state_abs):
    """NamedSharding tree matching ``state_abs`` under the active mesh ctx."""
    from .sharding import ParamSpec

    axes_by_path: dict[str, tuple] = {}
    flat_axes, _ = jax.tree_util.tree_flatten_with_path(
        model_spec(cfg), is_leaf=lambda x: isinstance(x, ParamSpec)
    )
    for path, spec in flat_axes:
        axes_by_path[_path_str(path)] = spec.axes

    def leaf_axes(path_str: str, leaf) -> tuple:
        parts = path_str.split("/")
        if parts[-1] in ("q", "s"):
            parts = parts[:-1]
        # strip state prefixes: params/..., ef/..., opt/<idx>/...
        if parts[0] in ("params", "ef"):
            parts = parts[1:]
        elif parts[0] == "opt":
            parts = parts[2:]
        key = "/".join(parts)
        if key in axes_by_path:
            return axes_by_path[key]
        return (None,) * leaf.ndim  # scalars / step counters -> replicated

    flat_state, treedef = jax.tree_util.tree_flatten_with_path(state_abs)
    out = [
        sharding_for(leaf_axes(_path_str(path), leaf), leaf.shape)
        for path, leaf in flat_state
    ]
    return jax.tree_util.tree_unflatten(treedef, out)


# ------------------------------------------------------------------- caches
_CACHE_AXES = {
    "k": ("layers", "batch", "kv_seq", "cache_heads", None),
    "v": ("layers", "batch", "kv_seq", "cache_heads", None),
    "k_scale": ("layers", "batch", "kv_seq"),
    "v_scale": ("layers", "batch", "kv_seq"),
    "ckv": ("layers", "batch", "kv_seq", None),
    "kr": ("layers", "batch", "kv_seq", None),
    "ckv_scale": ("layers", "batch", "kv_seq"),
    "kr_scale": ("layers", "batch", "kv_seq"),
    "h": ("layers", "batch", "inner", None),
    "conv": ("layers", "batch", None, "inner"),
}


def abstract_caches(
    cfg: ModelConfig, rc: RunConfig, batch: int, capacity: int, *, num_pages=None
):
    return jax.eval_shape(
        lambda: init_caches(cfg, rc, batch, capacity, num_pages=num_pages)
    )


# paged layout: one KV leaf is a page pool (layers, pages+1, block, F) —
# pages replicate (any slot's block table must reach any page from its data
# shard) and a GQA pool shards its head-major kv*hd features on heads, the
# vLLM-style TP cache split
_PAGED_CACHE_AXES = {
    "k": ("layers", None, None, "cache_heads"),
    "v": ("layers", None, None, "cache_heads"),
    "k_scale": ("layers", None, None),
    "v_scale": ("layers", None, None),
    "ckv": ("layers", None, None, None),
    "kr": ("layers", None, None, None),
    "ckv_scale": ("layers", None, None),
    "kr_scale": ("layers", None, None),
}


def cache_sharding(cfg: ModelConfig, rc: RunConfig, caches_abs):
    axes_map = dict(_CACHE_AXES)
    if rc.kv_layout == "paged":
        axes_map.update(_PAGED_CACHE_AXES)

    def one(path, leaf):
        name = str(getattr(path[-1], "key", path[-1]))
        axes = axes_map.get(name, (None,) * leaf.ndim)
        return sharding_for(axes, leaf.shape)

    flat, treedef = jax.tree_util.tree_flatten_with_path(caches_abs)
    return jax.tree_util.tree_unflatten(treedef, [one(p, l) for p, l in flat])


def batch_sharding(batch_abs):
    def one(path, leaf):
        name = str(getattr(path[-1], "key", path[-1]))
        axes = BATCH_AXES.get(name, (None,) * leaf.ndim)
        return sharding_for(axes, leaf.shape)

    flat, treedef = jax.tree_util.tree_flatten_with_path(batch_abs)
    return jax.tree_util.tree_unflatten(treedef, [one(p, l) for p, l in flat])


def abstract_prequant_params(cfg: ModelConfig, rc: RunConfig):
    """Abstract param tree after offline PTQ packing (serving weight path).

    Goes through quant.surgery so the QuantPolicy's per-leaf bitwidths shape
    the packed tree exactly as the real weights would be (mixed policies
    pack different leaves at different widths)."""
    from ..quant.surgery import apply_surgery

    params_abs = shape_structs(model_spec(cfg), jnp.dtype(rc.param_dtype))
    return jax.eval_shape(lambda p: apply_surgery(cfg, rc, p), params_abs)


def prequant_param_sharding(cfg: ModelConfig, rc: RunConfig, params_q_abs):
    """Shardings for a prequantized tree: qkernel inherits the kernel's axes
    (same rank — packing shrinks K in place), qscale keeps the leading stack
    axes plus the output axis (it drops K)."""
    from .sharding import ParamSpec

    axes_by_path: dict[str, tuple] = {}
    flat_axes, _ = jax.tree_util.tree_flatten_with_path(
        model_spec(cfg), is_leaf=lambda x: isinstance(x, ParamSpec)
    )
    for path, spec in flat_axes:
        axes_by_path[_path_str(path)] = spec.axes

    def _kernel_axes(base: str):
        # nested linear leaf ({.../wq/kernel}) or a raw MoE expert stack
        # whose ParamSpec sits at the key itself (.../experts/w_gate)
        axes = axes_by_path.get(base + "/kernel")
        return axes if axes is not None else axes_by_path.get(base)

    def one(path, leaf):
        ps = _path_str(path)
        if ps.endswith("/qkernel"):
            kaxes = _kernel_axes(ps[: -len("/qkernel")])
            axes = kaxes if kaxes is not None else (None,) * leaf.ndim
        elif ps.endswith("/qscale"):
            kaxes = _kernel_axes(ps[: -len("/qscale")])
            axes = (kaxes[:-2] + (kaxes[-1],)) if kaxes is not None \
                else (None,) * leaf.ndim
        else:
            axes = axes_by_path.get(ps, (None,) * leaf.ndim)
        return sharding_for(axes, leaf.shape)

    flat, treedef = jax.tree_util.tree_flatten_with_path(params_q_abs)
    return jax.tree_util.tree_unflatten(treedef, [one(p, l) for p, l in flat])


def with_sharding(abs_tree, sharding_tree):
    """Attach NamedShardings into ShapeDtypeStructs (jit.lower consumes them)."""
    return jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        abs_tree,
        sharding_tree,
    )
