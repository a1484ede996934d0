"""Unified backbone for every assigned family: dense / MoE / SSM / hybrid /
encoder / VLM.

Layers are partitioned into **scan groups** so HLO size stays O(#distinct
layer kinds), not O(num_layers) — required for the 48-layer 400B config on a
512-device mesh. A *kind* is the static structure of one block
(attention type, MoE?, global-vs-sliding attention); the planner finds a
periodic pattern (llama4's dense/MoE alternation scans as 24 two-block
super-layers) or falls back to contiguous uniform segments (hymba's three
full-attention layers split the SWA stack). Params and caches for a group are
stacked along a leading ``layers`` axis and driven by ``lax.scan``: params
as the scan's xs, caches in its carry, so each layer writes and reads its
cache in place at its layer index (the scan counter) and a step never
slices out or copies a whole layer of the cache.

Block layouts (pre-norm, residual):
- dense/MoE:  x += attn(norm(x));  x += mlp|moe(norm(x))
- ssm:        x += mamba(norm(x))                      (mamba1: no separate MLP)
- hybrid:     x += fuse(attn(norm(x)), mamba(norm(x))); x += mlp(norm(x))
  where fuse = mean of per-branch RMS-normed outputs (Hymba's parallel heads).
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig, RunConfig
from ..parallel.sharding import ParamSpec, constrain
from ..quant import capture as stats_capture
from ..quant.qlinear import GemmBackend, dense
from .attention import (
    KVView,
    gqa_attention,
    gqa_spec,
    init_kv_cache,
    mla_attention,
    mla_spec,
)
from .layers import embed_lookup, embed_spec, linear_spec, mlp, mlp_spec, rms_norm, rms_norm_spec
from .moe import moe_ffn, moe_spec
from .ssm import init_ssm_state, mamba_decode_step, mamba_mixer, mamba_spec

__all__ = [
    "LayerKind",
    "layer_kind",
    "plan_groups",
    "model_spec",
    "forward",
    "lm_logits",
    "init_caches",
    "backend_from",
]


# --------------------------------------------------------------- layer plan
@dataclass(frozen=True)
class LayerKind:
    mixer: str          # gqa | mla | ssm | hybrid
    moe: bool
    is_global: bool     # full attention (vs sliding window)


def layer_kind(cfg: ModelConfig, i: int) -> LayerKind:
    if cfg.family == "ssm":
        mixer = "ssm"
    elif cfg.family == "hybrid":
        mixer = "hybrid"
    else:
        mixer = cfg.attn_type
    return LayerKind(mixer=mixer, moe=cfg.is_moe_layer(i), is_global=cfg.is_global_attn(i))


@dataclass(frozen=True)
class Group:
    kinds: tuple[LayerKind, ...]   # super-block structure (usually length 1)
    repeats: int


def plan_groups(cfg: ModelConfig) -> tuple[Group, ...]:
    kinds = [layer_kind(cfg, i) for i in range(cfg.num_layers)]
    # periodic pattern (e.g. llama4 dense/MoE alternation)
    for p in (1, 2, 3, 4):
        if cfg.num_layers % p == 0 and all(
            kinds[i] == kinds[i % p] for i in range(cfg.num_layers)
        ):
            return (Group(tuple(kinds[:p]), cfg.num_layers // p),)
    # contiguous uniform segments
    groups: list[Group] = []
    i = 0
    while i < cfg.num_layers:
        j = i
        while j < cfg.num_layers and kinds[j] == kinds[i]:
            j += 1
        groups.append(Group((kinds[i],), j - i))
        i = j
    return tuple(groups)


# -------------------------------------------------------------- block specs
def _mixer_spec(cfg: ModelConfig, kind: LayerKind) -> dict:
    if kind.mixer == "gqa":
        return {"attn": gqa_spec(cfg)}
    if kind.mixer == "mla":
        return {"attn": mla_spec(cfg)}
    if kind.mixer == "ssm":
        return {"ssm": mamba_spec(cfg)}
    if kind.mixer == "hybrid":
        return {
            "attn": gqa_spec(cfg),
            "ssm": mamba_spec(cfg),
            "fuse_attn_norm": rms_norm_spec(cfg.d_model),
            "fuse_ssm_norm": rms_norm_spec(cfg.d_model),
        }
    raise ValueError(kind.mixer)


def block_spec(cfg: ModelConfig, kind: LayerKind) -> dict:
    spec = {"norm1": rms_norm_spec(cfg.d_model), **_mixer_spec(cfg, kind)}
    if kind.mixer != "ssm":
        spec["norm2"] = rms_norm_spec(cfg.d_model)
        spec["ffn"] = moe_spec(cfg) if kind.moe else mlp_spec(cfg.d_model, cfg.d_ff, cfg.mlp_type)
    return spec


def _stack_spec(spec, repeats: int):
    """Prepend a ``layers`` axis of size ``repeats`` to every ParamSpec."""
    return jax.tree.map(
        lambda s: ParamSpec((repeats,) + s.shape, ("layers",) + s.axes, init=s.init, scale=s.scale),
        spec,
        is_leaf=lambda x: isinstance(x, ParamSpec),
    )


def model_spec(cfg: ModelConfig) -> dict:
    spec: dict = {}
    if cfg.frontend == "audio":
        spec["frontend_proj"] = linear_spec(512, cfg.d_model, (None, "embed"), bias=True)
    else:
        spec["embed"] = embed_spec(cfg.vocab_size, cfg.d_model)
    spec["groups"] = tuple(
        _stack_spec({f"k{j}": block_spec(cfg, kind) for j, kind in enumerate(g.kinds)}, g.repeats)
        for g in plan_groups(cfg)
    )
    spec["final_norm"] = rms_norm_spec(cfg.d_model)
    if not cfg.tie_embeddings:
        spec["head"] = linear_spec(cfg.d_model, cfg.vocab_size, ("embed", "vocab"))
    return spec


def backend_from(rc: RunConfig):
    """The RunConfig's QuantPolicy as a per-GEMM resolution table.

    Every ``dense(...)`` call site hands this object down and qlinear
    resolves it per GEMM *name* at trace time (memoized dict lookup — the
    compiled program carries only already-specialized backends, zero
    pattern matching on the hot path)."""
    from ..quant.policy import effective_policy

    return effective_policy(rc).resolved()


# -------------------------------------------------------------------- cache
def _block_cache(
    cfg: ModelConfig,
    kind: LayerKind,
    batch: int,
    capacity: int,
    kv_dtype,
    *,
    paged_pool: tuple[int, int] | None = None,   # (num_pages, block_size)
) -> dict:
    cache: dict = {}
    if kind.mixer in ("gqa", "mla", "hybrid"):
        if paged_pool is not None:
            # the paged KV pool: batch -> pages (+1 trash page for dropped
            # writes), capacity -> block_size, in the layout the paged
            # kernel reads; one block table addresses every layer
            pages, bs = paged_pool
            cache.update(init_kv_cache(cfg, pages + 1, bs, kv_dtype, paged=True))
        else:
            cache.update(init_kv_cache(cfg, batch, capacity, kv_dtype))
    if kind.mixer in ("ssm", "hybrid"):
        cache.update(init_ssm_state(cfg, batch))
    return cache


def init_caches(
    cfg: ModelConfig,
    rc: RunConfig,
    batch: int,
    capacity: int,
    *,
    num_pages: int | None = None,
):
    """Stacked per-group cache trees.

    ``rc.kv_layout="dense"``: KV leaves are (layers, batch, capacity, ...).
    ``rc.kv_layout="paged"``: KV leaves become page pools
    (layers, num_pages+1, block_size, features) shared by all slots — GQA
    k/v hold kv*hd head-major features, int8 scales one — and indexed
    through a block table (models.attention.KVView); the trailing trash page
    swallows masked writes. SSM state stays dense per slot (no seq axis).
    ``num_pages`` defaults to the dense equivalent batch*ceil(cap/bs)."""
    kv_dtype = jnp.int8 if rc.kv_cache_dtype == "int8" else jnp.dtype(rc.dtype)
    paged_pool = None
    if rc.kv_layout == "paged":
        bs = rc.block_size
        pages = num_pages if num_pages is not None else batch * (-(-capacity // bs))
        paged_pool = (pages, bs)
    out = []
    for g in plan_groups(cfg):
        blocks = {
            f"k{j}": _block_cache(
                cfg, kind, batch, capacity, kv_dtype, paged_pool=paged_pool
            )
            for j, kind in enumerate(g.kinds)
        }
        out.append(
            jax.tree.map(lambda x: jnp.broadcast_to(x, (g.repeats,) + x.shape).copy(), blocks)
        )
    return tuple(out)


# ------------------------------------------------------------------- blocks
def _apply_block(
    cfg: ModelConfig,
    kind: LayerKind,
    p: dict,
    x: jnp.ndarray,
    positions,
    *,
    backend: GemmBackend,
    cache: dict | None,
    layer,
    cache_pos,
    kv_view: KVView | None,
    chunk: int,
    want_state: bool,
):
    """One block. ``cache`` is the block's layer-stacked cache and ``layer``
    this block's index into it. Returns (x, new_cache|None, aux,
    stats|None): new_cache is the whole stack, updated at ``layer``; stats
    is the block's drained capture frame ({gemm name: CapturedGemm}) when a
    stats capture is active, so the per-layer tuGEMM cycle counts travel
    through jax.checkpoint / lax.scan as ordinary traced outputs."""
    if stats_capture.capturing():
        with stats_capture.frame() as fr:
            x, new_cache, aux, _ = _apply_block_inner(
                cfg, kind, p, x, positions, backend=backend, cache=cache,
                layer=layer, cache_pos=cache_pos, kv_view=kv_view, chunk=chunk,
                want_state=want_state,
            )
        return x, new_cache, aux, stats_capture.as_tree(fr)
    return _apply_block_inner(
        cfg, kind, p, x, positions, backend=backend, cache=cache,
        layer=layer, cache_pos=cache_pos, kv_view=kv_view, chunk=chunk,
        want_state=want_state,
    )


def _apply_block_inner(
    cfg: ModelConfig,
    kind: LayerKind,
    p: dict,
    x: jnp.ndarray,
    positions,
    *,
    backend: GemmBackend,
    cache: dict | None,
    layer,
    cache_pos,
    kv_view: KVView | None,
    chunk: int,
    want_state: bool,
):
    aux = jnp.zeros((), jnp.float32)
    h = rms_norm(p["norm1"], x, cfg.rms_eps)
    new_cache: dict = {}

    if kind.mixer in ("gqa", "mla", "hybrid"):
        attn_fn = mla_attention if kind.mixer == "mla" else gqa_attention
        kv_cache = None
        if cache is not None and ("k" in cache or "ckv" in cache):
            kv_cache = {k: v for k, v in cache.items() if k not in ("h", "conv")}
        y_attn, kv_out = attn_fn(
            cfg, p["attn"], h, positions,
            backend=backend, cache=kv_cache, layer=layer, cache_pos=cache_pos,
            kv_view=kv_view, is_global=kind.is_global, chunk=chunk,
        )
        if kv_out is not None:
            new_cache.update(kv_out)

    if kind.mixer == "ssm" or kind.mixer == "hybrid":
        if cache is not None and "h" in cache:
            ssm_state = {"h": cache["h"][layer], "conv": cache["conv"][layer]}
            if x.shape[1] == 1:
                y_ssm, st = mamba_decode_step(cfg, p["ssm"], h, ssm_state, backend=backend)
            else:
                y_ssm, st = mamba_mixer(cfg, p["ssm"], h, backend=backend, return_state=True)
            new_cache.update({n: cache[n].at[layer].set(v) for n, v in st.items()})
        else:
            y_ssm, st = mamba_mixer(
                cfg, p["ssm"], h, backend=backend, return_state=want_state
            )
            if st is not None:
                new_cache.update(st)

    if kind.mixer == "hybrid":
        y = 0.5 * (
            rms_norm(p["fuse_attn_norm"], y_attn, cfg.rms_eps)
            + rms_norm(p["fuse_ssm_norm"], y_ssm, cfg.rms_eps)
        )
    elif kind.mixer == "ssm":
        y = y_ssm
    else:
        y = y_attn
    # pin the branch output to the residual layout *before* the add: under SP
    # this turns the o-proj/down-proj psum into a reduce-scatter instead of a
    # full-sequence all-reduce followed by a slice
    x = x + constrain(y, "batch", "seq", "act_embed")

    if kind.mixer != "ssm":
        h2 = rms_norm(p["norm2"], x, cfg.rms_eps)
        if kind.moe:
            y2, aux = moe_ffn(cfg, p["ffn"], h2, backend=backend)
        else:
            y2 = mlp(p["ffn"], h2, cfg.mlp_type, backend=backend)
        x = x + constrain(y2, "batch", "seq", "act_embed")

    return x, (new_cache or None), aux, None


# ------------------------------------------------------------------ forward
def forward(
    cfg: ModelConfig,
    rc: RunConfig,
    params: dict,
    batch: dict,
    *,
    caches=None,
    cache_pos=None,
    kv_view: KVView | None = None,
):
    """Returns (hidden (B,S,D), new_caches, aux_loss).

    batch: {"tokens": (B,S) int32} or {"embeds": (B,S,F)};
           optional "positions" (B,S) or (3,B,S) for M-RoPE.
    caches: output of init_caches (stacked per group) or None.
    cache_pos: int32 write offset (required with caches) — scalar, or a
           per-row (B,) vector when rows sit at different positions.
    kv_view: per-row block-table addressing for the mixed prefill+decode
           step (models.attention.KVView); None = legacy dense addressing.
    """
    backend = backend_from(rc)
    pol = getattr(backend, "policy", None)
    if pol is not None and pol.rules:
        # trace-time only: a typo'd/shadowed rule raises here instead of
        # silently resolving every GEMM to the default (quant.surgery does
        # the same for the offline paths)
        from ..quant.surgery import validate_runtime_policy

        validate_runtime_policy(cfg, pol, params)
    dtype = jnp.dtype(rc.dtype)
    groups = plan_groups(cfg)

    if "tokens" in batch:
        x = embed_lookup(params["embed"], batch["tokens"], dtype)
    else:
        x = dense(params["frontend_proj"], batch["embeds"].astype(dtype), backend=backend,
                  name="frontend")
    B, S = x.shape[:2]
    if "positions" in batch:
        positions = batch["positions"]
    else:
        base = jnp.asarray(0 if cache_pos is None else cache_pos, jnp.int32)
        if base.ndim == 1:  # per-row offsets (mixed step)
            base = base[:, None]
        positions = base + jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    x = constrain(x, "batch", "seq", "act_embed")

    want_state = caches is not None
    aux_total = jnp.zeros((), jnp.float32)
    new_caches = []
    stats_groups = []  # per-group stats trees, stacked along the layers axis

    def superblock(kinds, x, p, cache, layer):
        # residual stream layout anchor (seq-sharded under SP overrides)
        x = constrain(x, "batch", "seq", "act_embed")
        aux = jnp.zeros((), jnp.float32)
        ncache = {}
        sdict = {}
        view = None if kv_view is None else dataclasses.replace(kv_view, layer=layer)
        for j, kind in enumerate(kinds):
            c_j = cache[f"k{j}"] if cache is not None else None
            x, nc, a, bs = _apply_block(
                cfg, kind, p[f"k{j}"], x, positions,
                backend=backend, cache=c_j, layer=layer, cache_pos=cache_pos,
                kv_view=view, chunk=rc.attn_chunk, want_state=want_state,
            )
            if nc is not None:
                ncache[f"k{j}"] = nc
            if bs is not None:
                sdict[f"k{j}"] = bs
            aux = aux + a
        return x, (ncache or None), aux, (sdict or None)

    for gi, g in enumerate(groups):
        gp = params["groups"][gi]

        def step(carry, xs, _kinds=g.kinds):
            # the group's stacked caches ride in the carry and every layer
            # updates them in place at its index; as scan xs/ys each layer
            # would slice its cache out and write a fresh stack back
            x, aux, c = carry
            p_slice, layer = xs
            fn = lambda x_, p_, c_, l_: superblock(_kinds, x_, p_, c_, l_)
            if rc.remat in ("block", "full"):
                fn = jax.checkpoint(
                    fn,
                    policy=None if rc.remat == "full" else jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
                )
            x, c, a, st = fn(x, p_slice, c, layer)
            return (x, aux + a, c), st

        carry = (x, aux_total, caches[gi] if caches is not None else None)
        if rc.scan_layers and g.repeats > 1:
            layers = jnp.arange(g.repeats, dtype=jnp.int32)
            carry, st = jax.lax.scan(step, carry, (gp, layers))
        else:
            sts = []
            for i in range(g.repeats):
                carry, st_i = step(carry, (jax.tree.map(lambda a, i=i: a[i], gp), i))
                sts.append(st_i)
            st = jax.tree.map(lambda *xs: jnp.stack(xs), *sts) if sts[0] is not None else None
        x, aux_total, gc = carry
        new_caches.append(gc)
        stats_groups.append(st)

    x = rms_norm(params["final_norm"], x, cfg.rms_eps)
    x = constrain(x, "batch", "seq", "act_embed")
    if stats_capture.capturing():
        # stats arrays carry a leading (repeats,) layers axis per group; the
        # frontend/LM-head GEMMs drain from the capture's root frame directly
        stats_capture.deposit("groups", tuple(stats_groups))
    return x, (tuple(new_caches) if caches is not None else None), aux_total


def lm_logits(cfg: ModelConfig, rc: RunConfig, params: dict, h: jnp.ndarray) -> jnp.ndarray:
    """(B, S, D) -> (B, S, V). Sharded on ("batch", None, "act_vocab")."""
    backend = backend_from(rc)
    if cfg.tie_embeddings:
        logits = jnp.einsum(
            "bsd,vd->bsv", h, params["embed"]["embedding"].astype(h.dtype),
            preferred_element_type=jnp.float32,
        ).astype(h.dtype)
    else:
        logits = dense(params["head"], h, backend=backend, name="lm_head")
    return constrain(logits, "batch", None, "act_vocab")
