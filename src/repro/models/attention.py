"""Attention variants: GQA (+qk_norm, RoPE/M-RoPE, SWA) and MLA (DeepSeek-V2).

Decode uses a pre-allocated KV cache in one of two layouts:

- **dense** (legacy): per-slot ``(batch, capacity)`` buffers, scalar write
  position (the whole pool advances in lock step);
- **paged**: a fixed pool of ``block_size``-token pages shared by all slots,
  addressed through per-slot block tables (a :class:`KVView`) — per-row write
  positions/lengths, so one jitted step can mix prefill chunks and decode
  rows (serve/scheduler.py) and cache memory scales with live tokens. A GQA
  pool stores each token's heads head-major on one feature axis,
  ``(pages+1, block_size, kv*hd)``: the layout the paged kernel reads.

The attention functions take a layer group's caches *stacked* along a
leading layers axis, and the index of the layer to use (``KVView.layer``,
or ``layer`` on the legacy path, which has no view): writes scatter the
step's tokens into the stack in place at ``[layer, ...]`` and reads gather
only the pages (or rows) they need, so a step never copies a whole layer of
the paged pool (models/transformer.py carries the stack through the layer
loop).

MLA caches the *compressed* kv latent and decodes in the absorbed form (no
decompression — the production DeepSeek serving path). KV caches optionally
store int8 with per-(token, head) scales (``kv_dtype="int8"``) — the tuGEMM
low-precision thesis applied to cache traffic. int8 reads are length-masked:
positions at or beyond the live length dequantize to exact zeros, so slot
reuse never leaks a previous occupant's stale pages/rows into the view.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..parallel.sharding import ParamSpec, constrain
from ..quant.qlinear import GemmBackend, dense
from .flash import blockwise_attention, paged_decode_attention
from .layers import apply_mrope, apply_rope, linear_spec, rms_norm, rms_norm_spec

__all__ = [
    "gqa_spec",
    "gqa_attention",
    "mla_spec",
    "mla_attention",
    "init_kv_cache",
    "kv_cache_write",
    "kv_cache_read",
    "KVView",
]


# ------------------------------------------------------------------ KV cache
@dataclass
class KVView:
    """Per-row addressing for one mixed prefill+decode step.

    ``pos[b]`` is row b's first write position (tokens already in its
    sequence), ``lens[b]`` how many of the step's S columns are real tokens
    (0 = row idle this tick; its writes are dropped and its outputs unread).
    ``tables[b]`` maps block index -> page id in the pooled cache for the
    paged layout (None = dense per-row addressing). ``layer`` is the index,
    into a layer group's stacked cache buffers, of the layer being run (the
    model's layer loop sets it per layer). ``block_size`` and ``layout``
    are static (trace-time) attributes."""

    pos: jnp.ndarray                  # (B,) int32
    lens: jnp.ndarray                 # (B,) int32
    tables: jnp.ndarray | None = None  # (B, max_blocks) int32 page ids
    block_size: int = 16
    layout: str = "dense"             # dense | paged
    layer: jnp.ndarray | int | None = None  # int32 scalar layer index

    def tree_flatten(self):
        return (self.pos, self.lens, self.tables, self.layer), (self.block_size, self.layout)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children[:3], *aux, children[3])

    @property
    def kv_len(self) -> jnp.ndarray:
        """Per-row live length after this step's writes."""
        return self.pos + self.lens


jax.tree_util.register_pytree_node(
    KVView, KVView.tree_flatten, KVView.tree_unflatten
)


def paged_view_capacity(view: KVView) -> int:
    """Token capacity of the contiguous per-row view a block table spans."""
    return view.tables.shape[1] * view.block_size
def init_kv_cache(
    cfg: ModelConfig, batch: int, capacity: int, dtype, *, paged: bool = False
) -> dict:
    """Per-layer attention cache (unstacked; caller stacks per layer group).

    Dense: (batch, capacity, kv, hd) k/v. ``paged`` (batch = pages+1,
    capacity = block_size): k/v (pages+1, bs, kv*hd), head-major, the
    layout the paged kernel reads. MLA latents are (rows, tokens, f) and
    int8 token scales (rows, tokens) either way."""
    hd = cfg.resolved_head_dim
    if cfg.attn_type == "mla":
        cache = {
            "ckv": jnp.zeros((batch, capacity, cfg.kv_lora_rank), dtype),
            "kr": jnp.zeros((batch, capacity, cfg.qk_rope_head_dim), dtype),
        }
    else:
        kv = cfg.num_kv_heads
        feat = (kv * hd,) if paged else (kv, hd)
        cache = {
            "k": jnp.zeros((batch, capacity) + feat, dtype),
            "v": jnp.zeros((batch, capacity) + feat, dtype),
        }
    if dtype == jnp.int8:
        for n in list(cache):
            cache[n + "_scale"] = jnp.zeros((batch, capacity), jnp.float32)
    return cache


def _quantize_kv(x: jnp.ndarray, sync=None) -> tuple[jnp.ndarray, jnp.ndarray]:
    # per-(batch, position) scale over heads*dim; ``sync`` (mesh serving)
    # max-merges the raw amax across tensor-parallel head shards *before*
    # the scale transform, so the synced scale is bit-identical to the
    # single-device all-heads reduction (keep the division form below — it
    # is the form the single-device cache writes compile to)
    amax = jnp.abs(x.astype(jnp.float32)).max(axis=tuple(range(2, x.ndim)))
    if sync is not None:
        amax = sync(amax)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.round(x.astype(jnp.float32) / scale.reshape(scale.shape + (1,) * (x.ndim - 2)))
    return jnp.clip(q, -128, 127).astype(jnp.int8), scale


def _scatter_targets(view: KVView, B: int, S: int, capacity: int):
    """Per-token write coordinates for a :class:`KVView` step.

    Returns (rows, tp) index arrays of shape (B, S): dense rows/positions,
    with every padded column (col >= lens[row]) redirected out of bounds so
    ``.at[...].set(mode="drop")`` discards it."""
    cols = jnp.arange(S, dtype=jnp.int32)
    tp = view.pos[:, None] + cols[None, :]                     # (B, S)
    live = cols[None, :] < view.lens[:, None]
    tp = jnp.where(live, tp, capacity)                         # OOB -> dropped
    rows = jnp.broadcast_to(jnp.arange(B, dtype=jnp.int32)[:, None], (B, S))
    return rows, tp


def _paged_targets(view: KVView, B: int, S: int, num_rows: int):
    """(page, offset) per token for the paged pool; padded columns land on
    the trash page (the pool's last row, never read)."""
    bs = view.block_size
    cols = jnp.arange(S, dtype=jnp.int32)
    tp = view.pos[:, None] + cols[None, :]                     # (B, S)
    live = cols[None, :] < view.lens[:, None]
    max_blocks = view.tables.shape[1]
    blk = jnp.clip(tp // bs, 0, max_blocks - 1)
    rows = jnp.broadcast_to(jnp.arange(B, dtype=jnp.int32)[:, None], (B, S))
    page = view.tables[rows, blk]                              # (B, S)
    trash = num_rows - 1
    page = jnp.where(live & (tp < max_blocks * bs), page, trash)
    return page, tp % bs


def _write_one(
    cache: dict, out: dict, name: str, val, pos, view: KVView | None, layer
):
    """Write ``val`` (B, S, ...) into one layer of one stacked cache buffer
    (plus its scale), in place: ``view.layer``, or ``layer`` with no view."""
    from ..parallel import collectives as dist  # trace-time mesh program

    prog = dist.current_program()
    sync = None
    if prog is not None and name in prog.kv_sync_names:
        sync = lambda a: prog.sync_amax_tp(a, f"kv.{name}")  # noqa: E731
    buf = cache[name]
    if buf.dtype == jnp.int8:
        q, s = _quantize_kv(val, sync)
        vals = [(name, q), (name + "_scale", s.astype(jnp.float32))]
    else:
        vals = [(name, val.astype(buf.dtype))]
    if (
        prog is not None
        and prog.write_view is not None
        and view is not None
        and view.tables is not None
    ):
        # paged pool is replicated across dp (pages are shared by all rows),
        # so every device must write every row's tokens: gather the dp-local
        # rows — already quantized, so int8 planes on the wire — and address
        # through the full-batch write view
        vals = [(n, prog.gather_rows_dp(v, f"kv.{n}")) for n, v in vals]
        view = dataclasses.replace(prog.write_view, layer=view.layer)
    B, S = vals[0][1].shape[:2]
    if view is not None:
        layer = view.layer
    for n, v in vals:
        dst = cache[n]                                     # (L, ...)
        if view is None:
            start = (layer, 0, pos) + (0,) * (dst.ndim - 3)
            out[n] = jax.lax.dynamic_update_slice(dst, v[None], start)
        elif view.tables is None:  # dense layout, per-row positions
            rows, tp = _scatter_targets(view, B, S, dst.shape[2])
            out[n] = dst.at[layer, rows, tp].set(v, mode="drop")
        else:                      # paged pool: (L, pages+1, block_size[, F])
            page, off = _paged_targets(view, B, S, dst.shape[1])
            v = v.reshape((B, S) + dst.shape[3:])          # heads -> features
            out[n] = dst.at[layer, page, off].set(v, mode="drop")
    return out


def kv_cache_write(
    cache: dict, names: tuple[str, str], new: tuple, pos, *,
    view: KVView | None = None, layer=None,
) -> dict:
    """Write a (B, S, ...) span of k/v tokens into one layer of the stacked
    cache buffers: ``view.layer``, or ``layer`` on the legacy path.

    Legacy path (``view=None``): all rows share the scalar write position
    ``pos`` (dynamic_update_slice over a static-capacity buffer). With a
    :class:`KVView`, each row writes ``lens[b]`` tokens at its own
    ``pos[b]`` — scattered into the dense buffer or through the block table
    into the page pool; padded columns are dropped."""
    out = dict(cache)
    for name, val in zip(names, new):
        out = _write_one(cache, out, name, val, pos, view, layer)
    return out


def _mask_dead(x: jnp.ndarray, kv_len) -> jnp.ndarray:
    """Zero every position at or beyond the live length (scalar or (B,))."""
    if kv_len is None:
        return x
    kv_len = jnp.asarray(kv_len, jnp.int32)
    pos = jnp.arange(x.shape[1], dtype=jnp.int32)
    live = pos[None, :] < (kv_len[:, None] if kv_len.ndim == 1 else kv_len)
    return jnp.where(live.reshape(live.shape + (1,) * (x.ndim - 2)), x, 0)


def kv_cache_read(
    cache: dict,
    name: str,
    compute_dtype,
    *,
    kv_len=None,
    view: KVView | None = None,
    layer=None,
) -> jnp.ndarray:
    """Materialize one layer of one stacked cache buffer (``view.layer``, or
    ``layer`` with no view) as a contiguous (B, capacity, ...) view.

    ``kv_len`` (scalar or per-row (B,)) length-masks the result: dead
    positions come back as exact zeros, so the int8 dequant never exposes a
    previous occupant's stale rows/pages and a fresh page needs no zeroing.
    With a paged :class:`KVView`, that layer's pages are gathered through
    the block table into a contiguous view of ``max_blocks * block_size``
    tokens per row, (B, capacity, F) with F the pool's feature axis."""
    if view is not None:
        layer = view.layer
    if view is not None and view.tables is not None:
        pool = cache[name]                                  # (L, P+1, bs, F)
        B = view.tables.shape[0]
        cap = paged_view_capacity(view)
        buf = pool[layer, view.tables].reshape((B, cap) + pool.shape[3:])
        if pool.dtype == jnp.int8:
            s = cache[name + "_scale"][layer, view.tables].reshape(B, cap)
            deq = buf.astype(jnp.float32) * s.reshape(s.shape + (1,) * (buf.ndim - 2))
            return _mask_dead(deq, kv_len).astype(compute_dtype)
        return _mask_dead(buf, kv_len).astype(compute_dtype)
    buf = cache[name][layer]
    if buf.dtype == jnp.int8:
        s = cache[name + "_scale"][layer]
        deq = buf.astype(jnp.float32) * s.reshape(s.shape + (1,) * (buf.ndim - 2))
        return _mask_dead(deq, kv_len).astype(compute_dtype)
    return _mask_dead(buf, kv_len).astype(compute_dtype)


# ----------------------------------------------------------------------- GQA
def gqa_spec(cfg: ModelConfig) -> dict:
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    spec = {
        "wq": linear_spec(d, h * hd, ("embed", "heads")),
        "wk": linear_spec(d, kv * hd, ("embed", "kv_heads")),
        "wv": linear_spec(d, kv * hd, ("embed", "kv_heads")),
        "wo": linear_spec(h * hd, d, ("heads", "embed")),
    }
    if cfg.qk_norm:
        spec["q_norm"] = rms_norm_spec(hd)
        spec["k_norm"] = rms_norm_spec(hd)
    return spec


def gqa_attention(
    cfg: ModelConfig,
    p: dict,
    x: jnp.ndarray,                 # (B, S, D)
    positions: jnp.ndarray,         # (B, S) or (3, B, S) for M-RoPE
    *,
    backend: GemmBackend,
    cache: dict | None = None,      # the layer group's stacked k/v buffers
    layer=None,                     # index into ``cache`` with no kv_view
    cache_pos=None,                 # scalar write position (decode)
    kv_view: KVView | None = None,  # per-row addressing (mixed steps / paged)
    is_global: bool = True,         # False -> sliding window
    chunk: int = 1024,
) -> tuple[jnp.ndarray, dict | None]:
    B, S, _ = x.shape
    h, kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim

    q = dense(p["wq"], x, backend=backend, name="attn.q").reshape(B, S, h, hd)
    k = dense(p["wk"], x, backend=backend, name="attn.k").reshape(B, S, kv, hd)
    v = dense(p["wv"], x, backend=backend, name="attn.v").reshape(B, S, kv, hd)
    if cfg.qk_norm:
        q = rms_norm(p["q_norm"], q, cfg.rms_eps)
        k = rms_norm(p["k_norm"], k, cfg.rms_eps)
    if cfg.mrope_sections is not None:
        q = apply_mrope(q, positions, cfg.rope_theta, cfg.mrope_sections)
        k = apply_mrope(k, positions, cfg.rope_theta, cfg.mrope_sections)
    elif cfg.attn_type != "none":
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    # "seq" first: under sequence-parallel overrides the duplicate-mesh-axis
    # guard then drops act_heads, giving seq-sharded attention (for GQA the
    # gathered K/V are only (2·kv/H)·D bytes — cheaper than gathering x);
    # without SP, act_heads shards on model when the head count divides.
    q = constrain(q, "batch", "seq", "act_heads", None)

    window = None if is_global else cfg.sliding_window
    if cache is not None:
        out = None
        if kv_view is not None:
            cache = kv_cache_write(cache, ("k", "v"), (k, v), None, view=kv_view)
            kv_len = kv_view.kv_len                            # (B,)
            q_offset = kv_view.pos                             # (B,)
            if kv_view.tables is not None:
                # fused paged kernel: pages stream HBM->VMEM once, dequant
                # in the inner loop — no pool[tables] gather materialized
                out = paged_decode_attention(
                    q, cache, ("k",), "v", kv_view,
                    kv_heads=kv, causal=cfg.causal, window=window,
                    name="attn.paged",
                )
            if out is None:
                k_full = kv_cache_read(
                    cache, "k", x.dtype, kv_len=kv_len, view=kv_view)
                v_full = kv_cache_read(
                    cache, "v", x.dtype, kv_len=kv_len, view=kv_view)
        else:
            cache = kv_cache_write(cache, ("k", "v"), (k, v), cache_pos, layer=layer)
            capacity = cache["k"].shape[2]
            kv_len = jnp.minimum(jnp.asarray(cache_pos, jnp.int32) + S, capacity)
            k_full = kv_cache_read(cache, "k", x.dtype, layer=layer, kv_len=kv_len)
            v_full = kv_cache_read(cache, "v", x.dtype, layer=layer, kv_len=kv_len)
            q_offset = cache_pos
        if out is None:
            # a paged read is (B, cap, kv*hd); this shard's heads back apart
            k_full = k_full.reshape(k_full.shape[:2] + k.shape[2:])
            v_full = v_full.reshape(v_full.shape[:2] + v.shape[2:])
            out = blockwise_attention(
                q,
                k_full,
                v_full,
                q_offset=q_offset,
                kv_len=kv_len,
                causal=cfg.causal,
                window=window,
                chunk=chunk,
            )
    else:
        out = blockwise_attention(
            q, k, v, causal=cfg.causal, window=window, chunk=chunk,
            softcap=cfg.attn_logit_softcap,
        )
    out = constrain(out, "batch", "seq", "act_heads", None)
    y = dense(p["wo"], out.reshape(B, S, h * hd), backend=backend, name="attn.o")
    return y, cache


# ----------------------------------------------------------------------- MLA
def mla_spec(cfg: ModelConfig) -> dict:
    d, h = cfg.d_model, cfg.num_heads
    nope, rope_d = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    vd, lora = cfg.v_head_dim, cfg.kv_lora_rank
    return {
        "wq": linear_spec(d, h * (nope + rope_d), ("embed", "heads")),
        "w_dkv": linear_spec(d, lora + rope_d, ("embed", "kv_lora")),
        "kv_norm": rms_norm_spec(lora),
        "w_uk": {"kernel": ParamSpec((lora, h, nope), ("kv_lora", "heads", "qk_dim"))},
        "w_uv": {"kernel": ParamSpec((lora, h, vd), ("kv_lora", "heads", "qk_dim"))},
        "wo": linear_spec(h * vd, d, ("heads", "embed")),
    }


def mla_attention(
    cfg: ModelConfig,
    p: dict,
    x: jnp.ndarray,
    positions: jnp.ndarray,
    *,
    backend: GemmBackend,
    cache: dict | None = None,
    layer=None,
    cache_pos=None,
    kv_view: KVView | None = None,
    chunk: int = 1024,
    **_unused,
) -> tuple[jnp.ndarray, dict | None]:
    B, S, _ = x.shape
    h = cfg.num_heads
    nope, rope_d = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    vd, lora = cfg.v_head_dim, cfg.kv_lora_rank
    scale_dim = nope + rope_d

    q = dense(p["wq"], x, backend=backend, name="mla.q").reshape(B, S, h, scale_dim)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    dkv = dense(p["w_dkv"], x, backend=backend, name="mla.dkv")
    ckv, k_rope = dkv[..., :lora], dkv[..., lora:]
    ckv = rms_norm(p["kv_norm"], ckv, cfg.rms_eps)
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    k_rope = apply_rope(k_rope[:, :, None, :], positions, cfg.rope_theta)[:, :, 0, :]

    # absorbed form: q_abs[b,s,h,:] = q_nope · W_uk[:,h,:]^T  (lives in latent space)
    q_abs = jnp.einsum("bshn,lhn->bshl", q_nope.astype(jnp.float32),
                       p["w_uk"]["kernel"].astype(jnp.float32)).astype(x.dtype)
    q_eff = jnp.concatenate([q_abs, q_rope], axis=-1)          # (B,S,h,lora+rope)

    # score scale must be 1/sqrt(nope+rope), not 1/sqrt(lora+rope):
    # blockwise_attention scales by k dim; compensate.
    comp = ((lora + rope_d) ** 0.5) / (scale_dim ** 0.5)

    if cache is not None and kv_view is not None:
        cache = kv_cache_write(cache, ("ckv", "kr"), (ckv, k_rope), None, view=kv_view)
        kv_len = kv_view.kv_len
        q_offset = kv_view.pos
        if kv_view.tables is not None:
            # fused paged kernel: K = [ckv ; kr] concatenated per page
            # in-register, V = the ckv pool — no gathered latent tensor
            ctx = paged_decode_attention(
                q_eff * comp, cache, ("ckv", "kr"), "ckv", kv_view,
                kv_heads=1, causal=cfg.causal, name="mla.paged",
            )
            if ctx is not None:
                out = jnp.einsum("bshl,lhv->bshv", ctx.astype(jnp.float32),
                                 p["w_uv"]["kernel"].astype(jnp.float32)).astype(x.dtype)
                y = dense(p["wo"], out.reshape(B, S, h * vd), backend=backend,
                          name="mla.o")
                return y, cache
        ckv_full = kv_cache_read(cache, "ckv", x.dtype, kv_len=kv_len, view=kv_view)
        kr_full = kv_cache_read(cache, "kr", x.dtype, kv_len=kv_len, view=kv_view)
    elif cache is not None:
        cache = kv_cache_write(
            cache, ("ckv", "kr"), (ckv, k_rope), cache_pos, layer=layer
        )
        kv_len = jnp.minimum(
            jnp.asarray(cache_pos, jnp.int32) + S, cache["ckv"].shape[2]
        )
        ckv_full = kv_cache_read(cache, "ckv", x.dtype, layer=layer, kv_len=kv_len)
        kr_full = kv_cache_read(cache, "kr", x.dtype, layer=layer, kv_len=kv_len)
        q_offset = cache_pos
    else:
        ckv_full, kr_full, kv_len, q_offset = ckv, k_rope, None, 0

    # MQA in latent space: K = [ckv ; k_rope] (single head), V = ckv
    k_eff = jnp.concatenate([ckv_full, kr_full], axis=-1)[:, :, None, :]
    v_eff = ckv_full[:, :, None, :]
    ctx = blockwise_attention(
        q_eff * comp, k_eff, v_eff,
        q_offset=q_offset, kv_len=kv_len, causal=cfg.causal, chunk=chunk,
    )                                                          # (B,S,h,lora)
    out = jnp.einsum("bshl,lhv->bshv", ctx.astype(jnp.float32),
                     p["w_uv"]["kernel"].astype(jnp.float32)).astype(x.dtype)
    y = dense(p["wo"], out.reshape(B, S, h * vd), backend=backend, name="mla.o")
    return y, cache
