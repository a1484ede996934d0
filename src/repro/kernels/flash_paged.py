"""Pallas TPU kernel: paged flash-decode attention (DESIGN.md §13).

The serving scheduler keeps the KV cache as a pooled set of
``block_size``-token pages addressed through per-slot block tables
(models/attention.py ``KVView``). The XLA read path materializes a gathered
contiguous view first — ``pool[tables]`` — which costs a full extra
HBM round-trip over the cache *and* stages a dequantized bf16/f32 copy of
the int8 pool before a single score is computed. This kernel fuses the whole
decode read side instead:

* grid = (batch, max_blocks) — split-K over the per-row block table. The
  kernel takes the whole layer-stacked pool ``(layers, pages+1, bs, F)``
  and a layer index; the block for grid step (b, m) is
  ``(layer, tables[b, m])``, wired through a scalar-prefetch index map
  (``pltpu.PrefetchScalarGridSpec``), so each page streams HBM→VMEM exactly
  once, and neither the gathered intermediate nor a slice of one layer's
  pool ever exists.
* the grid keeps its ``max_blocks`` page steps per row, but only the pages
  below a row's ``kv_len`` are live: a dead step (a page at or after
  ``kv_len``, every step of an idle row) skips the body under ``pl.when``,
  and its index maps hold the row's last live page, so it neither fetches
  nor computes. The kernel's work follows live context, not capacity; what
  a dead step still costs is the grid step itself.
* int8 KV dequant happens in-register per page (``int8 * scale[token]``,
  the same float ops as the XLA twin's pool dequant), fused into the
  attention inner loop.
* online softmax (running max / sum / weighted accumulator in VMEM scratch,
  the FlashAttention recurrence) across the page axis; per-row
  ``q_offset``/``kv_len`` masking with ``models/flash.py`` semantics
  (valid-length, causal, sliding window; masked probabilities forced to
  exact zeros so idle rows and stale pages contribute nothing).

Operand model (covers both attention families):

* GQA: one K part ``(layers, pages+1, bs, kv*hd)`` and V of the same
  shape, head-major as the cache stores them; query heads are kv-major
  (head h reads kv head h // n_rep), so the per-kv-head feature slices line
  up with contiguous query-row blocks.
* MLA (absorbed decode): two K parts — the compressed latent
  ``(layers, pages+1, bs, lora)`` and the rope keys
  ``(layers, pages+1, bs, rope_d)`` —
  concatenated per page in-register (dot over a concat == sum of dots, but
  concatenating first keeps the float accumulation order identical to the
  XLA twin's ``concat([ckv, kr])``); V is the latent part.

Numerics: the online-softmax recurrence is the mathematically exact
rescaled form, so outputs match the twin to float-accumulation order;
greedy-decode token streams are bit-identical (tests/test_flash_paged.py).
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "flash_paged_decode",
    "paged_impl",
    "set_paged_impl",
]

NEG_INF = -1e30  # models/flash.py's mask value (finite: exp() underflows to 0)
SCALE_ROWS = 8   # pages of int8 scales per block: one f32 sublane tile

# ------------------------------------------------------------ impl selection
# Mirrors kernels/ops.py ``_resolve`` but module-scoped: the paged decode
# path is selected at *trace* time inside the scheduler's jitted mixed step,
# where there is no per-call impl kwarg to thread. Default "auto" = compiled
# Pallas on TPU, the (gather-read) XLA twin elsewhere. Tests pin
# "pallas_interpret"; the env knob lets a deployment force either side.

_impl_override: str | None = None


def set_paged_impl(impl: str | None) -> None:
    """Force the paged-attention path: auto|pallas|pallas_interpret|xla|None."""
    global _impl_override
    if impl is not None and impl not in ("auto", "pallas", "pallas_interpret", "xla"):
        raise ValueError(f"unknown paged impl {impl!r}")
    _impl_override = impl


def paged_impl() -> tuple[str, bool]:
    """Returns (path, interpret) with path in {pallas, xla}."""
    impl = _impl_override or os.environ.get("REPRO_PAGED_ATTN", "auto")
    if impl == "auto":
        return ("pallas", False) if jax.default_backend() == "tpu" else ("xla", False)
    if impl == "pallas":
        return "pallas", False
    if impl == "pallas_interpret":
        return "pallas", True
    return "xla", False


def _deq(ref, scale_ref, row):
    """One page (1, 1, bs, F) in storage dtype → (bs, F) f32, dequantized.

    Same float op as the XLA twin's pool read: ``int8 → f32 * scale[token]``
    with the per-token scale — row ``row`` of the (1, SCALE_ROWS, bs) block
    of page scales, turned into a (bs, 1) column that broadcasts over every
    feature lane — applied to every feature."""
    page = ref[0, 0]
    if page.dtype == jnp.int8:
        scale = scale_ref[0, pl.ds(row, 1), :]               # (1, bs)
        return page.astype(jnp.float32) * jnp.transpose(scale)
    return page.astype(jnp.float32)


def _kernel(
    # scalar prefetch
    tables_ref, pos_ref, len_ref, _layer_ref,
    # tensor operands: q, then per K part (pool [+ scale]), then v [+ scale]
    *refs,
    n_pages, bs, kv, group, sq, part_dims, hdv, scale_rows,
    causal, window, k_int8, v_int8,
):
    it = iter(refs)
    q_ref = next(it)
    k_refs, ks_refs = [], []
    for _ in part_dims:
        k_refs.append(next(it))
        ks_refs.append(next(it) if k_int8 else None)
    v_ref = next(it)
    vs_ref = next(it) if v_int8 else None
    o_ref = next(it)
    m_scr, l_scr, acc_scr = next(it), next(it), next(it)

    b, m = pl.program_id(0), pl.program_id(1)
    hq = kv * group * sq  # query rows, laid out (kv, n_rep, sq)

    @pl.when(m == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # a page at or after the row's live length is masked for every query
    # row: it would leave m, l and acc exactly as they are (alpha = 1,
    # p = 0), so the step skips the body, and its index maps repeat the
    # last live page's block, so no page is fetched for it either
    @pl.when(m * bs < len_ref[b])
    def _attend():
        # visibility mask for this page, models/flash.py semantics: row r of
        # the (kv, n_rep, sq) query layout sits at absolute position
        # pos[b] + (r % sq)
        sq_idx = jax.lax.broadcasted_iota(jnp.int32, (hq, bs), 0) % sq
        k_pos = m * bs + jax.lax.broadcasted_iota(jnp.int32, (hq, bs), 1)
        q_pos = pos_ref[b] + sq_idx
        mask = k_pos < len_ref[b]
        if causal:
            mask = mask & (k_pos <= q_pos)
        if window is not None:
            mask = mask & (q_pos - k_pos < window)

        # dequantized page: K parts concatenated on features (MLA
        # [ckv ; kr]), V taken whole — each laid out (bs, kv * per-head
        # features); ``row`` is this page's row of its scale block
        row = tables_ref[b, m] % scale_rows
        parts = [_deq(r, s, row) for r, s in zip(k_refs, ks_refs)]
        v_page = _deq(v_ref, vs_ref, row)

        # scores per kv head: q rows [g*group*sq, (g+1)*group*sq) dot that
        # head's feature slice of every part
        s_rows = []
        for g in range(kv):
            qg = q_ref[0, g * group * sq : (g + 1) * group * sq, :]
            kg = jnp.concatenate(
                [p[:, g * f : (g + 1) * f] for p, f in zip(parts, part_dims)],
                axis=-1,
            ) if len(parts) > 1 else (
                parts[0][:, g * part_dims[0] : (g + 1) * part_dims[0]])
            s_rows.append(
                jax.lax.dot_general(
                    qg, kg, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
            )
        s = jnp.concatenate(s_rows, axis=0) if kv > 1 else s_rows[0]  # (hq, bs)
        s = jnp.where(mask, s, NEG_INF)

        # online softmax update (FlashAttention recurrence); masked positions
        # get probability exactly 0 so stale page contents never leak into acc
        m_new = jnp.maximum(m_scr[...], s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_scr[...] - m_new)
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        l_new = l_scr[...] * alpha + p.sum(axis=1, keepdims=True)

        pv_rows = []
        for g in range(kv):
            pg = p[g * group * sq : (g + 1) * group * sq, :]
            vg = v_page[:, g * hdv : (g + 1) * hdv]
            pv_rows.append(
                jax.lax.dot_general(
                    pg, vg, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
            )
        pv = jnp.concatenate(pv_rows, axis=0) if kv > 1 else pv_rows[0]  # (hq, hdv)
        acc_scr[...] = acc_scr[...] * alpha + pv
        m_scr[...] = m_new
        l_scr[...] = l_new

    @pl.when(m == n_pages - 1)
    def _flush():
        # same guard as _fwd_scan: fully-masked rows (idle slots) emit 0
        out = acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = out.astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("kv_heads", "causal", "window", "interpret"),
)
def flash_paged_decode(
    q: jnp.ndarray,                    # (B, Sq, H, hd_tot) — Sq = step width
    k_parts: tuple,                    # pools (L, P+1, bs, kv*f_i) — concat = K
    k_scales: tuple,                   # per part: (L, P+1, bs) f32 or None
    v_pool: jnp.ndarray,               # (L, P+1, bs, kv*hdv)
    v_scale: jnp.ndarray | None,       # (L, P+1, bs) f32 or None
    tables: jnp.ndarray,               # (B, MB) int32 page ids
    pos: jnp.ndarray,                  # (B,) int32 — absolute position of q[:, 0]
    kv_len: jnp.ndarray,               # (B,) int32 — valid tokens per row
    layer,                             # int32 scalar — the pools' layer to read
    *,
    kv_heads: int,
    causal: bool = True,
    window: int | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Paged flash-decode attention; returns (B, Sq, H, hdv) in q.dtype.

    ``hd_tot = sum(f_i)`` must equal the per-head feature width of the
    concatenated K parts; query heads are kv-major (h // n_rep selects the
    kv head, matching models/flash.py ``_repeat_kv``). Scores are scaled by
    ``1 / sqrt(hd_tot)`` exactly like ``blockwise_attention``. int8 pools
    carry a per-(page, token) f32 scale; float pools pass scale=None. Every
    pool is the whole layer stack; only layer ``layer``'s pages are read."""
    B, sq, H, hd_tot = q.shape
    kv = kv_heads
    group = H // kv
    bs = v_pool.shape[2]
    n_pages = tables.shape[1]
    part_dims = tuple(p.shape[3] // kv for p in k_parts)
    hdv = v_pool.shape[3] // kv
    assert sum(part_dims) == hd_tot, (part_dims, hd_tot)
    assert H == kv * group, (q.shape, kv)
    hq = kv * group * sq

    # (B, Sq, H, hd) → (B, kv, n_rep, Sq, hd) → (B, hq, hd), pre-scaled f32
    # (the same ``q * 1/sqrt(d)`` op _fwd_scan/_decode_direct apply)
    qf = q.astype(jnp.float32) * (1.0 / (hd_tot ** 0.5))
    qf = qf.transpose(0, 2, 1, 3).reshape(B, kv, group, sq, hd_tot)
    qf = qf.reshape(B, hq, hd_tot)

    k_int8 = k_parts[0].dtype == jnp.int8
    v_int8 = v_pool.dtype == jnp.int8

    def live_page(b, m, tbl, ln):
        """Row b's page for grid step m, held at its last live page (page 0
        for an idle row) once m passes it: a dead step repeats the block
        index of the step before it, so the pipeline fetches nothing."""
        last = jnp.maximum((ln[b] + bs - 1) // bs - 1, 0)
        return tbl[b, jnp.minimum(m, last)]

    def page_map(b, m, tbl, _pos, ln, lyr):
        return (lyr[0], live_page(b, m, tbl, ln), 0, 0)

    # int8 page scales (L, P+1, bs) are read SCALE_ROWS pages at a time: a
    # (1, 1, bs) block is refused by Mosaic (its second-minor dim is neither
    # a multiple of 8 nor the whole axis), and the scales are stored in the
    # layout the cache writes them in, so no relayout of the plane is made
    scale_rows = min(SCALE_ROWS, v_pool.shape[1])

    def scale_map(b, m, tbl, _pos, ln, lyr):
        return (lyr[0], live_page(b, m, tbl, ln) // scale_rows, 0)

    in_specs = [pl.BlockSpec((1, hq, hd_tot), lambda b, m, *_: (b, 0, 0))]
    operands: list = [qf]
    for part, scale in zip(k_parts, k_scales):
        in_specs.append(pl.BlockSpec((1, 1, bs, part.shape[3]), page_map))
        operands.append(part)
        if k_int8:
            in_specs.append(pl.BlockSpec((1, scale_rows, bs), scale_map))
            operands.append(scale)
    in_specs.append(pl.BlockSpec((1, 1, bs, v_pool.shape[3]), page_map))
    operands.append(v_pool)
    if v_int8:
        in_specs.append(pl.BlockSpec((1, scale_rows, bs), scale_map))
        operands.append(v_scale)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B, n_pages),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, hq, hdv), lambda b, m, *_: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((hq, 1), jnp.float32),      # running max
            pltpu.VMEM((hq, 1), jnp.float32),      # running sum
            pltpu.VMEM((hq, hdv), jnp.float32),    # weighted accumulator
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _kernel,
            n_pages=n_pages, bs=bs, kv=kv, group=group, sq=sq,
            part_dims=part_dims, hdv=hdv, scale_rows=scale_rows,
            causal=causal, window=window,
            k_int8=k_int8, v_int8=v_int8,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, hq, hdv), jnp.float32),
        interpret=interpret,
    )(tables.astype(jnp.int32), pos.astype(jnp.int32), kv_len.astype(jnp.int32),
      jnp.reshape(layer, (1,)).astype(jnp.int32), *operands)
    # (B, hq, hdv) → (B, kv, n_rep, Sq, hdv) → (B, Sq, H, hdv)
    out = out.reshape(B, kv, group, sq, hdv).reshape(B, H, sq, hdv)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)
