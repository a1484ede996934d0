"""Blockwise (flash-style) attention in pure JAX: online softmax over KV
chunks, with a **custom-VJP chunked-recompute backward** for training.

Memory is O(S·chunk) instead of O(S²) in BOTH directions: the forward scans
KV chunks with an online softmax; the backward saves only (q, k, v, out, lse)
and recomputes each chunk's probabilities while accumulating dq and emitting
per-chunk dk/dv — the FlashAttention-2 recipe. Without the custom VJP,
autodiff through the forward scan saves every chunk's (B,H,Sq,C) probability
tensor, which restores the O(S²) footprint the whole design exists to avoid
(measured: ~60 GB/layer-loop of pure p-tensor traffic on the train_4k cells).

One implementation covers training (full seq), prefill, single-token
decode (Sq=1 against a long cache), and the serving scheduler's mixed
prefill+decode step: GQA/MQA by chunk-local KV head repetition,
causal/sliding-window/encoder masking by position arithmetic, valid-length
masking for caches. ``q_offset``/``kv_len`` accept per-row (B,) vectors so
rows of one step may sit at different positions/lengths (chunked prefill
packed with decode rows). The cached-decode path (q_offset/kv_len dynamic)
skips the custom VJP — serving never differentiates.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["blockwise_attention", "paged_decode_attention"]

NEG_INF = -1e30


def _repeat_kv(x: jnp.ndarray, n_rep: int) -> jnp.ndarray:
    """(B, C, KV, hd) -> (B, C, KV*n_rep, hd)."""
    if n_rep == 1:
        return x
    b, c, kv, hd = x.shape
    return jnp.broadcast_to(x[:, :, :, None, :], (b, c, kv, n_rep, hd)).reshape(
        b, c, kv * n_rep, hd
    )


def _chunk_mask(q_pos, k_pos, valid_len, causal, window):
    """Visibility mask over one KV chunk.

    ``q_pos`` is (Sq,) or, for per-row offsets (mixed prefill+decode steps),
    (B, Sq); ``valid_len`` is a scalar or a per-row (B,) vector. Returns
    (Sq, C) in the legacy scalar case, else (B, Sq, C)."""
    q_pos = jnp.asarray(q_pos)
    valid_len = jnp.asarray(valid_len)
    if q_pos.ndim == 1 and valid_len.ndim == 0:
        mask = k_pos[None, :] < valid_len
        if causal:
            mask = mask & (k_pos[None, :] <= q_pos[:, None])
        if window is not None:
            mask = mask & (q_pos[:, None] - k_pos[None, :] < window)
        return mask  # (Sq, C)
    qp = q_pos if q_pos.ndim == 2 else q_pos[None, :]          # (B|1, Sq)
    vl = valid_len if valid_len.ndim == 1 else valid_len[None]  # (B|1,)
    mask = k_pos[None, None, :] < vl[:, None, None]
    if causal:
        mask = mask & (k_pos[None, None, :] <= qp[:, :, None])
    if window is not None:
        mask = mask & (qp[:, :, None] - k_pos[None, None, :] < window)
    return mask  # (B, Sq, C)


def _apply_mask(s, mask):
    """``s`` is (B, H, Sq, C); ``mask`` is (Sq, C) or (B, Sq, C)."""
    m = mask[None, None, :, :] if mask.ndim == 2 else mask[:, None, :, :]
    return jnp.where(m, s, NEG_INF)


def _fwd_scan(q, k, v, q_offset, valid_len, causal, window, chunk, softcap):
    """Returns (out (B,Sq,H,hdv), lse (B,H,Sq))."""
    B, Sq, H, hd = q.shape
    _, Skv, KV, hdv = v.shape
    n_rep = H // KV
    scale = 1.0 / (k.shape[-1] ** 0.5)

    pad = (-Skv) % chunk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    n_chunks = k.shape[1] // chunk

    qf = q.astype(jnp.float32) * scale
    q_off = jnp.asarray(q_offset, jnp.int32)
    q_pos = (q_off[:, None] if q_off.ndim == 1 else q_off) + jnp.arange(
        Sq, dtype=jnp.int32
    )

    m0 = jnp.full((B, H, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, Sq), jnp.float32)
    a0 = jnp.zeros((B, H, Sq, hdv), jnp.float32)

    ks = k.reshape(B, n_chunks, chunk, KV, hd).transpose(1, 0, 2, 3, 4)
    vs = v.reshape(B, n_chunks, chunk, KV, hdv).transpose(1, 0, 2, 3, 4)

    def body(carry, inp):
        m, l, acc = carry
        ci, k_c, v_c = inp
        k_pos = ci * chunk + jnp.arange(chunk, dtype=jnp.int32)
        k_r = _repeat_kv(k_c, n_rep)
        v_r = _repeat_kv(v_c, n_rep)
        s = jnp.einsum("bqhd,bchd->bhqc", qf, k_r.astype(jnp.float32))
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        mask = _chunk_mask(q_pos, k_pos, valid_len, causal, window)
        s = _apply_mask(s, mask)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bhqc,bchd->bhqd", p, v_r.astype(jnp.float32)
        )
        return (m_new, l_new, acc_new), None

    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, a0), (jnp.arange(n_chunks, dtype=jnp.int32), ks, vs)
    )
    out = acc / jnp.maximum(l[..., None], 1e-30)
    lse = m + jnp.log(jnp.maximum(l, 1e-30))
    return out.transpose(0, 2, 1, 3).astype(q.dtype), lse


def _bwd_scan(res, g, causal, window, chunk, softcap):
    """FlashAttention-2 backward: recompute p per chunk; accumulate dq,
    emit per-chunk dk/dv."""
    q, k, v, out, lse = res
    B, Sq, H, hd = q.shape
    _, Skv, KV, hdv = v.shape
    n_rep = H // KV
    scale = 1.0 / (k.shape[-1] ** 0.5)

    pad = (-Skv) % chunk
    kp = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0))) if pad else k
    vp = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0))) if pad else v
    n_chunks = kp.shape[1] // chunk

    qf = q.astype(jnp.float32)
    do = g.astype(jnp.float32).transpose(0, 2, 1, 3)          # (B,H,Sq,hdv)
    of = out.astype(jnp.float32).transpose(0, 2, 1, 3)
    delta = (do * of).sum(-1)                                  # (B,H,Sq)
    q_pos = jnp.arange(Sq, dtype=jnp.int32)
    valid_len = jnp.asarray(Skv, jnp.int32)

    ks = kp.reshape(B, n_chunks, chunk, KV, hd).transpose(1, 0, 2, 3, 4)
    vs = vp.reshape(B, n_chunks, chunk, KV, hdv).transpose(1, 0, 2, 3, 4)

    def body(dq_acc, inp):
        ci, k_c, v_c = inp
        k_pos = ci * chunk + jnp.arange(chunk, dtype=jnp.int32)
        k_r = _repeat_kv(k_c, n_rep).astype(jnp.float32)       # (B,C,H,hd)
        v_r = _repeat_kv(v_c, n_rep).astype(jnp.float32)
        s_raw = jnp.einsum("bqhd,bchd->bhqc", qf * scale, k_r)
        if softcap is not None:
            t = jnp.tanh(s_raw / softcap)
            s_eff = softcap * t
        else:
            s_eff = s_raw
        mask = _chunk_mask(q_pos, k_pos, valid_len, causal, window)
        p = jnp.where(
            mask[None, None, :, :], jnp.exp(s_eff - lse[..., None]), 0.0
        )                                                       # (B,H,Sq,C)
        dp = jnp.einsum("bhqd,bchd->bhqc", do, v_r)
        ds = p * (dp - delta[..., None])
        if softcap is not None:
            ds = ds * (1.0 - t * t)
        dq_acc = dq_acc + jnp.einsum("bhqc,bchd->bqhd", ds, k_r) * scale
        dk_c = jnp.einsum("bhqc,bqhd->bchd", ds, qf) * scale    # (B,C,H,hd)
        dv_c = jnp.einsum("bhqc,bhqd->bchd", p, do)             # (B,C,H,hdv)
        dk_c = dk_c.reshape(B, chunk, KV, n_rep, hd).sum(3)
        dv_c = dv_c.reshape(B, chunk, KV, n_rep, hdv).sum(3)
        return dq_acc, (dk_c, dv_c)

    dq0 = jnp.zeros((B, Sq, H, hd), jnp.float32)
    dq, (dks, dvs) = jax.lax.scan(
        body, dq0, (jnp.arange(n_chunks, dtype=jnp.int32), ks, vs)
    )
    dk = dks.transpose(1, 0, 2, 3, 4).reshape(B, n_chunks * chunk, KV, hd)[:, :Skv]
    dv = dvs.transpose(1, 0, 2, 3, 4).reshape(B, n_chunks * chunk, KV, hdv)[:, :Skv]
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


@functools.lru_cache(maxsize=None)
def _trainable_attention(causal, window, chunk, softcap):
    """custom-VJP attention for the no-cache (training/encoder) path."""

    @jax.custom_vjp
    def attn(q, k, v):
        out, _ = _fwd_scan(q, k, v, 0, jnp.asarray(k.shape[1], jnp.int32),
                           causal, window, chunk, softcap)
        return out

    def fwd(q, k, v):
        out, lse = _fwd_scan(q, k, v, 0, jnp.asarray(k.shape[1], jnp.int32),
                             causal, window, chunk, softcap)
        return out, (q, k, v, out, lse)

    def bwd(res, g):
        return _bwd_scan(res, g, causal, window, chunk, softcap)

    attn.defvjp(fwd, bwd)
    return attn


def _decode_direct(q, k, v, q_offset, valid_len, causal, window, softcap):
    """Sq==1 decode without the chunk scan: one masked einsum + softmax.

    Under SPMD with the KV cache sequence-sharded on ``model`` this keeps
    scores and the p·V contraction shard-local; the only collectives are the
    tiny softmax max/sum and output psums ((B,H,hd) per layer — MBs/step,
    vs all-gathering the whole cache chunk-by-chunk through a scan, which is
    GBs/step). Score memory is (B,H,Sq,Skv) — fine for Sq ≲ 4."""
    B, Sq, H, hd = q.shape
    _, Skv, KV, hdv = v.shape
    n_rep = H // KV
    scale = 1.0 / (k.shape[-1] ** 0.5)

    # keep the (huge) cache operands in their storage dtype and accumulate in
    # f32 — an f32 astype here would materialize an f32 copy of the whole
    # cache (hoisted out of the layer scan: 3.6+ GB/chip/token measured)
    qf = (q.astype(jnp.float32) * scale).reshape(B, Sq, KV, n_rep, hd)
    s = jnp.einsum(
        "bqkrd,bckd->bkrqc", qf.astype(k.dtype), k,
        preferred_element_type=jnp.float32,
    )
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    k_pos = jnp.arange(Skv, dtype=jnp.int32)
    q_off = jnp.asarray(q_offset, jnp.int32)
    q_pos = (q_off[:, None] if q_off.ndim == 1 else q_off) + jnp.arange(
        Sq, dtype=jnp.int32
    )
    mask = _chunk_mask(q_pos, k_pos, valid_len, causal, window)  # (Sq|B,Sq, Skv)
    m = mask[None, None, None, :, :] if mask.ndim == 2 else mask[:, None, None, :, :]
    s = jnp.where(m, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum(
        "bkrqc,bckd->bqkrd", p.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(B, Sq, H, hdv).astype(q.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "chunk", "window", "softcap"),
)
def blockwise_attention(
    q: jnp.ndarray,          # (B, Sq, H, hd)
    k: jnp.ndarray,          # (B, Skv, KV, hd)
    v: jnp.ndarray,          # (B, Skv, KV, hdv)
    *,
    q_offset: jnp.ndarray | int = 0,   # absolute position of q[0]
    kv_len: jnp.ndarray | None = None, # valid cache length (None -> Skv)
    causal: bool = True,
    window: int | None = None,         # sliding-window width (None -> full)
    chunk: int = 1024,
    softcap: float | None = None,
) -> jnp.ndarray:
    Skv = k.shape[1]
    chunk = min(chunk, Skv)
    if kv_len is None and isinstance(q_offset, int) and q_offset == 0:
        return _trainable_attention(causal, window, chunk, softcap)(q, k, v)
    valid_len = jnp.asarray(Skv if kv_len is None else kv_len, jnp.int32)
    if q.shape[1] <= 4:
        return _decode_direct(q, k, v, q_offset, valid_len, causal, window, softcap)
    out, _ = _fwd_scan(q, k, v, q_offset, valid_len, causal, window, chunk, softcap)
    return out


def paged_decode_attention(
    q: jnp.ndarray,            # (B, Sq, H, hd_tot), pre-scaled by caller if MLA
    cache: dict,               # layer-stacked paged pools (L, pages+1, block_size, F)
    k_names: tuple[str, ...],  # pool names whose feature concat forms K
    v_name: str,               # pool name read as V
    view,                      # KVView with tables (paged layout) and layer
    *,
    kv_heads: int,
    causal: bool = True,
    window: int | None = None,
    name: str = "attn.paged",
) -> jnp.ndarray | None:
    """Fused paged read+attend via kernels/flash_paged.py, or ``None``.

    Returning ``None`` tells the caller to take the reference path
    (``kv_cache_read`` gather + :func:`blockwise_attention`) — so every
    downgrade is an explicit fallback the kernel counters record, never a
    silent rewrite of the math. The kernel applies when the resolved impl is
    pallas (kernels.flash_paged.paged_impl: auto = TPU, or forced via
    ``REPRO_PAGED_ATTN`` / set_paged_impl) and the step is not running a
    sharded mesh program (the gather path owns the collective choreography).

    The kernel reads the whole layer stack in the layout the cache stores
    it, by layer index, so no slice or relayout of a layer's pool is made;
    the trace-time counter ``kv.pool: in_place`` records that it did.
    """
    from ..kernels import ops
    from ..kernels.flash_paged import flash_paged_decode, paged_impl
    from ..parallel import collectives as dist

    path, interpret = paged_impl()
    if path != "pallas":
        ops.record_path(name, "xla")
        return None
    if dist.current_program() is not None:
        ops.record_fallback(name, "mesh")
        return None
    int8 = cache[k_names[0]].dtype == jnp.int8
    ops.record_path(name, "pallas")
    ops.record_path("kv.pool", "in_place")
    return flash_paged_decode(
        q,
        tuple(cache[n] for n in k_names),
        tuple(cache[n + "_scale"] if int8 else None for n in k_names),
        cache[v_name],
        cache[v_name + "_scale"] if int8 else None,
        view.tables,
        view.pos,
        view.kv_len,
        view.layer,
        kv_heads=kv_heads,
        causal=causal,
        window=window,
        interpret=interpret,
    )
