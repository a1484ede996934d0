"""The Qwen3 family: what the benchmark needs to know of a Qwen3 block.

A configuration file names its family with ``"arch"``; the harness loads
``bench/arch/<arch>.py`` by path and reads from it:

- ``KEYS``: configuration-file key -> registry ``ModelConfig`` field, checked
  against the registry at every run (a ``reduced`` key is applied through it);
- ``GEMMS`` and ``ATTENTION``: the quantized GEMMs and the paged attention
  that every run must have traced to the kernel path;
- ``logit_gaps``: the plain float32 reference that decides ``correct``;
- ``layer_gemms``, ``attn_cost`` and ``step_useful_time``: the block's
  operations and bytes, for the per-layer metrics (bench/costs.py).

The reference is straight ``jax.numpy``, every matrix product at
``Precision.HIGHEST``, no kernels, no cache, no batching: one sequence at a
time through the whole stack, causal attention over the whole sequence,
computed layer by layer and in blocks of queries so that it fits beside
nothing else on the chip.

The block follows the published Qwen3 description (hf:Qwen/Qwen3-0.6B,
hf:Qwen/Qwen3-8B): pre-norm RMSNorm (eps from the configuration), q/k/v
projections without bias, RMSNorm over each head of q and k (qk-norm), rotary
embedding on the two halves of each head (theta from the configuration),
grouped-query attention with 1/sqrt(head_dim) scaling, output projection,
residual; RMSNorm, SwiGLU MLP (silu(x Wg) * (x Wu)) Wd, residual. A final
RMSNorm, then logits against the tied embedding or the separate head.

Weights come in as the benchmark made them (bench/weights.py), in the
program's tree layout; they are widened to float32 here, one layer at a time.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.costs import BF16, Gemm, bits_for

KEYS = {
    "hidden_size": "d_model", "intermediate_size": "d_ff",
    "num_hidden_layers": "num_layers",
    "num_attention_heads": "num_heads", "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim", "vocab_size": "vocab_size",
    "rope_theta": "rope_theta", "rms_norm_eps": "rms_eps",
    "tie_word_embeddings": "tie_embeddings",
}
GEMMS = ("attn.q", "attn.k", "attn.v", "attn.o", "mlp.gate", "mlp.up", "mlp.down")
ATTENTION = "attn.paged"

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512      # queries per attention block
LOGIT_BLOCK = 256  # positions per block of vocabulary logits


def padded_length(n: int, capacity: int) -> int:
    """The smallest of a quarter, a half or all of ``capacity`` (rounded up to
    whole query blocks) that holds ``n`` tokens: three shapes per
    configuration, so the reference compiles once for each and then finds
    them in the compilation cache."""
    for frac in (4, 2, 1):
        t = -(-(-(-capacity // frac)) // Q_BLOCK) * Q_BLOCK
        if t >= n:
            return t
    raise ValueError(f"{n} tokens exceed capacity {capacity}")


@dataclass(frozen=True)
class Dims:
    heads: int
    kv_heads: int
    head_dim: int
    eps: float
    theta: float
    tied: bool

    @classmethod
    def of(cls, config: dict) -> "Dims":
        return cls(config["num_attention_heads"], config["num_key_value_heads"],
                   config["head_dim"], float(config["rms_norm_eps"]),
                   float(config["rope_theta"]), bool(config["tie_word_embeddings"]))


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None, None].astype(jnp.float32) * inv          # (T, 1, hd/2)
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    c, s = jnp.cos(ang), jnp.sin(ang)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


@partial(jax.jit, static_argnames=("dims",))
def _layer(x, p, dims: Dims):
    """x (T, D) f32 -> (T, D); p: one layer's weights (any float dtype)."""
    p = _f32(p)
    T = x.shape[0]
    H, KV, hd = dims.heads, dims.kv_heads, dims.head_dim
    pos = jnp.arange(T)
    h = _rms(x, p["norm1"]["scale"], dims.eps)
    a = p["attn"]
    q = jnp.dot(h, a["wq"]["kernel"], precision=HI).reshape(T, H, hd)
    k = jnp.dot(h, a["wk"]["kernel"], precision=HI).reshape(T, KV, hd)
    v = jnp.dot(h, a["wv"]["kernel"], precision=HI).reshape(T, KV, hd)
    q = _rope(_rms(q, a["q_norm"]["scale"], dims.eps), pos, dims.theta)
    k = _rope(_rms(k, a["k_norm"]["scale"], dims.eps), pos, dims.theta)
    rep = H // KV
    k = jnp.repeat(k, rep, axis=1)                              # (T, H, hd)
    v = jnp.repeat(v, rep, axis=1)
    scale = 1.0 / np.sqrt(hd)

    def block(qb_start):
        qb = jax.lax.dynamic_slice_in_dim(q, qb_start, Q_BLOCK, 0)   # (Qb, H, hd)
        s = jnp.einsum("qhd,khd->hqk", qb, k, precision=HI) * scale
        qpos = qb_start + jnp.arange(Q_BLOCK)
        s = jnp.where(pos[None, None, :] <= qpos[None, :, None], s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", w, v, precision=HI)

    starts = jnp.arange(0, T, Q_BLOCK)
    o = jax.lax.map(block, starts).reshape(T, H * hd)
    x = x + jnp.dot(o, a["wo"]["kernel"], precision=HI)
    h2 = _rms(x, p["norm2"]["scale"], dims.eps)
    f = p["ffn"]
    g = jnp.dot(h2, f["w_gate"]["kernel"], precision=HI)
    u = jnp.dot(h2, f["w_up"]["kernel"], precision=HI)
    return x + jnp.dot(jax.nn.silu(g) * u, f["w_down"]["kernel"], precision=HI)


@partial(jax.jit, static_argnames=("dims",))
def _gaps(h, final_scale, unembed, served, dims: Dims):
    """Per position: best logit minus the served token's logit.
    h (N, D) f32; unembed (V, D) tied or (D, V)."""
    h = _rms(h, final_scale.astype(jnp.float32), dims.eps)
    w = unembed.astype(jnp.float32)
    lg = (jnp.dot(h, w.T, precision=HI) if dims.tied
          else jnp.dot(h, w, precision=HI))                     # (N, V)
    got = jnp.take_along_axis(lg, served[:, None], axis=1)[:, 0]
    return lg.max(axis=1) - got


def layers_of(params) -> tuple[dict, int]:
    """The stacked per-layer subtree of a dense program tree, and its depth."""
    (group,) = params["groups"]
    stacked = group["k0"]
    return stacked, jax.tree.leaves(stacked)[0].shape[0]


def hidden(params, dims: Dims, seq: list[int], capacity: int):
    """Last layer's output (before the final norm) at every position of
    ``seq``, padded to ``padded_length``."""
    T = len(seq)
    tokens = jnp.asarray(list(seq) + [0] * (padded_length(T, capacity) - T), jnp.int32)
    x = params["embed"]["embedding"][tokens].astype(jnp.float32)
    stacked, depth = layers_of(params)
    for i in range(depth):
        x = _layer(x, jax.tree.map(lambda a, i=i: a[i], stacked), dims)
    return x


def logit_gaps(params, config: dict, prompt: list[int], served: list[int],
               capacity: int) -> np.ndarray:
    """Reference gap of each served token: the reference's best logit at that
    position minus its logit for the token the program served."""
    dims = Dims.of(config)
    x = hidden(params, dims, list(prompt) + list(served[:-1]), capacity)
    first = len(prompt) - 1
    h = x[first: first + len(served)]
    unembed = (params["embed"]["embedding"] if dims.tied
               else params["head"]["kernel"])
    out = []
    served_arr = np.asarray(served, np.int32)
    for s in range(0, len(served), LOGIT_BLOCK):
        hb = h[s: s + LOGIT_BLOCK]
        n = hb.shape[0]
        hb = jnp.pad(hb, ((0, LOGIT_BLOCK - n), (0, 0)))
        sv = jnp.asarray(np.pad(served_arr[s: s + n], (0, LOGIT_BLOCK - n)))
        g = _gaps(hb, params["final_norm"]["scale"], unembed, sv, dims)
        out.append(np.asarray(g)[:n])
    return np.concatenate(out)


# ------------------------------------------------------------------- costs
def layer_gemms(config: dict, policy: str) -> list[Gemm]:
    """The quantizable GEMMs of one Qwen3 block, (K, N) as the program holds
    them."""
    d, ff = config["hidden_size"], config["intermediate_size"]
    h, kv, hd = (config["num_attention_heads"], config["num_key_value_heads"],
                 config["head_dim"])
    shapes = [("attn.q", d, h * hd), ("attn.k", d, kv * hd), ("attn.v", d, kv * hd),
              ("attn.o", h * hd, d), ("mlp.gate", d, ff), ("mlp.up", d, ff),
              ("mlp.down", ff, d)]
    return [Gemm(n, k, m, bits_for(n, policy)) for n, k, m in shapes]


def attn_cost(config: dict, rows, block_size: int, kv_bytes: int = BF16) -> tuple[float, float]:
    """(operations, bytes) of one layer's paged attention over ``rows``, each
    (pos, n): n queries at positions pos..pos+n-1 against the causal context.
    Bytes are the KV pages the rows' live lengths span, read once, plus the
    queries and outputs."""
    h, kv, hd = (config["num_attention_heads"], config["num_key_value_heads"],
                 config["head_dim"])
    ops = byts = 0.0
    for pos, n in rows:
        visible = n * pos + n * (n + 1) / 2        # sum of causal context lengths
        ops += 4.0 * h * hd * visible              # QK^T and PV
        pages = -(-(pos + n) // block_size)
        byts += pages * block_size * 2 * kv * hd * kv_bytes
        byts += 2 * n * h * hd * BF16
    return ops, byts


def step_useful_time(config: dict, policy: str, rows, pk: dict) -> float:
    """Seconds one step's useful work takes at the device's peaks: each real
    (unpadded) token through every GEMM at that GEMM's precision, the
    vocabulary projection of each scheduled row in bf16, and attention over
    the real causal context in bf16."""
    tokens = sum(n for _, n in rows)
    t = 0.0
    for g in layer_gemms(config, policy):
        peak = pk["bf16_flops"] if g.bits == 16 else pk["int8_ops"]
        t += config["num_hidden_layers"] * 2.0 * tokens * g.k * g.n / peak
    t += 2.0 * len(rows) * config["hidden_size"] * config["vocab_size"] / pk["bf16_flops"]
    a_ops, _ = attn_cost(config, rows, 1)
    t += config["num_hidden_layers"] * a_ops / pk["bf16_flops"]
    return t
