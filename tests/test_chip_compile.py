"""Compile guards for the two hot-path Pallas kernels on a described TPU v5e.

Interpret mode (the rest of the suite) runs the kernel bodies in Python and
accepts block shapes and ops that Mosaic, the TPU kernel compiler, refuses.
These tests compile — without running — the fused tuGEMM kernel and the
paged flash-decode kernel for one chip of a described ``v5e:2x2`` topology,
at the published widths of qwen3-0.6b (GQA) and deepseek-v2-lite (MLA), so
a layout or op Mosaic cannot lower fails here rather than on the chip.

The topology is described inside a module fixture, never at import: the TPU
library may be loaded by one process at a time, and every test worker
imports every test file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.flash_paged import flash_paged_decode

# qwen3-0.6b widths (configs/qwen3_0_6b.py)
D_MODEL, D_FF, HEADS, KV_HEADS, HEAD_DIM = 1024, 3072, 16, 8, 128
# deepseek-v2-lite MLA decode widths (configs/deepseek_v2_lite.py)
MLA_LORA, MLA_ROPE = 512, 64
# a serving step: 4 rows of a 128-token prefill chunk, or 4 decode rows
ROWS, CHUNK = 4, 128
PAGES, BLOCK, MAX_BLOCKS = 512, 16, 128


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # can never be read back without the chip: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _tpu_kernels(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


@pytest.mark.parametrize("m", [ROWS, ROWS * CHUNK], ids=["decode", "prefill"])
@pytest.mark.parametrize(
    "bits,w_quantized,k,n",
    [
        (8, False, D_MODEL, D_FF),       # dynamic int8 (quantize-on-load)
        (8, True, D_MODEL, D_FF),        # prequant int8
        (4, True, D_MODEL, D_FF),        # prequant int4, plane-packed
        (2, True, D_FF, D_MODEL),        # prequant int2, plane-packed
    ],
    ids=["dyn-int8", "pre-int8", "pre-int4", "pre-int2"],
)
def test_fused_gemm_compiles(one_chip, bits, w_quantized, k, n, m):
    planes = {8: 1, 4: 2, 2: 4}[bits] if w_quantized else 1
    x = _spec(one_chip, (m, k), jnp.bfloat16)
    w = _spec(one_chip, (k // planes, n), jnp.int8 if w_quantized else jnp.bfloat16)
    sx = _spec(one_chip, (m,), jnp.float32)   # per-token activation scales
    sw = _spec(one_chip, (n,), jnp.float32)

    def gemm(x, w, sx, sw):
        return ops.matmul_fused(
            x, w, sx=sx, sw=sw, bits=bits, w_quantized=w_quantized,
            collect_stats=True, impl="pallas",
        )

    compiled = jax.jit(gemm).lower(x, w, sx, sw).compile()
    assert _tpu_kernels(compiled) >= 1
    y, _stats = jax.eval_shape(gemm, x, w, sx, sw)
    assert y.shape == (m, n) and y.dtype == jnp.bfloat16


@pytest.mark.parametrize(
    "sq,kv_heads,parts,v_width",
    [
        (1, KV_HEADS, (HEAD_DIM,), KV_HEADS * HEAD_DIM),
        (CHUNK, KV_HEADS, (HEAD_DIM,), KV_HEADS * HEAD_DIM),
        (1, 1, (MLA_LORA, MLA_ROPE), MLA_LORA),
    ],
    ids=["gqa-decode", "gqa-prefill", "mla-decode"],
)
@pytest.mark.parametrize("kv_dtype", [jnp.int8, jnp.bfloat16], ids=["int8", "bf16"])
def test_flash_paged_compiles(one_chip, sq, kv_heads, parts, v_width, kv_dtype):
    int8 = kv_dtype == jnp.int8
    q = _spec(one_chip, (ROWS, sq, HEADS, sum(parts)), jnp.bfloat16)
    k_parts = tuple(_spec(one_chip, (PAGES + 1, BLOCK, kv_heads * f), kv_dtype)
                    for f in parts)
    scale = _spec(one_chip, (PAGES + 1, BLOCK), jnp.float32) if int8 else None
    k_scales = tuple(scale for _ in parts)
    v_pool = _spec(one_chip, (PAGES + 1, BLOCK, v_width), kv_dtype)
    tables = _spec(one_chip, (ROWS, MAX_BLOCKS), jnp.int32)
    vec = _spec(one_chip, (ROWS,), jnp.int32)

    def attend(q, k_parts, k_scales, v_pool, v_scale, tables, pos, kv_len):
        return flash_paged_decode(q, k_parts, k_scales, v_pool, v_scale,
                                  tables, pos, kv_len, kv_heads=kv_heads)

    args = (q, k_parts, k_scales, v_pool, scale, tables, vec, vec)
    compiled = jax.jit(attend).lower(*args).compile()
    assert _tpu_kernels(compiled) == 1
    out = jax.eval_shape(attend, *args)
    assert out.shape == (ROWS, sq, HEADS, v_width // kv_heads)
