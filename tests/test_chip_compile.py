"""Compile guards for the two hot-path Pallas kernels on a described TPU v5e.

Interpret mode (the rest of the suite) runs the kernel bodies in Python and
accepts block shapes and ops that Mosaic, the TPU kernel compiler, refuses.
These tests compile — without running — the fused tuGEMM kernel and the
paged flash-decode kernel for one chip of a described ``v5e:2x2`` topology,
at the published widths of qwen3-0.6b (GQA) and deepseek-v2-lite (MLA), so
a layout or op Mosaic cannot lower fails here rather than on the chip. One
more compiles the whole donated serving step and checks, in the compiled
program, that the paged KV pool is updated and read in place: no
instruction outside the kernel copies, slices or relayouts a layer of it.

The topology is described inside a module fixture, never at import: the TPU
library may be loaded by one process at a time, and every test worker
imports every test file.
"""

import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.flash_paged import flash_paged_decode, set_paged_impl

# qwen3-0.6b widths (configs/qwen3_0_6b.py)
D_MODEL, D_FF, HEADS, KV_HEADS, HEAD_DIM = 1024, 3072, 16, 8, 128
# deepseek-v2-lite MLA decode widths (configs/deepseek_v2_lite.py)
MLA_LORA, MLA_ROPE = 512, 64
# a serving step: 4 rows of a 128-token prefill chunk, or 4 decode rows
ROWS, CHUNK = 4, 128
PAGES, BLOCK, MAX_BLOCKS = 512, 16, 128
LAYERS = 3  # the paged kernel reads one layer of a stacked pool
# the benchmark cells' paged steps (bench/configs): 32 rows, 128-token
# pages; qwen3-0.6b (16 heads, 416 pages, 40 a row) and qwen3-8b-l12 (32
# heads, 608 pages, 65 a row), both with kv 8 and head_dim 128
CELLS = {
    None: dict(rows=ROWS, heads=HEADS, pages=PAGES, block=BLOCK,
               max_blocks=MAX_BLOCKS),
    "qwen3-0.6b": dict(rows=32, heads=16, pages=416, block=128, max_blocks=40),
    "qwen3-8b-l12": dict(rows=32, heads=32, pages=608, block=128, max_blocks=65),
}


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
    "sq,kv_heads,parts,v_width,cell",
    [
        (1, KV_HEADS, (HEAD_DIM,), KV_HEADS * HEAD_DIM, None),
        (CHUNK, KV_HEADS, (HEAD_DIM,), KV_HEADS * HEAD_DIM, None),
        (1, 1, (MLA_LORA, MLA_ROPE), MLA_LORA, None),
        (1, KV_HEADS, (HEAD_DIM,), KV_HEADS * HEAD_DIM, "qwen3-0.6b"),
        (128, KV_HEADS, (HEAD_DIM,), KV_HEADS * HEAD_DIM, "qwen3-0.6b"),
        (1, KV_HEADS, (HEAD_DIM,), KV_HEADS * HEAD_DIM, "qwen3-8b-l12"),
        (64, KV_HEADS, (HEAD_DIM,), KV_HEADS * HEAD_DIM, "qwen3-8b-l12"),
    ],
    ids=["gqa-decode", "gqa-prefill", "mla-decode",
         "qwen3-0.6b-decode", "qwen3-0.6b-mixed",
         "qwen3-8b-l12-decode", "qwen3-8b-l12-mixed"],
)
@pytest.mark.parametrize("kv_dtype", [jnp.int8, jnp.bfloat16], ids=["int8", "bf16"])
def test_flash_paged_compiles(one_chip, sq, kv_heads, parts, v_width, cell, kv_dtype):
    int8 = kv_dtype == jnp.int8
    c = CELLS[cell]
    rows, pool = c["rows"], (LAYERS, c["pages"] + 1, c["block"])
    q = _spec(one_chip, (rows, sq, c["heads"], sum(parts)), jnp.bfloat16)
    k_parts = tuple(_spec(one_chip, (*pool, kv_heads * f), kv_dtype) for f in parts)
    scale = _spec(one_chip, pool, jnp.float32) if int8 else None
    k_scales = tuple(scale for _ in parts)
    v_pool = _spec(one_chip, (*pool, v_width), kv_dtype)
    tables = _spec(one_chip, (rows, c["max_blocks"]), jnp.int32)
    vec = _spec(one_chip, (rows,), jnp.int32)
    layer = _spec(one_chip, (), jnp.int32)

    def attend(q, k_parts, k_scales, v_pool, v_scale, tables, pos, kv_len, layer):
        return flash_paged_decode(q, k_parts, k_scales, v_pool, v_scale,
                                  tables, pos, kv_len, layer, kv_heads=kv_heads)

    args = (q, k_parts, k_scales, v_pool, scale, tables, vec, vec, layer)
    compiled = jax.jit(attend).lower(*args).compile()
    assert _tpu_kernels(compiled) == 1
    out = jax.eval_shape(attend, *args)
    assert out.shape == (rows, sq, c["heads"], v_width // kv_heads)


# ------------------------------------------------- the serving step, in place
_COMPUTATION = re.compile(r"^(?:ENTRY )?%([\w.\-]+) .*\{\s*$")
_INSTRUCTION = re.compile(
    r"^\s*(ROOT )?%([\w.\-]+) = ([a-z0-9]+)\[([0-9,]*)\]\S* ([\w\-]+)\(")
_CALLS = re.compile(r"calls=%([\w.\-]+)")
_ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "f32": 4, "s32": 4, "u32": 4}
# instructions that name a buffer without moving it
_VIEWS = {"parameter", "get-tuple-element", "tuple", "bitcast", "while"}


def _layer_sized(hlo: str, layer_bytes: int, stacks: set) -> list[str]:
    """Instructions of the compiled program, outside fused computations,
    whose result is at least ``layer_bytes`` — one layer's pool — other
    than buffer views, the paged kernel's custom call, and a scatter (or a
    fusion rooted in one) whose result is a whole stacked cache leaf: the
    in-place write of the step's tokens into the carried stack. What is
    left copies, slices or relayouts at least a layer of the pool."""
    comps: dict = {}
    fused: set = set()
    body = None
    for ln in hlo.splitlines():
        head = _COMPUTATION.match(ln)
        if head:
            body = comps.setdefault(head.group(1), [])
            continue
        m = _INSTRUCTION.match(ln)
        if m and body is not None:
            body.append(m.groups() + (ln,))
            if m.group(5) == "fusion":
                fused.update(_CALLS.findall(ln))
    root_op = {c: next((i[4] for i in ins if i[0]), None) for c, ins in comps.items()}
    hits = []
    for c, ins in comps.items():
        if c in fused:
            continue
        for _root, _name, dtype, dims, op, ln in ins:
            shape = tuple(int(d) for d in dims.split(",") if d)
            size = _ITEMSIZE.get(dtype, 4)
            for d in shape:
                size *= d
            if size < layer_bytes or op in _VIEWS:
                continue
            if op == "custom-call" and "tpu_custom_call" in ln:
                continue
            calls = _CALLS.search(ln)
            scatter = op == "scatter" or (
                op == "fusion" and calls and root_op.get(calls.group(1)) == "scatter")
            if scatter and shape in stacks:
                continue
            hits.append(ln.strip()[:160])
    return hits


@pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
@pytest.mark.parametrize("width", [1, 64], ids=["decode", "mixed"])
def test_serving_step_updates_the_kv_pool_in_place(one_chip, width, kv_dtype):
    """The donated mixed step at qwen3-0.6b widths (3 layers, 128-token
    pages, 256 pages): its temporary memory stays under one layer's K pool,
    and no instruction outside the kernel makes a result a layer's pool in
    size — the pool is carried through the layer loop, scattered into and
    read by layer index where it lies."""
    from repro.configs.base import RunConfig, get_config
    from repro.models import abstract_params, init_caches
    from repro.quant import apply_surgery
    from repro.quant.policy import load_policy
    from repro.serve.scheduler import build_mixed_step

    cfg = dataclasses.replace(get_config("qwen3-0.6b"), num_layers=3)
    rc = RunConfig(
        dtype="bfloat16", param_dtype="bfloat16", remat="none",
        kv_cache_dtype=kv_dtype, kv_layout="paged", block_size=128,
        prefill_chunk=64, token_budget=0,
        quant_policy=load_policy("attn.*=int8:prequant:per_token:pallas,"
                                 "mlp.*=int8:prequant:per_token:pallas,*=bf16"),
    )
    rows, capacity, pages = 8, 1024, 256

    def on_chip(tree):
        return jax.tree.map(lambda a: _spec(one_chip, a.shape, a.dtype), tree)

    params = on_chip(jax.eval_shape(lambda p: apply_surgery(cfg, rc, p),
                                    abstract_params(cfg, rc)))
    caches = on_chip(jax.eval_shape(
        lambda: init_caches(cfg, rc, rows, capacity, num_pages=pages)))
    pool = caches[0]["k0"]["k"]
    layer_bytes = pool.size // pool.shape[0] * jnp.dtype(pool.dtype).itemsize
    i32 = jnp.int32
    args = (params, caches, _spec(one_chip, (rows, width), i32),
            _spec(one_chip, (rows,), i32), _spec(one_chip, (rows,), i32),
            _spec(one_chip, (rows, capacity // rc.block_size), i32))
    set_paged_impl("pallas")   # the CPU's default would pick the XLA twin
    try:
        compiled = jax.jit(build_mixed_step(cfg, rc), donate_argnums=(1,)) \
            .lower(*args).compile()
    finally:
        set_paged_impl(None)
    hlo = compiled.as_text()
    assert hlo.count("flash_paged_decode") >= 1
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < layer_bytes, (temp, layer_bytes)
    stacks = {leaf.shape for leaf in jax.tree.leaves(caches)}
    moved = _layer_sized(hlo, layer_bytes, stacks)
    assert not moved, "whole-layer pool traffic outside the kernel:\n" + "\n".join(moved)
