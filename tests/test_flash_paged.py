"""Paged flash-decode kernel tests (kernels/flash_paged.py, DESIGN.md §13).

The contract: the fused paged kernel — split-K over per-slot block tables,
int8 dequant in the attention inner loop, online softmax — matches the XLA
twin (``kv_cache_read`` gather + ``blockwise_attention``) on every layout it
serves: GQA and MLA pools, float and int8 KV, decode (Sq=1) and mixed
prefill+decode widths, sliding windows, and every block-table edge case
(partial last page, single-page rows, empty/idle rows, stale trash pages).

The kernel reads the layer-stacked pool by layer index; every case runs on
a 3-layer stack whose layers hold different contents, and the twin reads
the one layer sliced out on its own, so a kernel that read another layer
would not match.

Outputs agree to float-accumulation order (online softmax reassociates the
sum); the serving-level acceptance is exact: the scheduler's greedy token
stream through the Pallas path is bit-identical to the twin's, and the
decode-step HLO on the Pallas path contains no materialized ``pool[tables]``
gather.
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs.base import RunConfig, get_config
from repro.kernels import ops
from repro.kernels.flash_paged import flash_paged_decode, set_paged_impl
from repro.models import init
from repro.models.attention import KVView, _quantize_kv, kv_cache_read
from repro.models.flash import blockwise_attention, paged_decode_attention

RC = RunConfig(
    dtype="float32", param_dtype="float32", remat="none",
    prefill_chunk=5, kv_cache_dtype="int8",
)

TOL = 2e-5  # float-accumulation-order headroom; values are O(1)
LAYERS = 3  # every pool is a layer stack of this depth, layers all distinct


def _pool(P, bs, feat, int8, seed):
    """One layer-stacked paged cache buffer, as the cache stores it:
    (LAYERS, pages+1, bs, F) with the last page the trash page, F the
    head-major feature axis, int8 token scales (LAYERS, pages+1, bs)."""
    r = np.random.default_rng(seed)
    F = int(np.prod(feat))
    data = r.standard_normal((LAYERS * (P + 1), bs, F)).astype(np.float32)
    if not int8:
        return {"k": jnp.asarray(data.reshape(LAYERS, P + 1, bs, F))}
    q, s = _quantize_kv(jnp.asarray(data))
    return {"k": q.reshape(LAYERS, P + 1, bs, F),
            "k_scale": s.reshape(LAYERS, P + 1, bs)}


def _one_layer(cache: dict, view: KVView):
    """The view's layer sliced out as a stack of one, and a view of it."""
    return ({n: b[view.layer][None] for n, b in cache.items()},
            dataclasses.replace(view, layer=0))


def _view(rows, bs, MB, P, seed=0, layer=1):
    """KVView for per-row (pos, lens) specs; pages assigned disjointly,
    unused table entries left on the trash page (id P) like BlockManager."""
    r = np.random.default_rng(seed)
    B = len(rows)
    tables = np.full((B, MB), P, np.int32)
    ids = r.permutation(P)
    nxt = 0
    pos = np.zeros(B, np.int32)
    lens = np.zeros(B, np.int32)
    for b, (p, l) in enumerate(rows):
        pos[b], lens[b] = p, l
        for m in range(-(-(p + l) // bs) if (p + l) else 0):
            tables[b, m] = ids[nxt]
            nxt += 1
    return KVView(jnp.asarray(pos), jnp.asarray(lens), jnp.asarray(tables),
                  block_size=bs, layout="paged", layer=jnp.int32(layer))


def _gqa_case(rows, *, kv=2, group=3, hd=8, sq=1, bs=4, MB=3, int8=True,
              window=None, seed=0, layer=1, prep=None):
    """Kernel output and twin output for one GQA case; ``prep(cache,
    view)``, if given, rewrites the pools both read."""
    B = len(rows)
    P = B * MB
    view = _view(rows, bs, MB, P, seed=seed, layer=layer)
    kc = _pool(P, bs, (kv, hd), int8, seed + 1)
    vc = {k.replace("k", "v", 1): v for k, v in _pool(P, bs, (kv, hd), int8, seed + 2).items()}
    cache = {**kc, **vc}
    if prep is not None:
        cache = prep(cache, view)
    q = jnp.asarray(np.random.default_rng(seed + 3)
                    .standard_normal((B, sq, kv * group, hd)).astype(np.float32))

    out = flash_paged_decode(
        q, (cache["k"],), (cache.get("k_scale"),), cache["v"], cache.get("v_scale"),
        view.tables, view.pos, view.kv_len, view.layer,
        kv_heads=kv, causal=True, window=window, interpret=True,
    )
    one, view1 = _one_layer(cache, view)
    k_full, v_full = (
        kv_cache_read(one, n, q.dtype, kv_len=view.kv_len, view=view1)
        .reshape(B, -1, kv, hd) for n in ("k", "v"))
    ref = blockwise_attention(q, k_full, v_full, q_offset=view.pos,
                              kv_len=view.kv_len, causal=True, window=window)
    return np.asarray(out), np.asarray(ref)


# --------------------------------------------------------------- GQA anchors
@pytest.mark.parametrize("layer", range(LAYERS))
@pytest.mark.parametrize("int8", [False, True])
@pytest.mark.parametrize("sq", [1, 3])
def test_gqa_kernel_matches_twin(int8, sq, layer):
    rows = [(5, 1), (0, sq), (0, 0), (10, 1)]  # partial page / fresh / idle / near-full
    out, ref = _gqa_case(rows, sq=sq, int8=int8, layer=layer)
    np.testing.assert_allclose(out, ref, atol=TOL, rtol=0)


def test_gqa_kernel_sliding_window():
    out, ref = _gqa_case([(5, 1), (9, 1), (0, 0)], int8=True, window=3)
    np.testing.assert_allclose(out, ref, atol=TOL, rtol=0)


def test_gqa_kernel_mixed_step_width():
    """Sq=5 — the scheduler's mixed prefill+decode step shape: one prefill
    chunk from zero, one mid-sequence chunk, one decode row, one idle row."""
    out, ref = _gqa_case([(0, 5), (3, 5), (7, 1), (0, 0)], sq=5, kv=2, group=2,
                         MB=4, int8=True)
    np.testing.assert_allclose(out, ref, atol=TOL, rtol=0)


def test_idle_rows_emit_zeros():
    """kv_len == 0 rows are fully masked: the kernel's l accumulator stays 0
    and the flush guard must emit exact zeros (not NaN from 0/0)."""
    out, _ = _gqa_case([(0, 0), (0, 0)], int8=True)
    assert np.all(out == 0.0) and not np.any(np.isnan(out))


# --------------------------------------------------------------- MLA anchors
@pytest.mark.parametrize("layer", range(LAYERS))
@pytest.mark.parametrize("int8", [False, True])
@pytest.mark.parametrize("sq", [1, 3])
def test_mla_kernel_matches_twin(int8, sq, layer):
    """Two K parts concatenated per page in-register ([ckv ; kr], single
    latent head), V = the ckv pool — the absorbed-decode MLA layout."""
    out, ref = _mla_case([(5, sq), (0, 0), (11 - sq, sq)], sq=sq, int8=int8,
                         layer=layer)
    np.testing.assert_allclose(out, ref, atol=TOL, rtol=0)


def _mla_case(rows, *, sq, int8, layer=1, prep=None):
    """Kernel output and twin output for one MLA case (MB 4, bs 4);
    ``prep(cache, view)``, if given, rewrites the pools both read."""
    lora, rope_d, h = 32, 16, 4
    B, bs, MB = len(rows), 4, 4
    P = B * MB
    view = _view(rows, bs, MB, P, seed=7, layer=layer)
    ckv = _pool(P, bs, (lora,), int8, 8)
    kr = {k.replace("k", "kr", 1): v for k, v in _pool(P, bs, (rope_d,), int8, 9).items()}
    cache = {"ckv": ckv["k"], "kr": kr["kr"]}
    if int8:
        cache["ckv_scale"], cache["kr_scale"] = ckv["k_scale"], kr["kr_scale"]
    if prep is not None:
        cache = prep(cache, view)
    q = jnp.asarray(np.random.default_rng(10)
                    .standard_normal((B, sq, h, lora + rope_d)).astype(np.float32))

    out = flash_paged_decode(
        q, (cache["ckv"], cache["kr"]),
        (cache.get("ckv_scale"), cache.get("kr_scale")),
        cache["ckv"], cache.get("ckv_scale"),
        view.tables, view.pos, view.kv_len, view.layer,
        kv_heads=1, causal=True, interpret=True,
    )
    one, view1 = _one_layer(cache, view)
    ckv_full = kv_cache_read(one, "ckv", q.dtype, kv_len=view.kv_len, view=view1)
    kr_full = kv_cache_read(one, "kr", q.dtype, kv_len=view.kv_len, view=view1)
    k_eff = jnp.concatenate([ckv_full, kr_full], axis=-1)[:, :, None, :]
    ref = blockwise_attention(q, k_eff, ckv_full[:, :, None, :],
                              q_offset=view.pos, kv_len=view.kv_len, causal=True)
    return np.asarray(out), np.asarray(ref)


# ------------------------------------------------------- dead pages unread
def _poison_dead(cache: dict, view: KVView) -> dict:
    """Every page that holds no live key of any row (the pages wholly at or
    after a row's kv_len: the trash page and every page no live table entry
    names) filled with NaN in every layer: in the token scales of an int8
    pool, else in the data, which is stored as bf16. A kernel that adds
    such a page into its sums, even with probability 0, returns NaN."""
    tables, kv_len = np.asarray(view.tables), np.asarray(view.kv_len)
    bs = view.block_size
    live = {int(tables[b, m]) for b in range(len(kv_len))
            for m in range(-(-int(kv_len[b]) // bs))}
    int8 = any(n.endswith("_scale") for n in cache)
    out = {}
    for name, buf in cache.items():
        if buf.dtype == jnp.int8:
            out[name] = buf
            continue
        arr = np.asarray(buf, np.float32).copy()
        dead = [p for p in range(arr.shape[1]) if p not in live]
        arr[:, dead] = np.nan
        out[name] = jnp.asarray(arr, jnp.float32 if int8 else jnp.bfloat16)
    return out


@pytest.mark.parametrize("sq", [1, 4], ids=["decode", "mixed"])
@pytest.mark.parametrize("int8", [True, False], ids=["int8", "bf16"])
@pytest.mark.parametrize("family", ["gqa", "mla"])
def test_dead_pages_are_never_read(family, int8, sq):
    """Pages at or after a row's kv_len, and the trash page, hold NaN: the
    kernel must neither fetch them into its sums nor let them reach the
    output, and must still match the twin (which masks them with
    ``where``). Rows: idle, partial last page, ending on a page boundary,
    one short of a full table (MB 4, bs 4)."""
    rows = [(0, 0), (5, sq), (8 - sq, sq), (15 - sq, sq)]
    if family == "gqa":
        out, ref = _gqa_case(rows, sq=sq, MB=4, int8=int8, prep=_poison_dead)
    else:
        out, ref = _mla_case(rows, sq=sq, int8=int8, prep=_poison_dead)
    assert np.isfinite(ref).all()
    assert np.isfinite(out).all(), np.argwhere(~np.isfinite(out))[:4]
    np.testing.assert_allclose(out, ref, atol=TOL, rtol=0)


# ------------------------------------------------------- split-K edge cases
@pytest.mark.parametrize(
    "rows",
    [
        [(3, 1), (7, 1)],            # pos+len on an exact page boundary
        [(0, 2), (1, 2)],            # whole row inside a single page
        [(0, 0), (0, 0), (0, 0)],    # all idle (every page is trash)
        [(11, 1), (0, 1), (5, 0)],   # last page one-short of full / fresh / idle
        [(4, 1)],                    # batch of one, starts exactly on page 2
    ],
)
def test_split_k_edge_rows(rows):
    """Deterministic twin of the hypothesis sweep below — these exact
    boundary shapes always run even when hypothesis is stubbed out."""
    sq = max(1, max(l for _, l in rows))
    out, ref = _gqa_case(rows, sq=sq, int8=True, seed=len(rows))
    np.testing.assert_allclose(out, ref, atol=TOL, rtol=0)


# ------------------------------------------------- split-K edge cases (prop)
@settings(max_examples=20, deadline=None)
@given(
    bs=st.sampled_from([2, 4]),
    data=st.data(),
)
def test_split_k_edge_shapes(bs, data):
    """Arbitrary per-row (pos, lens) over a small page pool: rows ending
    mid-page (partial last page), exactly on a page boundary, within a
    single page, and idle — every split-K boundary the grid can hit."""
    MB = 3
    cap = bs * MB
    B = data.draw(st.integers(1, 3), label="B")
    rows = []
    for i in range(B):
        lens = data.draw(st.integers(0, 2), label=f"lens{i}")
        pos = data.draw(st.integers(0, cap - lens), label=f"pos{i}") if lens else 0
        rows.append((pos, lens))
    sq = max(1, max(l for _, l in rows))
    out, ref = _gqa_case(rows, sq=sq, bs=bs, MB=MB, int8=True,
                         seed=data.draw(st.integers(0, 3), label="seed"))
    np.testing.assert_allclose(out, ref, atol=TOL, rtol=0)


# ---------------------------------------------------- dispatcher + counters
def test_dispatcher_fallback_and_counters():
    """paged_decode_attention returns None (-> caller takes the twin) when
    the impl resolves to xla, returns the kernel output when forced to
    pallas — and the path counters record both, per GEMM name."""
    rows = [(5, 1), (0, 0)]
    B, bs, MB, kv, hd = len(rows), 4, 3, 2, 8
    P = B * MB
    view = _view(rows, bs, MB, P, seed=11)
    kc = _pool(P, bs, (kv, hd), True, 12)
    vc = {k.replace("k", "v", 1): v for k, v in _pool(P, bs, (kv, hd), True, 13).items()}
    cache = {**kc, **vc}
    q = jnp.asarray(np.random.default_rng(14)
                    .standard_normal((B, 1, kv * 2, hd)).astype(np.float32))
    try:
        ops.reset_kernel_counters()
        set_paged_impl("xla")
        assert paged_decode_attention(q, cache, ("k",), "v", view,
                                      kv_heads=kv, name="t.paged") is None
        set_paged_impl("pallas_interpret")
        out = paged_decode_attention(q, cache, ("k",), "v", view,
                                     kv_heads=kv, name="t.paged")
        assert out is not None and out.shape == (B, 1, kv * 2, hd)
        paths = ops.kernel_counters()["paths"]["t.paged"]
        assert paths == {"xla": 1, "pallas": 1}, paths
        assert "t.paged" not in ops.kernel_counters()["fallbacks"]
    finally:
        set_paged_impl(None)
        ops.reset_kernel_counters()


# ----------------------------------------------- serving: greedy token A/B
@pytest.mark.parametrize(
    "arch,policy",
    [
        ("qwen3-0.6b_smoke", "attn.*=int8,*=int2"),
        ("deepseek-v2-lite-16b_smoke", "mla.*=int8,*=int2"),
    ],
)
def test_scheduler_greedy_tokens_identical_pallas_vs_xla(arch, policy):
    """The acceptance gate: the full paged scheduler, kernel path vs twin
    path, emits bit-identical greedy token streams AND identical per-slot
    tuGEMM cycle totals — and health()['kernels'] shows the paged kernel
    compiled on the Pallas path with zero fallbacks."""
    from repro.serve import Request, Scheduler

    cfg = get_config(arch)
    rc = dataclasses.replace(RC, quant_policy=policy, kv_layout="paged",
                             block_size=4)
    params = init(cfg, rc, jax.random.PRNGKey(0))
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab_size, 4 + 2 * i).tolist() for i in range(3)]

    def run():
        s = Scheduler(cfg, rc, params, capacity=32, max_batch=3,
                      track_energy=True)
        for rid, p in enumerate(prompts):
            s.submit(Request(rid=rid, prompt=list(p), max_new=3))
        done = s.run()
        toks = {r.rid: r.out for r in done}
        cyc = {e["rid"]: e["cycles_by_bits"] for e in s.energy_summary()}
        return toks, cyc, s.health()["kernels"]

    try:
        ops.reset_kernel_counters()
        set_paged_impl("xla")
        toks_x, cyc_x, _ = run()
        set_paged_impl("pallas_interpret")
        ops.reset_kernel_counters()
        toks_p, cyc_p, kernels = run()
    finally:
        set_paged_impl(None)
        ops.reset_kernel_counters()

    assert toks_x == toks_p
    assert cyc_x == cyc_p
    name = "mla.paged" if "mla" in policy else "attn.paged"
    assert kernels["paths"][name].get("pallas", 0) > 0, kernels
    assert name not in kernels["fallbacks"], kernels


# --------------------------------------------------- decode-step HLO gather
_INSTR = re.compile(r"%([\w.\-]+) = [a-z0-9]+\[([0-9,]*)\]")
_GATHER_OPERAND = re.compile(r"\bgather\(%([\w.\-]+)")


def _pool_gathers(hlo: str, pool_shapes: set) -> list[str]:
    """Gather instructions whose operand is a paged pool — the whole layer
    stack or one layer of it, by shape: the materialized ``pool[tables]``
    read. Other gathers (the step's ``take_along_axis`` of each row's last
    hidden column, embedding lookups, table indexing) read other shapes."""
    shapes = {m.group(1): tuple(int(d) for d in m.group(2).split(",") if d)
              for m in _INSTR.finditer(hlo)}
    hits = []
    for ln in hlo.splitlines():
        m = _GATHER_OPERAND.search(ln)
        if m and shapes.get(m.group(1)) in pool_shapes:
            hits.append(ln.strip()[:120])
    return hits


def test_decode_step_hlo_has_no_pool_gather():
    """On the Pallas path, the compiled mixed decode step must not contain a
    materialized paged-pool gather; the twin path must (detector sanity)."""
    from repro.models import init_caches
    from repro.serve.scheduler import build_mixed_step

    cfg = get_config("qwen3-0.6b_smoke")
    rc = dataclasses.replace(RC, kv_layout="paged", block_size=4)
    params = init(cfg, rc, jax.random.PRNGKey(2))
    B, cap = 2, 16
    caches = init_caches(cfg, rc, B, cap)
    pool_shapes = {s for leaf in jax.tree.leaves(caches)
                   for s in (leaf.shape, leaf.shape[1:])}
    tokens = jnp.ones((B, 5), jnp.int32)
    pos = jnp.asarray([3, 0], jnp.int32)
    lens = jnp.asarray([1, 0], jnp.int32)
    tables = jnp.arange(B * (cap // 4), dtype=jnp.int32).reshape(B, cap // 4)

    def lower():
        return jax.jit(build_mixed_step(cfg, rc)).lower(
            params, caches, tokens, pos, lens, tables
        ).compile().as_text()

    try:
        set_paged_impl("xla")
        twin = _pool_gathers(lower(), pool_shapes)
        set_paged_impl("pallas_interpret")
        kernel = _pool_gathers(lower(), pool_shapes)
    finally:
        set_paged_impl(None)
    assert twin, "detector sanity: twin path should materialize pool gathers"
    assert not kernel, f"pool gather survived on the Pallas path:\n" + "\n".join(kernel)


def test_paged_step_reads_the_pool_in_place():
    """A paged step on the kernel path records ``kv.pool: in_place`` (the
    kernel read the layer-stacked pool by layer index) beside
    ``attn.paged: pallas``, with no fallback, in health()["kernels"]."""
    from repro.serve import Request, Scheduler

    cfg = get_config("qwen3-0.6b_smoke")
    rc = dataclasses.replace(RC, kv_layout="paged", block_size=4)
    params = init(cfg, rc, jax.random.PRNGKey(3))
    try:
        set_paged_impl("pallas_interpret")
        s = Scheduler(cfg, rc, params, capacity=16, max_batch=2)
        s.submit(Request(rid=0, prompt=[1, 2, 3, 4, 5, 6], max_new=2))
        s.run()
        kernels = s.health()["kernels"]
    finally:
        set_paged_impl(None)
    assert set(kernels["paths"]["kv.pool"]) == {"in_place"}, kernels
    assert set(kernels["paths"]["attn.paged"]) == {"pallas"}, kernels
    assert not kernels["fallbacks"], kernels
