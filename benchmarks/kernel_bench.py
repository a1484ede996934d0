"""Kernel micro-benchmark: exactness sweep + fused-vs-unfused pipeline A/B
+ roofline-fraction gate + end-to-end quantized-vs-fp32 decode-step A/B.

Sections:

1. **Exactness sweep** — for each kernel (int8 GEMM, packed int4/int2 GEMM,
   thermometer-decomposed temporal GEMM, fused pipeline at per-tensor AND
   per-token activation scales) checks bit-exactness of the Pallas body
   (interpret mode) and the XLA path against the jnp oracle, then times the
   XLA path (what CPU users run; TPU would run the compiled Pallas kernels,
   which cannot be timed here).
2. **Pipeline A/B** — times the complete dynamic-quant linear layer through
   qlinear.gemm with ``fused=True`` vs ``fused=False`` on the XLA path and
   counts device dispatches for both (DESIGN.md §4's ≥6 → 2 claim, measured).
3. **Roofline gate** — compiles the two serving hot-path kernels (fused
   per-token tuGEMM, paged flash-decode attention — on CPU the XLA twins
   those paths actually run), prices their optimized-HLO byte traffic under
   the running backend's HW profile, and reports achieved fraction of the
   memory-bound roofline. Below-floor fractions **hard-fail on accelerator
   backends** (tpu/gpu) and are report-only on CPU (DESIGN.md §13).
4. **E2E decode A/B** — a full continuous-batching decode step on the smoke
   model: fp32 vs surgered int8/int4 (dynamic + prequant), logits
   correlation vs fp32, plus the per-step tuGEMM cycle totals and modeled
   energy from the stats-enabled path (DESIGN.md §6).
5. **Mixed-policy A/B** — uniform int8 vs the mixed QuantPolicy deployment
   (attn int8 / mlp int2 / rest bf16, DESIGN.md §7): per-bits cycle split
   and modeled energy on the same decode step.

``benchmarks/BENCH_kernels.json`` is a **per-backend keyed trajectory**
(schema 2): ``{"schema": 2, "backends": {backend: latest-entry},
"history": [compact per-emit rows with backend + git rev]}`` — so a CPU
refresh never clobbers the TPU numbers and a regression is visible the PR
it lands. v1 (flat single-snapshot) files migrate on first write.
``BENCH_e2e.json`` / ``BENCH_policy.json`` use the same store. ``--fast``
never writes the committed files but asserts the schema round-trips and
history appends in-memory. Usage: ``PYTHONPATH=src python
benchmarks/kernel_bench.py [--fast]``.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import subprocess
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops
from repro.kernels.ref import matmul_int_ref
from repro.quant import GemmBackend, effective_policy, gemm, tree_totals_by_bits

_OUT = pathlib.Path(__file__).resolve().parent / "BENCH_kernels.json"
_OUT_E2E = pathlib.Path(__file__).resolve().parent / "BENCH_e2e.json"
_OUT_POLICY = pathlib.Path(__file__).resolve().parent / "BENCH_policy.json"

SCHEMA = 2
_HISTORY_CAP = 100

# declared floors: achieved fraction of the memory-bound roofline each
# serving hot-path kernel must clear on an accelerator backend
ROOFLINE_FLOORS = {"tugemm_fused_pertoken": 0.3, "flash_paged_decode": 0.3}


def git_rev() -> str:
    try:
        return subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            cwd=pathlib.Path(__file__).resolve().parent,
            capture_output=True, text=True, timeout=10, check=True,
        ).stdout.strip()
    except Exception:
        return "unknown"


def _migrate(store: dict) -> dict:
    """v1 (flat single-backend snapshot) -> v2 per-backend keyed store."""
    if not isinstance(store, dict) or not store:
        return {"schema": SCHEMA, "backends": {}, "history": []}
    if store.get("schema") == SCHEMA:
        store.setdefault("backends", {})
        store.setdefault("history", [])
        return store
    return {"schema": SCHEMA,
            "backends": {store.get("backend", "cpu"): store},
            "history": []}


def merge_entry(store: dict, backend: str, entry: dict, rev: str) -> dict:
    """Set ``backends[backend]`` to the new entry and append a compact
    history row (trajectory: backend, git rev, exactness, headline numbers).
    Returns the migrated/updated store (mutated in place when already v2)."""
    store = _migrate(store)
    entry = dict(entry, git_rev=rev)
    store["backends"][backend] = entry
    row: dict = {"backend": backend, "git_rev": rev}
    if "exact" in entry:
        row["exact"] = entry["exact"]
    if entry.get("timings"):
        row["timings"] = entry["timings"]
    if entry.get("pipeline"):
        row["fused_speedup_min"] = min(
            r["speedup"] for r in entry["pipeline"].values())
    if entry.get("roofline"):
        row["roofline_fraction"] = {
            k: v["fraction"] for k, v in entry["roofline"].items()}
    store["history"].append(row)
    store["history"] = store["history"][-_HISTORY_CAP:]
    return store


def emit(path: pathlib.Path, backend: str, entry: dict) -> None:
    """Merge one bench emit into a per-backend store file on disk."""
    try:
        store = json.loads(path.read_text())
    except (OSError, json.JSONDecodeError):
        store = {}
    store = merge_entry(store, backend, entry, git_rev())
    path.write_text(json.dumps(store, indent=2, sort_keys=True) + "\n")
    print(f"wrote {path} (backends: {sorted(store['backends'])}, "
          f"history: {len(store['history'])})")


def check_store_roundtrip(backend: str, entry: dict) -> None:
    """--fast invariant: the v2 schema JSON-round-trips, keys per backend,
    appends history, and migrates a v1 snapshot — all in memory."""
    s1 = merge_entry({}, backend, entry, "aaaaaaa")
    s1 = json.loads(json.dumps(s1))                    # round-trip
    s2 = merge_entry(s1, backend, entry, "bbbbbbb")
    assert s2["schema"] == SCHEMA and backend in s2["backends"]
    assert len(s2["history"]) == 2, s2["history"]
    assert s2["history"][-1]["git_rev"] == "bbbbbbb"
    other = merge_entry(s2, backend + "_other", entry, "ccccccc")
    assert set(other["backends"]) == {backend, backend + "_other"}
    v1 = {"backend": backend, "exact": True, "timings": {"t": 1.0}}
    m = merge_entry(v1, backend, entry, "ddddddd")
    assert m["schema"] == SCHEMA and len(m["history"]) == 1
    print("[schema] per-backend store round-trips, appends history, "
          "migrates v1: ok")


def _rand_int8(key, shape, bits=8):
    m = 1 << (bits - 1)
    return jax.random.randint(key, shape, -m, m, dtype=jnp.int32).astype(jnp.int8)


def _time(fn, *args, iters=5):
    fn(*args).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    out.block_until_ready()
    return (time.perf_counter() - t0) / iters


def bench_exactness(shapes, out):
    key = jax.random.PRNGKey(0)
    print(f"\n{'kernel':<18} {'shape':<18} {'xla ms':>8} {'exact(xla)':>11} {'exact(interp)':>14}")
    for (M, K, N) in shapes:
        ka, kb = jax.random.split(jax.random.fold_in(key, M * N))
        a = _rand_int8(ka, (M, K))
        b = _rand_int8(kb, (K, N))
        ref = matmul_int_ref(a, b)

        y_xla = ops.matmul_int8(a, b, impl="xla")
        ok_x = bool((y_xla == ref).all())
        ok_i = True
        if M <= 128:  # interpret mode is python-slow; keep it to small shapes
            y_int = ops.matmul_int8(a, b, impl="pallas_interpret")
            ok_i = bool((y_int == ref).all())
        dt = _time(lambda a, b: ops.matmul_int8(a, b, impl="xla"), a, b)
        out["exact"] &= ok_x and ok_i
        out["timings"][f"int8_{M}x{K}x{N}"] = dt * 1e3
        gmacs = M * K * N / dt / 1e9
        print(f"{'matmul_int8':<18} {f'{M}x{K}x{N}':<18} {dt*1e3:>8.2f} {str(ok_x):>11} {str(ok_i):>14}  ({gmacs:.1f} GMAC/s)")

        for bits in (4, 2):
            mb = 1 << (bits - 1)
            a_s = jnp.clip(a, -mb, mb - 1)
            b_s = jnp.clip(b, -mb, mb - 1)
            packed = ops.pack_weights(b_s, bits)
            y_p = ops.matmul_packed(a_s, packed, bits=bits, impl="xla")
            ref_p = matmul_int_ref(a_s, b_s)
            ok_p = bool((y_p == ref_p).all())
            out["exact"] &= ok_p
            print(f"{f'matmul_packed w{bits}':<18} {f'{M}x{K}x{N}':<18} {'-':>8} {str(ok_p):>11} {'-':>14}")

    # temporal (thermometer) validation path, small shapes only
    for bits in (2, 4):
        m = 1 << (bits - 1)
        a = jax.random.randint(key, (32, 16), -m, m, dtype=jnp.int32).astype(jnp.int8)
        b = jax.random.randint(key, (16, 32), -m, m, dtype=jnp.int32).astype(jnp.int8)
        y = ops.temporal_gemm(a, b, bitwidth=bits, impl="xla")
        ok = bool((y == matmul_int_ref(a, b)).all())
        out["exact"] &= ok
        print(f"{f'temporal_gemm w{bits}':<18} {'32x16x32':<18} {'-':>8} {str(ok):>11} {'-':>14}")

    # fused per-token-scale path (PR 9 kernel): interpret-Pallas vs XLA
    # bit-exact through the full qlinear layer, and the Pallas path must
    # record zero fallbacks — the downgrade this PR removed stays removed
    ops.reset_kernel_counters()
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(0, 1, (48, 96)), jnp.float32)
    w = jnp.asarray(rng.normal(0, 0.1, (96, 64)), jnp.float32)
    be_x = GemmBackend("int8", impl="xla", fused=True, act_scale="token")
    be_p = GemmBackend("int8", impl="pallas_interpret", fused=True,
                       act_scale="token")
    y_x = gemm(x, w, backend=be_x, name="bench.pertoken")
    y_p = gemm(x, w, backend=be_p, name="bench.pertoken")
    ok = bool((np.asarray(y_x) == np.asarray(y_p)).all())
    out["exact"] &= ok
    fb = ops.kernel_counters()["fallbacks"].get("bench.pertoken", {})
    assert not fb, f"per-token fused matmul fell back to XLA: {fb}"
    print(f"{'fused per-token':<18} {'48x96x64':<18} {'-':>8} {str(ok):>11} "
          f"{str(ok):>14}  (pallas fallbacks: 0)")


def bench_fused_pipeline(shapes, out, iters=10):
    """A/B the full dynamic-quant linear layer: fused vs unfused, XLA path."""
    rng = np.random.default_rng(0)
    print(f"\n{'pipeline (int8 dynamic+stats-off)':<34} {'unfused ms':>11} {'fused ms':>9} "
          f"{'speedup':>8} {'GMAC/s':>8} {'disp u→f':>9}")
    results = {}
    for (M, K, N) in shapes:
        x = jnp.asarray(rng.normal(0, 1, (M, K)), jnp.float32)
        w = jnp.asarray(rng.normal(0, 0.1, (K, N)), jnp.float32)
        b = jnp.asarray(rng.normal(0, 0.1, (N,)), jnp.float32)
        be_f = GemmBackend("int8", impl="xla", fused=True)
        be_u = GemmBackend("int8", impl="xla", fused=False)

        y_f = gemm(x, w, backend=be_f, bias=b)
        y_u = gemm(x, w, backend=be_u, bias=b)
        exact = bool((y_f == y_u).all())
        out["exact"] &= exact

        t_u = _time(lambda x, w: gemm(x, w, backend=be_u, bias=b), x, w, iters=iters)
        t_f = _time(lambda x, w: gemm(x, w, backend=be_f, bias=b), x, w, iters=iters)

        # dispatch counts include the stats sweeps (the profiling configuration)
        with ops.counting_dispatches() as log_u:
            gemm(x, w, backend=be_u.with_stats(), bias=b)
        with ops.counting_dispatches() as log_f:
            gemm(x, w, backend=be_f.with_stats(), bias=b)

        gmacs = M * K * N / t_f / 1e9
        tag = f"{M}x{K}x{N}"
        results[tag] = {
            "unfused_ms": t_u * 1e3,
            "fused_ms": t_f * 1e3,
            "speedup": t_u / t_f,
            "fused_gmacs": gmacs,
            "dispatches_unfused": len(log_u),
            "dispatches_fused": len(log_f),
            "bit_exact": exact,
        }
        print(f"{tag:<34} {t_u*1e3:>11.2f} {t_f*1e3:>9.2f} {t_u/t_f:>7.2f}x "
              f"{gmacs:>8.1f} {len(log_u):>4}→{len(log_f)}")
    out["pipeline"] = results
    worst = min(r["speedup"] for r in results.values())
    dmax = max(r["dispatches_fused"] for r in results.values())
    print(f"\nfused pipeline: min speedup {worst:.2f}x, max dispatches {dmax}")


def _measure_bound(jitted, args, hw, iters):
    """(hlo_bytes, memory_bound_s, measured_s) for one compiled callable."""
    from repro.roofline.hlo_parse import parse_hlo

    compiled = jitted.lower(*args).compile()
    nbytes = float(parse_hlo(compiled.as_text()).hbm_bytes)
    jax.block_until_ready(jitted(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        r = jitted(*args)
    jax.block_until_ready(r)
    dt = (time.perf_counter() - t0) / iters
    return nbytes, nbytes / hw.hbm_bw, dt


def bench_roofline_gate(fast: bool, out: dict, iters: int = 10) -> None:
    """Gate the two serving hot-path kernels against their memory-bound
    roofline (DESIGN.md §13): price each compiled call's optimized-HLO byte
    traffic under the running backend's HW profile and report

        fraction = (HLO_bytes / hbm_bw) / measured_s

    — the fraction of the memory-bound bound the kernel actually achieves.
    Fractions below the declared ROOFLINE_FLOORS hard-fail on accelerator
    backends; on CPU the numbers are report-only (CPU runs the XLA twins and
    the cpu HW profile is a class estimate, not a calibration)."""
    from repro.models.attention import KVView, _quantize_kv, kv_cache_read
    from repro.models.flash import blockwise_attention, paged_decode_attention
    from repro.roofline.analysis import hw_profile

    backend = jax.default_backend()
    hw = hw_profile("auto")
    enforce = backend in ("tpu", "gpu")
    rng = np.random.default_rng(0)
    results: dict = {}

    # fused per-token tuGEMM — the serving linear-layer hot path
    M, K, N = (128, 512, 512) if fast else (512, 2048, 2048)
    x = jnp.asarray(rng.normal(0, 1, (M, K)), jnp.float32)
    w = jnp.asarray(rng.normal(0, 0.1, (K, N)), jnp.float32)
    be = GemmBackend("int8", fused=True, act_scale="token")  # impl=auto
    gemm_fn = jax.jit(
        lambda x, w: gemm(x, w, backend=be, name="roofline.gemm"))
    results["tugemm_fused_pertoken"] = _measure_bound(gemm_fn, (x, w), hw, iters)

    # paged flash-decode — the serving attention hot path (int8 KV pool)
    kv, group, hd, bs, MB, B = (2, 2, 32, 8, 4, 4) if fast else (4, 4, 64, 16, 8, 8)
    P = B * MB
    kq, ks = _quantize_kv(jnp.asarray(
        rng.standard_normal((P + 1, bs, kv * hd)).astype(np.float32)))
    vq, vs = _quantize_kv(jnp.asarray(
        rng.standard_normal((P + 1, bs, kv * hd)).astype(np.float32)))
    # a stack of one layer, as the cache stores it: (1, P+1, bs, kv*hd)
    kq, ks, vq, vs = (a[None] for a in (kq, ks, vq, vs))
    tables = jnp.arange(P, dtype=jnp.int32).reshape(B, MB)
    pos = jnp.full((B,), MB * bs - 1, jnp.int32)   # full rows, decode step
    lens = jnp.ones((B,), jnp.int32)
    q = jnp.asarray(
        rng.standard_normal((B, 1, kv * group, hd)).astype(np.float32))

    def step(q, kq, ks, vq, vs, tables, pos, lens):
        view = KVView(pos, lens, tables, block_size=bs, layout="paged", layer=0)
        cache = {"k": kq, "k_scale": ks, "v": vq, "v_scale": vs}
        o = paged_decode_attention(q, cache, ("k",), "v", view,
                                   kv_heads=kv, name="roofline.paged")
        if o is None:  # CPU: the XLA twin is the path serving actually runs
            kf, vf = (kv_cache_read(cache, n, q.dtype, kv_len=view.kv_len, view=view)
                      .reshape(B, -1, kv, hd) for n in ("k", "v"))
            o = blockwise_attention(q, kf, vf, q_offset=view.pos,
                                    kv_len=view.kv_len, causal=True)
        return o

    results["flash_paged_decode"] = _measure_bound(
        jax.jit(step), (q, kq, ks, vq, vs, tables, pos, lens), hw, iters)

    print(f"\n{'roofline gate (' + hw.name + ' profile)':<34} {'HLO MB':>8} "
          f"{'bound us':>9} {'meas us':>8} {'frac':>6} {'floor':>6} {'gate':>7}")
    gate: dict = {}
    failures = []
    for name, (nbytes, bound_s, meas_s) in results.items():
        frac = bound_s / meas_s if meas_s else 0.0
        floor = ROOFLINE_FLOORS[name]
        ok = frac >= floor
        gate[name] = {
            "hlo_bytes": nbytes,
            "memory_bound_s": bound_s,
            "measured_s": meas_s,
            "fraction": frac,
            "floor": floor,
            "enforced": enforce,
            "hw": hw.name,
        }
        verdict = ("pass" if ok else "FAIL") if enforce else "report"
        print(f"{name:<34} {nbytes/1e6:>8.2f} {bound_s*1e6:>9.1f} "
              f"{meas_s*1e6:>8.1f} {frac:>6.3f} {floor:>6.2f} {verdict:>7}")
        if enforce and not ok:
            failures.append(f"{name}: {frac:.3f} < floor {floor}")
    out["roofline"] = gate
    if failures:
        raise RuntimeError(
            "roofline gate failed on accelerator backend "
            f"{backend}: {'; '.join(failures)}")


def bench_e2e(fast: bool, write_json: bool) -> dict:
    """Quantized-vs-fp32 decode-step A/B on the smoke model (XLA path)."""
    import dataclasses

    from repro.configs.base import RunConfig, get_config
    from repro.core.report import slot_energy
    from repro.models import init, init_caches
    from repro.quant import apply_surgery, tree_totals
    from repro.serve import build_decode, build_prefill

    cfg = get_config("qwen3-0.6b_smoke")
    rc0 = RunConfig(dtype="float32", param_dtype="float32", remat="none")
    params = init(cfg, rc0, jax.random.PRNGKey(0))
    B, T, cap = 4, 8, 32
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, T), 0, cfg.vocab_size)
    nxt = jnp.ones((B, 1), jnp.int32)
    pos = jnp.asarray(T, jnp.int32)
    iters = 5 if fast else 20

    variants = {
        "fp32": rc0,
        "int8_dynamic": dataclasses.replace(rc0, quant_policy="*=int8"),
        "int4_dynamic": dataclasses.replace(rc0, quant_policy="*=int4"),
        "int4_prequant": dataclasses.replace(rc0, quant_policy="*=int4:prequant"),
    }
    out: dict = {"backend": jax.default_backend(), "fast": fast, "variants": {}}
    ref_logits = None
    print(f"\n{'e2e decode step (B=4, smoke model)':<26} {'ms/step':>9} "
          f"{'corr vs fp32':>13} {'Mcycles':>9} {'energy/step':>12}")
    for name, rc in variants.items():
        p = apply_surgery(cfg, rc, params)
        caches = init_caches(cfg, rc, B, cap)
        caches, _ = jax.jit(build_prefill(cfg, rc))(p, caches, {"tokens": toks})
        quant = effective_policy(rc).is_quant
        dec = jax.jit(build_decode(cfg, rc, with_stats=quant))
        res = dec(p, caches, nxt, pos)
        jax.block_until_ready(res)
        t0 = time.perf_counter()
        for _ in range(iters):
            res = dec(p, caches, nxt, pos)
        jax.block_until_ready(res)
        dt = (time.perf_counter() - t0) / iters
        logits = np.asarray(res[1])
        if name == "fp32":
            ref_logits = logits
            corr = 1.0
        else:
            corr = float(np.corrcoef(logits.ravel(), ref_logits.ravel())[0, 1])
        entry = {"ms_per_step": dt * 1e3, "corr_vs_fp32": corr}
        if quant:
            tot = tree_totals(res[2])
            e_j = sum(
                slot_energy(b, "serial", t["serial_cycles"])[1]
                for b, t in tree_totals_by_bits(res[2]).items()
            )
            entry.update(
                serial_cycles=tot["serial_cycles"],
                parallel_cycles=tot["parallel_cycles"],
                energy_j_16x16_serial=e_j,
            )
            extra = f"{tot['serial_cycles']/1e6:>9.2f} {e_j*1e6:>10.2f}uJ"
        else:
            extra = f"{'-':>9} {'-':>12}"
        out["variants"][name] = entry
        print(f"{name:<26} {dt*1e3:>9.2f} {corr:>13.4f} {extra}")

    if write_json:
        emit(_OUT_E2E, out["backend"], out)
    return out


def bench_policy(fast: bool, write_json: bool) -> dict:
    """Mixed-policy e2e cell: uniform int8 vs the exploration paper's mixed
    deployment (attention int8 / MLP int2) on a decode step — per-bits cycle
    split, modeled 16×16-unit energy, and logits correlation vs fp32.
    Writes ``benchmarks/BENCH_policy.json``."""
    import dataclasses

    from repro.configs.base import RunConfig, get_config
    from repro.core.report import energy_report
    from repro.models import init, init_caches
    from repro.serve import build_decode, build_prefill

    cfg = get_config("qwen3-0.6b_smoke")
    rc0 = RunConfig(dtype="float32", param_dtype="float32", remat="none")
    params = init(cfg, rc0, jax.random.PRNGKey(0))
    B, T, cap = 4, 8, 32
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, T), 0, cfg.vocab_size)
    nxt = jnp.ones((B, 1), jnp.int32)
    pos = jnp.asarray(T, jnp.int32)
    iters = 5 if fast else 20

    policies = {
        "uniform_int8": "*=int8",
        "mixed_int8attn_int2mlp": "attn.*=int8,mlp.*=int2,*=bf16",
    }
    out: dict = {"backend": jax.default_backend(), "fast": fast, "policies": {}}
    ref_logits = None
    # fp32 reference logits for the correlation column
    caches = init_caches(cfg, rc0, B, cap)
    caches, _ = jax.jit(build_prefill(cfg, rc0))(params, caches, {"tokens": toks})
    ref_logits = np.asarray(jax.jit(build_decode(cfg, rc0))(params, caches, nxt, pos)[1])

    print(f"\n{'mixed-policy decode A/B':<26} {'ms/step':>9} {'corr':>7} "
          f"{'Mcyc(ser)':>10} {'energy/step':>12}  cycles by bits")
    for name, pol in policies.items():
        rc = dataclasses.replace(rc0, quant_policy=pol)
        caches = init_caches(cfg, rc, B, cap)
        caches, _ = jax.jit(build_prefill(cfg, rc))(params, caches, {"tokens": toks})
        dec = jax.jit(build_decode(cfg, rc, with_stats=True))
        res = dec(params, caches, nxt, pos)
        jax.block_until_ready(res)
        t0 = time.perf_counter()
        for _ in range(iters):
            res = dec(params, caches, nxt, pos)
        jax.block_until_ready(res)
        dt = (time.perf_counter() - t0) / iters
        corr = float(np.corrcoef(np.asarray(res[1]).ravel(), ref_logits.ravel())[0, 1])
        rep = energy_report(res[2], variant="serial")
        by_bits = {
            str(b): {"cycles": s["cycles"], "energy_j": s["energy_j"],
                     "layers": s["layers"]}
            for b, s in rep.by_bits.items()
        }
        out["policies"][name] = {
            "policy": pol,
            "ms_per_step": dt * 1e3,
            "corr_vs_fp32": corr,
            "serial_cycles": rep.total_cycles,
            "energy_j_16x16_serial": rep.unit_energy_j,
            "by_bits": by_bits,
        }
        bb = ", ".join(f"int{b}:{s['cycles']}" for b, s in sorted(by_bits.items(), reverse=True))
        print(f"{name:<26} {dt*1e3:>9.2f} {corr:>7.4f} {rep.total_cycles/1e6:>10.2f} "
              f"{rep.unit_energy_j*1e6:>10.2f}uJ  {bb}")

    u = out["policies"]["uniform_int8"]
    m = out["policies"]["mixed_int8attn_int2mlp"]
    if m["energy_j_16x16_serial"] > 0:
        out["mixed_energy_ratio"] = u["energy_j_16x16_serial"] / m["energy_j_16x16_serial"]
        print(f"mixed policy energy: {out['mixed_energy_ratio']:.2f}x less than uniform int8")
    if write_json:
        emit(_OUT_POLICY, out["backend"], out)
    return out


def run(fast: bool = False, write_json: bool | None = None) -> dict:
    # default: only full-shape runs refresh the committed BENCH_kernels.json —
    # a --fast run must never silently clobber the perf-trajectory baseline
    if write_json is None:
        write_json = not fast
    shapes = [(64, 64, 64), (128, 256, 128)] if fast else [
        (64, 64, 64), (128, 256, 128), (256, 512, 256), (512, 512, 512),
    ]
    out = {
        "backend": jax.default_backend(),
        "fast": fast,
        "exact": True,
        "timings": {},
    }
    bench_exactness(shapes, out)
    bench_fused_pipeline(shapes, out, iters=5 if fast else 10)
    bench_roofline_gate(fast, out, iters=5 if fast else 10)
    print(f"\nall kernels bit-exact: {out['exact']}")
    if write_json:
        emit(_OUT, out["backend"], out)
    else:
        # --fast must still prove the per-backend trajectory store works:
        # schema round-trip, history append, v1 migration — in memory only
        check_store_roundtrip(out["backend"], out)
    out["e2e"] = bench_e2e(fast, write_json)
    out["policy"] = bench_policy(fast, write_json)
    return out


if __name__ == "__main__":
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--fast", action="store_true", help="small shapes only")
    p.add_argument("--no-json", action="store_true", help="skip BENCH_kernels.json")
    args = p.parse_args()
    run(fast=args.fast, write_json=False if args.no_json else None)
