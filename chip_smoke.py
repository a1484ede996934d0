"""Smoke run of the serving main path on a TPU v5e.

Drives the continuous-batching Scheduler exactly as ``python -m
repro.launch.serve`` builds it (``serve.build_engine``), at the full
published width of qwen3-0.6b (28 layers, weights random from ``--seed``),
through chunked prefill and decode to completion. Phases on one chip:

  k  each compiled Pallas kernel of the main path against its XLA twin on
     the same inputs, at the model's widths
  a  paged bf16 KV, dynamic int8 GEMMs (``--gemm-backend int8``)
  b  as a, with int8 KV (``--kv-dtype int8``)
  c  a mixed prequant policy: int8 attention, plane-packed int2 MLP
  d  reference: a with the XLA twins forced for every GEMM and for paged
     attention, on the same chip with the same weights

Checks: k's GEMMs agree bit for bit and its attention within ``ATTN_RTOL``;
every phase completes with finite logits; a-c trace every quantized GEMM
and ``attn.paged`` to Pallas with no fallback (d traces all to XLA); a's
first-step logits agree with d's within ``LOGIT_RTOL``, and each request's
first greedy token is equal (the reference's top-2 margin is printed beside
it, since bf16 rounding can decide a token whose margin is that small).

``--mesh 2,2`` runs instead phase a over a dp x tp mesh of four chips, and
the one-chip run it is compared with (paged attention on the XLA gather
path on both sides, since mesh programs take that path); it reports whether
the greedy tokens are equal and the largest logit difference.

Times and memory printed are smoke output, not benchmark numbers. The last
line of stdout is one JSON object, printed only on a TPU; ``"ok": true``
only on a TPU v5e with every check passed. Off the chip, ``--arch
qwen3-0.6b_smoke`` rehearses the phases on the CPU (kernels in interpret
mode) and ends not ok.

    python3 chip_smoke.py [--mesh 2,2] [--arch ARCH] [--seed N]
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# a vs d first-step logits: max |difference| over max |reference logit|.
# Both arms run bf16 activations, and the kernels and their twins round
# differently in places (attention's accumulation order, XLA's fusion of the
# ops around each kernel). An int8 activation rounding that such a one-ulp
# difference flips changes a GEMM output by a whole quantization step, so
# through 28 quantized layers of random weights the difference grows from
# ~1e-3 to a few percent (PERF.md, Findings). A kernel fault shows in
# phase k, exactly; this bound catches a path that computes something else.
LOGIT_RTOL = 0.15
# phase k attention: output max |difference| over max |twin output| (bf16
# outputs; online softmax vs the gather twin's blockwise softmax)
ATTN_RTOL = 2e-2

# 4 requests of 300-token prompts: three 128-column prefill chunks each,
# then decode; one 512-token slot of 16-token pages per request
PROMPT, CHUNK, REQUESTS, MAX_NEW = 300, 128, 4, 16
SERVE_ARGS = [
    "--kv-layout", "paged", "--requests", str(REQUESTS),
    "--prompt-len", str(PROMPT), "--max-new", str(MAX_NEW),
    "--max-batch", str(REQUESTS), "--capacity", "512",
    "--prefill-chunk", str(CHUNK), "--block-size", "16",
]

# name -> (serve arguments, forced paged-attention impl or None for auto)
PHASES = {
    "a": (["--gemm-backend", "int8"], None),
    "b": (["--gemm-backend", "int8", "--kv-dtype", "int8"], None),
    "c": (["--policy", "attn.*=int8:prequant:per_token,"
                       "mlp.*=int2:prequant:per_token,*=bf16"], None),
    "d": (["--policy", "*=int8:xla"], "xla"),
}


def is_v5e(kind: str) -> bool:
    kind = kind.lower()
    return "v5 lite" in kind or "v5e" in kind or "v5litepod" in kind


def kernel_phase(cfg, pallas: str) -> list[str]:
    """Phase k: the fused GEMM (dynamic int8, prequant int8/int4/int2 with
    per-token scales and stats) and paged attention (bf16 and int8 KV, step
    widths 1 and CHUNK) on the ``pallas`` impl vs the XLA twin."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops
    from repro.kernels.flash_paged import flash_paged_decode
    from repro.models.attention import KVView, _quantize_kv, kv_cache_read
    from repro.models.flash import blockwise_attention
    from repro.quant.quantize import compute_scale, quantize

    r = np.random.default_rng(0)
    bf16 = jnp.bfloat16
    d, ff = cfg.d_model, cfg.d_ff
    x = jnp.asarray(r.standard_normal((4 * CHUNK, d)), bf16)
    w = jnp.asarray(0.02 * r.standard_normal((d, ff)), bf16)
    errs = []
    for bits, prequant, per_token in [(8, False, False), (8, True, True),
                                      (4, True, True), (2, True, True)]:
        sw = compute_scale(w, bits, axis=1)
        wk = ops.pack_weights(quantize(w, sw.reshape(1, -1), bits), bits) if prequant else w
        kw = dict(sx=compute_scale(x, bits, axis=0 if per_token else None), sw=sw,
                  bits=bits, w_quantized=prequant, collect_stats=True)
        got, want = (jax.tree.leaves(ops.matmul_fused(x, wk, impl=i, **kw))
                     for i in (pallas, "xla"))
        same = all(np.array_equal(g, t) for g, t in zip(got, want))
        label = f"gemm int{bits} {'prequant' if prequant else 'dynamic'}" + (
            " per-token" if per_token else "")
        print(f"[smoke] k {label} {d}x{ff}: bit-identical to the twin: {same}")
        if not same:
            errs.append(f"k: {label} differs from its XLA twin")

    kv, hd, heads, bs, pages = cfg.num_kv_heads, cfg.resolved_head_dim, cfg.num_heads, 16, 128
    layers = 2   # a layer-stacked pool, read at its last layer
    tables = jnp.asarray(r.permutation(pages).reshape(4, pages // 4), jnp.int32)
    for int8 in (False, True):
        cache = {}
        for n in ("k", "v"):
            data = jnp.asarray(
                r.standard_normal((layers * (pages + 1), bs, kv * hd)), bf16)
            if int8:
                q8, s8 = _quantize_kv(data)
                cache[n] = q8.reshape(layers, pages + 1, bs, kv * hd)
                cache[n + "_scale"] = s8.reshape(layers, pages + 1, bs)
            else:
                cache[n] = data.reshape(layers, pages + 1, bs, kv * hd)
        for sq, pos in ((1, [0, 37, 300, 510]), (CHUNK, [0, 128, 200, 384])):
            view = KVView(jnp.asarray(pos, jnp.int32), jnp.full((4,), sq, jnp.int32),
                          tables, block_size=bs, layout="paged", layer=layers - 1)
            q = jnp.asarray(r.standard_normal((4, sq, heads, hd)), bf16)
            out = flash_paged_decode(
                q, (cache["k"],), (cache.get("k_scale"),), cache["v"],
                cache.get("v_scale"), view.tables, view.pos, view.kv_len, view.layer,
                kv_heads=kv, interpret=pallas == "pallas_interpret")
            k_full, v_full = (kv_cache_read(cache, n, bf16, kv_len=view.kv_len, view=view)
                              .reshape(4, -1, kv, hd) for n in ("k", "v"))
            ref = blockwise_attention(q, k_full, v_full, q_offset=view.pos,
                                      kv_len=view.kv_len)
            o, t = np.asarray(out, np.float32), np.asarray(ref, np.float32)
            rel = float(np.abs(o - t).max() / np.abs(t).max())
            label = f"paged attention {'int8' if int8 else 'bf16'} KV, step width {sq}"
            print(f"[smoke] k {label}: max|diff|/max|twin| {rel:.3g} "
                  f"(tolerance {ATTN_RTOL})")
            if not rel <= ATTN_RTOL:
                errs.append(f"k: {label} differs from its twin by {rel:.3g}")
    return errs


def run_phase(name, arch, seed, extra, *, paged_impl=None):
    """Serve the synthetic requests through ``serve.build_engine``; returns
    a result dict (tokens by rid, per-tick logits, kernel counters)."""
    import jax
    import numpy as np

    from repro.kernels.flash_paged import set_paged_impl
    from repro.launch import serve
    from repro.launch.mesh import make_local_mesh
    from repro.parallel.sharding import use_mesh

    args = serve.parse_args(["--arch", arch, "--seed", str(seed), *SERVE_ARGS, *extra])
    ticks = []   # (rids, their logits) per tick
    set_paged_impl(paged_impl)
    try:
        with use_mesh(make_local_mesh(args.data, args.model)):
            t0 = time.perf_counter()
            cfg, rc, eng, _ = serve.build_engine(args)
            eng.logits_hook = lambda rids, lg: ticks.append((rids, lg))
            for req in serve.synthetic_requests(cfg, args):
                if eng.submit(req) is not None:
                    raise RuntimeError(f"request {req.rid} rejected")
            t1 = time.perf_counter()
            eng.tick()                       # compiles the prefill step
            t2 = time.perf_counter()
            done = {r.rid: list(r.out) for r in eng.run()}
            jax.block_until_ready(eng.caches)
            t3 = time.perf_counter()
            health = eng.health()
    finally:
        set_paged_impl(None)
    del eng
    gc.collect()
    n_tok = sum(len(v) for v in done.values())
    lat = health["latency"]
    mem = jax.devices()[0].memory_stats() or {}
    print(f"[smoke] phase {name}: {' '.join(extra)}")
    print(f"  kernels: {json.dumps(health['kernels'], sort_keys=True)}")
    print(f"  smoke output, not benchmark numbers: build {t1 - t0:.2f}s, "
          f"first tick (prefill compile) {t2 - t1:.2f}s, remaining ticks "
          f"(decode compile) {t3 - t2:.2f}s, {n_tok} tokens; ttft p50 "
          f"{lat['ttft_s']['p50']:.4f}s, itl p50 {lat['itl_s']['p50']:.4f}s "
          f"(n={lat['itl_s']['count']}); bytes_in_use "
          f"{mem.get('bytes_in_use', 'n/a')}, peak_bytes_in_use "
          f"{mem.get('peak_bytes_in_use', 'n/a')}")
    return {"phase": name, "tokens": done, "ticks": ticks,
            "kernels": health["kernels"]}


def check_phase(res, *, expect, allow_fallback=()):
    """Failure strings for one phase (empty list = passed)."""
    import numpy as np

    errs = []
    name = res["phase"]
    if len(res["tokens"]) != REQUESTS or any(
            len(t) != MAX_NEW for t in res["tokens"].values()):
        errs.append(f"{name}: not every request completed {MAX_NEW} tokens")
    if not all(np.isfinite(lg).all() for _, lg in res["ticks"]):
        errs.append(f"{name}: non-finite logits")
    paths = res["kernels"]["paths"]
    if "attn.paged" not in paths or len(paths) < 2:
        errs.append(f"{name}: attn.paged or the GEMMs never traced: {paths}")
    for gemm, by in paths.items():
        want = "xla" if gemm in allow_fallback else expect
        if set(by) != {want}:
            errs.append(f"{name}: {gemm} traced to {sorted(by)}, want {want}")
    fb = {g: r for g, r in res["kernels"]["fallbacks"].items()
          if g not in allow_fallback}
    if fb:
        errs.append(f"{name}: fallbacks {fb}")
    return errs


def _nth_logits(res, rid, n):
    """Logits of request ``rid`` on the n-th tick that scheduled it."""
    seen = [lg[rids.index(rid)] for rids, lg in res["ticks"] if rid in rids]
    return seen[n]


def compare(res, ref, *, rtol):
    """Failure strings for res against ref: first-step logits within rtol,
    first greedy tokens equal."""
    import numpy as np

    tag = f"{res['phase']} vs {ref['phase']}"
    (rids, lg0), (rids_ref, ref0) = res["ticks"][0], ref["ticks"][0]
    if rids != rids_ref:
        return [f"{tag}: first steps scheduled different requests"]
    diff = float(np.abs(lg0 - ref0).max())
    rel = diff / float(np.abs(ref0).max())
    rms = float(np.sqrt(np.mean((lg0 - ref0) ** 2) / np.mean(ref0 ** 2)))
    print(f"[smoke] {tag}: first-step max|dlogit| {diff:.6g}, relative "
          f"{rel:.4g} (tolerance {rtol}), rms relative {rms:.4g}, "
          f"bit-identical {diff == 0.0}; all tokens equal "
          f"{res['tokens'] == ref['tokens']}")
    errs = [] if rel <= rtol else [f"{tag}: first-step logits differ by {rel:.4g} > {rtol}"]
    # the first token is sampled on the tick that ends the prompt
    n = -(-PROMPT // CHUNK) - 1
    for rid in sorted(ref["tokens"]):
        got, want = res["tokens"][rid][0], ref["tokens"][rid][0]
        lg, lr = _nth_logits(res, rid, n), _nth_logits(ref, rid, n)
        if int(lg.argmax()) != got or int(lr.argmax()) != want:
            errs.append(f"{tag}: request {rid}'s first token is not the argmax "
                        f"of its {n + 1}th-tick logits")
            continue
        top2 = np.sort(lr)[-2:]
        margin, delta = float(top2[1] - top2[0]), float(np.abs(lg - lr).max())
        print(f"[smoke] {tag}: request {rid} first token {got} vs {want}: "
              f"{'equal' if got == want else 'DIFFER'} (reference top-2 margin "
              f"{margin:.4g}, max|dlogit| {delta:.4g})")
        if got != want:
            errs.append(f"{tag}: request {rid}'s first greedy token differs")
    return errs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mesh", default=None, metavar="DP,TP",
                    help="run only the sharded phase over DP*TP chips and "
                         "its one-chip comparison, e.g. 2,2")
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    on_tpu = dev.platform == "tpu"
    print(f"[smoke] device {device}")
    if not on_tpu and not args.arch.endswith("_smoke"):
        print(f"[smoke] no TPU ({dev.platform}); only a *_smoke arch may be "
              "rehearsed off the chip", file=sys.stderr)
        return 2

    from repro.configs.base import get_config
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = Path(enable_compile_cache())

    def entries():
        return len(list(cache_dir.glob("*"))) if cache_dir.is_dir() else 0

    print(f"[smoke] compile cache {cache_dir}: {entries()} entries at start")
    errs: list[str] = []
    try:
        if args.mesh:
            one = run_phase("one-chip", args.arch, args.seed, PHASES["a"][0],
                            paged_impl="xla")
            mesh = run_phase(f"mesh {args.mesh}", args.arch, args.seed,
                             [*PHASES["a"][0], "--mesh", args.mesh])
            for res in (one, mesh):
                errs += check_phase(res, expect="pallas", allow_fallback=("attn.paged",))
            errs += compare(mesh, one, rtol=LOGIT_RTOL)
        else:
            errs += kernel_phase(get_config(args.arch),
                                 "pallas" if on_tpu else "pallas_interpret")
            res = {}
            for name, (extra, paged_impl) in PHASES.items():
                res[name] = run_phase(name, args.arch, args.seed, extra,
                                      paged_impl=paged_impl)
                errs += check_phase(res[name], expect="xla" if name == "d" else "pallas")
                if name not in "ad":
                    del res[name]
            errs += compare(res["a"], res["d"], rtol=LOGIT_RTOL)
    except Exception as e:  # noqa: BLE001 - a phase that raises fails the run
        import traceback

        traceback.print_exc()
        errs.append(f"phase raised {type(e).__name__}: {e}")

    print(f"[smoke] compile cache {cache_dir}: {entries()} entries at end")
    if not on_tpu:
        errs.append(f"platform is {dev.platform}, not tpu")
    elif not is_v5e(dev.device_kind):
        errs.append(f"device_kind {dev.device_kind!r} is not a TPU v5e")
    for e in errs:
        print(f"[smoke] FAIL {e}")
    if not on_tpu:
        print("[smoke] rehearsal off the chip: not ok", file=sys.stderr)
        return 1
    print(json.dumps({"ok": not errs, "device": device}))
    return 0 if not errs else 1


if __name__ == "__main__":
    sys.exit(main())
