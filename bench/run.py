"""Benchmark harness: one run of one cell.

    python3 -m bench.run --workload <config>.<traffic> --seed N --seconds S --trace 0|1

The cell is looked up in ``BENCHMARK.json``; its configuration is
``bench/configs/<config>.json`` and its traffic ``bench/traffic/<traffic>.json``.
The configuration names its architecture family, ``bench/arch/<arch>.py``,
which holds what is particular to the block: the sizes checked against the
program's registry, the kernel names every run must trace, the reference and
the cost model.

The run builds the serving engine as the program's serve launcher does
(weights from ``--seed``, made on the device; quantization surgery; the
continuous-batching ``Scheduler``), warms both step widths, starts the
open-loop load, and after the warm-up measures ``--seconds`` seconds, driving
``Scheduler.submit`` and ``Scheduler.tick`` itself: each request is submitted
when it is due, and every token is stamped after the tick that emitted it.

After the window, a sample of the finished requests is checked against the
plain float32 reference of the family and the result is printed as one
JSON line, last on standard output. ``--trace 1`` runs the program's tracer
and the device profiler through the window and reports the per-layer metrics
of ``BENCHMARK.json`` (bench/metrics/<name>.py) instead of the end-to-end ones.

Exits 2, printing no result, where JAX finds no TPU or fewer chips than the
cell asks for.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()  # set-up is timed from here

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from contextlib import nullcontext  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import numpy as np  # noqa: E402

from bench import stats, traffic as traffic_mod  # noqa: E402


# ------------------------------------------------------------------ lookups
def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def workload(name: str) -> dict:
    for w in benchmark()["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json")


def config_file(name: str) -> dict:
    return load_json(BENCH / "configs" / f"{name}.json")


def traffic_file(name: str) -> dict:
    return load_json(BENCH / "traffic" / f"{name}.json")


def available(kind: str) -> list[str]:
    """Names of the configurations, traffic mixes, metric readers or
    architecture families on disk."""
    ext = ".py" if kind in ("metrics", "arch") else ".json"
    return sorted(p.name[: -len(ext)] for p in (BENCH / kind).glob(f"*{ext}")
                  if not p.name.startswith("_"))


def load_module(path: Path, name: str):
    """The module at ``path``, loaded once under ``name`` (in ``sys.modules``,
    so that its dataclasses resolve; loaded again where ``path`` differs)."""
    mod = sys.modules.get(name)
    if mod is not None and mod.__file__ == str(path):
        return mod
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str):
    """The module ``bench/metrics/<name>.py`` (names may hold dots)."""
    return load_module(BENCH / "metrics" / f"{name}.py", f"bench_metric_{name}")


def family(config: dict):
    """The architecture family ``bench/arch/<arch>.py`` that the configuration
    file names under ``arch`` (there is no default)."""
    if "arch" not in config:
        raise ValueError(f"{config['name']}: the configuration names no architecture "
                         f"family (\"arch\")")
    name = config["arch"]
    return load_module(BENCH / "arch" / f"{name}.py", f"bench_arch_{name}")


def per_layer_for(cell: str) -> list[dict]:
    return [m for m in benchmark()["per_layer"]
            if "workloads" not in m or cell in m["workloads"]]


def end_to_end_for(cell: str) -> list[dict]:
    return [m for m in benchmark()["end_to_end"]
            if "workloads" not in m or cell in m["workloads"]]


# ------------------------------------------------------------ model set-up
def model_config(config: dict):
    """The registry ModelConfig, cut as the file's ``reduced`` says (each key
    through the family's ``KEYS``, to the value the file holds); after the
    cut every size in ``KEYS`` has to match the registry's."""
    from repro.configs.base import get_config

    keys = family(config).KEYS
    cut = {}
    for key, r in config["reduced"].items():
        if key not in keys:
            raise ValueError(f"{config['name']}: reduced key {key!r} is not in the "
                             f"{config['arch']} family's KEYS")
        if r["here"] != config[key]:
            raise ValueError(f"{config['name']}: reduced {key} is {r['here']} here "
                             f"but the file holds {config[key]}")
        cut[keys[key]] = r["here"]
    cfg = get_config(config["registry"]).replace(**cut)
    for key, attr in keys.items():
        if getattr(cfg, attr) != config[key]:
            raise ValueError(f"{config['name']}: {key}={config[key]} but the "
                             f"registry's {attr} is {getattr(cfg, attr)}")
    return cfg


def run_config(config: dict, policy: str):
    from repro.configs.base import RunConfig
    from repro.quant.policy import load_policy

    kv, sv = config["kv"], config["serving"]
    return RunConfig(
        dtype="bfloat16", param_dtype="bfloat16", remat="none",
        kv_cache_dtype=kv["dtype"], kv_layout=kv["layout"],
        block_size=kv["block_size"], prefix_cache=False,
        prefill_chunk=sv["prefill_chunk"], token_budget=0,
        quant_policy=load_policy(policy), spec_gamma=0, draft_policy=None,
    )


def scheduler(cfg, rc, params, config: dict, seed: int, tracer=None):
    """An empty greedy Scheduler at the configuration's serving sizes."""
    from repro.serve import AdmissionController, Scheduler

    sv = config["serving"]
    return Scheduler(
        cfg, rc, params, capacity=sv["capacity"], max_batch=sv["max_batch"],
        num_pages=sv["num_pages"], temperature=0.0, seed=seed & 0x7FFFFFFF,
        admission=AdmissionController(), tracer=tracer,
    )


def build(config: dict, policy: str, seed: int, tracer=None):
    """(cfg, rc, scheduler) as the serve launcher builds them, with the
    benchmark's weights."""
    from repro.models import abstract_params
    from repro.quant import apply_surgery

    from bench.weights import make_weights

    cfg = model_config(config)
    rc = run_config(config, policy)
    params = make_weights(abstract_params(cfg, rc), seed)
    params = apply_surgery(cfg, rc, params)
    return cfg, rc, scheduler(cfg, rc, params, config, seed, tracer)


def kernel_faults(health: dict, path: str, family) -> list[str]:
    """Every quantized GEMM and the paged attention that ``family`` names
    must have been traced to ``path`` only, with no fallback."""
    k = health["kernels"]
    errs = [f"fallback {n}: {r}" for n, r in k["fallbacks"].items()]
    for name in (*family.GEMMS, family.ATTENTION):
        got = k["paths"].get(name)
        if not got:
            errs.append(f"{name} never traced")
        elif set(got) != {path}:
            errs.append(f"{name} traced to {sorted(got)}, want {path}")
    return errs


# ------------------------------------------------------------------ driving
@dataclass
class Rec:
    rid: int
    due: float                   # absolute, host perf_counter seconds
    prompt_len: int
    max_new: int
    fill: bool
    req: object = None
    submitted: float | None = None
    admitted: float | None = None
    times: list = field(default_factory=list)
    done: float | None = None
    rejected: bool = False


@dataclass
class Tick:
    t0: float
    t1: float


class Driver:
    """Open-loop load on a Scheduler, on the host's clock."""

    def __init__(self, sched, planned, t_load: float, *, annotate: bool):
        from repro.serve import Request

        self.sched = sched
        self.recs = [Rec(p.rid, t_load + p.due, len(p.prompt), p.max_new, p.fill,
                         req=Request(rid=p.rid, prompt=p.prompt.tolist(),
                                     max_new=p.max_new))
                     for p in planned]
        self.by_rid = {r.rid: r for r in self.recs}
        self.next = 0
        self.ticks: list[Tick] = []
        self.lateness: list[float] = []
        self.n_finished = 0
        self.annotate = annotate

    def submit_due(self, now: float) -> None:
        recs = self.recs
        while self.next < len(recs) and recs[self.next].due <= now:
            r = recs[self.next]
            r.submitted = time.perf_counter()
            if self.sched.submit(r.req) is not None:
                r.rejected = True
            self.lateness.append(r.submitted - r.due)
            self.next += 1

    def busy(self) -> bool:
        s = self.sched
        return any(x is not None for x in s.slots) or s.admission.pending() > 0

    def tick(self) -> None:
        sched = self.sched
        if self.annotate:
            import jax

            ctx = jax.profiler.TraceAnnotation("bench/tick")
        else:
            ctx = nullcontext()
        t0 = time.perf_counter()
        with ctx:
            sched.tick()
        t1 = time.perf_counter()
        self.ticks.append(Tick(t0, t1))
        by_rid = self.by_rid
        for sl in sched.slots:
            if sl is None:
                continue
            r = by_rid[sl.req.rid]
            if r.admitted is None:
                r.admitted = t0
            n = len(sl.req.out) - len(r.times)
            if n > 0:
                r.times.extend([t1] * n)
        fin = sched.finished
        while self.n_finished < len(fin):
            req = fin[self.n_finished]
            self.n_finished += 1
            r = by_rid.get(req.rid)
            if r is None:
                continue
            if r.admitted is None:
                r.admitted = t0
            n = len(req.out) - len(r.times)
            if n > 0:
                r.times.extend([t1] * n)
            r.done = t1

    def run_until(self, t_stop: float) -> None:
        while True:
            now = time.perf_counter()
            if now >= t_stop:
                return
            self.submit_due(now)
            if self.busy():
                self.tick()
            else:
                nxt = self.recs[self.next].due if self.next < len(self.recs) else t_stop
                time.sleep(max(0.0, min(nxt, t_stop) - time.perf_counter()))


def compile_cache() -> str:
    """Turn on the persistent compilation cache (the program's own choice of
    directory, inside the checkout unless ``JAX_COMPILATION_CACHE_DIR`` says
    otherwise) for every program, however quick to compile; returns it."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache_dir


def cached_programs(cache_dir: str) -> int:
    """Entries of the persistent compilation cache: a set-up that adds any
    has compiled."""
    d = Path(cache_dir) if cache_dir else None
    return sum(1 for _ in d.glob("*-cache")) if d and d.is_dir() else 0


def warm_compile(sched, config: dict, vocab: int) -> None:
    """Both step widths, and everything a tick runs, once: a request whose
    prompt takes one prefill tick (width prefill_chunk) and then decodes
    (width 1)."""
    from repro.serve import Request

    chunk = config["serving"]["prefill_chunk"]
    rng = np.random.default_rng(0)
    sched.submit(Request(rid=-1, prompt=rng.integers(0, vocab, chunk).tolist(),
                         max_new=3))
    sched.run()
    sched.finished.clear()


# --------------------------------------------------------------- the check
def sample_finished(recs, rng, min_tokens: int) -> list:
    """The longest finished request, then others in seeded order, until the
    sample holds ``min_tokens`` served tokens."""
    done = [r for r in recs if r.done is not None and not r.rejected]
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.req.out), r.rid))
    rest = [r for r in done if r is not longest]
    order = rng.permutation(len(rest))
    out, n = [longest], len(longest.req.out)
    for i in order:
        if n >= min_tokens:
            break
        out.append(rest[i])
        n += len(rest[i].req.out)
    return out


def check(config: dict, seed: int, sample) -> dict:
    """Reference gap of every served token of the sample (the program's state
    must be freed already)."""
    import jax

    from repro.models import abstract_params

    from bench.weights import make_weights

    reference = family(config)
    cfg = model_config(config)
    rc = run_config(config, config["quant_policy"])
    params = make_weights(abstract_params(cfg, rc), seed)
    worst, served, tokens_equal = 0.0, 0, 0
    for r in sample:
        g = reference.logit_gaps(params, config, r.req.prompt, r.req.out,
                                 config["serving"]["capacity"])
        worst = max(worst, float(g.max()))
        served += len(g)
        tokens_equal += int((g == 0).sum())
    del params
    jax.clear_caches()
    return {"max_logit_gap": worst, "served_tokens": served,
            "tokens_at_reference_best": tokens_equal, "requests": len(sample)}


# ----------------------------------------------------------------- one run
def device_info() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}


def run_cell(cell: str, config: dict, traffic: dict, *, seed: int, seconds: float,
             trace: bool, control: bool = False, expect_path: str = "pallas") -> dict:
    """One run; returns the result object (see module docstring)."""
    import jax

    cache_dir = compile_cache()
    cached = cached_programs(cache_dir)
    policy = config["control_policy"] if control else config["quant_policy"]
    tracer = None
    if trace:
        from repro.obs.trace import Tracer

        tracer = Tracer()
    cfg, rc, sched = build(config, policy, seed, tracer)
    vocab = cfg.vocab_size
    warm_compile(sched, config, vocab)
    compiled = cached_programs(cache_dir) > cached
    fam = family(config)
    faults = kernel_faults(sched.health(), expect_path, fam)
    sv = config["serving"]
    planned = traffic_mod.plan(traffic, seed=seed, seconds=seconds,
                               max_batch=sv["max_batch"], vocab=vocab)
    t_load = time.perf_counter()
    drv = Driver(sched, planned, t_load, annotate=trace)
    t_open = t_load + float(traffic["warm"]["seconds"])
    drv.run_until(t_open)
    setup_s = time.perf_counter() - T_PROCESS
    t_open = time.perf_counter()
    t_end = t_open + seconds
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    if trace:
        tr_mark = (time.perf_counter_ns(), tracer.ts())
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # Python's own calls would slow the host
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    drv.run_until(t_end)
    jax.block_until_ready(sched.caches)
    if trace:
        jax.profiler.stop_trace()
    t_close = time.perf_counter()
    mem = jax.devices()[0].memory_stats() or {}
    faults += [f"after the window: {e}"
               for e in kernel_faults(sched.health(), expect_path, fam)
               if "never traced" not in e]
    recs = drv.recs
    due_in = [r for r in recs if t_open <= r.due < t_end]
    out = {
        "policy": policy,
        "compiled": compiled,
        "setup_s": setup_s,
        "window_s": seconds,
        "overrun_s": t_close - t_end,
        "recs": recs, "ticks": drv.ticks, "t_open": t_open, "t_end": t_end,
        "attempted": len(due_in),
        "failed": sum(r.rejected for r in due_in),
        "lateness": drv.lateness,
        "memory_peak_bytes": int(mem.get("peak_bytes_in_use", 0)),
        "kernel_faults": faults,
    }
    if trace:
        out["spans"] = tracer.to_dict()["traceEvents"]
        out["tracer_offset_s"] = tr_mark[0] / 1e9 - tr_mark[1] / 1e6
        out["trace_dir"] = trace_dir
    rng = np.random.default_rng(seed)
    sample = sample_finished(recs, rng, traffic["check_tokens"])
    del sched, drv
    gc.collect()
    jax.clear_caches()
    t_chk = time.perf_counter()
    out["check"] = check(config, seed, sample) if sample else {
        "max_logit_gap": None, "served_tokens": 0, "tokens_at_reference_best": 0,
        "requests": 0}
    out["check_s"] = time.perf_counter() - t_chk
    return out


def end_to_end(res: dict) -> dict:
    recs, a, b = res["recs"], res["t_open"], res["t_end"]
    ttft = stats.ttft_samples(recs, a, b)
    itl = stats.itl_samples(recs, a, b)
    toks = stats.tokens_in(recs, a, b)
    return {
        "ttft_p95_s": (stats.percentile(ttft, 95), "s"),
        "itl_p95_s": (stats.percentile(itl, 95), "s"),
        "output_tok_s": (toks / res["window_s"], "tokens/s"),
        "setup_s": (res["setup_s"], "s"),
    }, {
        "ttft_p50_s": stats.percentile(ttft, 50), "ttft_n": len(ttft),
        "itl_p50_s": stats.percentile(itl, 50), "itl_n": len(itl),
        "output_tokens": toks, "ticks": sum(1 for t in res["ticks"] if a <= t.t0 < b),
        "lateness_p95_s": stats.percentile(res["lateness"], 95),
        "completed_in_window": sum(1 for r in recs if r.done is not None and a <= r.done < b),
        "compiled_in_setup": res["compiled"],
    }


def verdict(res: dict, config: dict, traffic: dict) -> tuple[bool, dict]:
    """``correct`` and the numbers compared, each beside its limit."""
    c = res["check"]
    limit = config["check"]["max_logit_gap"]
    cmp = {
        "max_logit_gap": {"value": c["max_logit_gap"], "limit": limit},
        "served_tokens_checked": {"value": c["served_tokens"],
                                  "limit": traffic["check_tokens"]},
        "kernel_faults": {"value": len(res["kernel_faults"]), "limit": 0},
    }
    ok = (c["max_logit_gap"] is not None and limit is not None
          and c["max_logit_gap"] <= limit
          and c["served_tokens"] >= traffic["check_tokens"]
          and not res["kernel_faults"])
    return ok, cmp


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="serve at the configuration's control_policy, the "
                         "next lower precision (for setting the limit; the "
                         "benchmark's own runs never pass it)")
    args = ap.parse_args(argv)

    w = workload(args.workload)
    config, traffic = config_file(w["config"]), traffic_file(w["traffic"])

    device = device_info()
    if device["platform"] != "tpu" or device["count"] < w["chips"]:
        print(f"[bench] needs {w['chips']} TPU chip(s); JAX found {device}",
              file=sys.stderr)
        return 2
    res = run_cell(args.workload, config, traffic, seed=args.seed,
                   seconds=args.seconds, trace=bool(args.trace),
                   control=args.control)
    correct, cmp = verdict(res, config, traffic)
    device["memory_peak_bytes"] = res["memory_peak_bytes"]
    if args.trace:
        from bench import layers

        metrics, breakdown, dev = layers.per_layer(args.workload, res, config, device)
        device.update(dev)
    else:
        e2e, extra = end_to_end(res)
        names = {m["name"] for m in end_to_end_for(args.workload)}
        metrics = {k: {"value": v, "unit": u} for k, (v, u) in e2e.items()
                   if k in names and v is not None}
        others = {k: v for k, (v, _) in e2e.items() if k not in names}
        print(f"[bench] {args.workload} seed {args.seed}: " + json.dumps({**extra, **others}),
              file=sys.stderr)
    print(f"[bench] check {json.dumps(res['check'])}; reference {res['check_s']:.1f}s; "
          f"window overran by {res['overrun_s']:.3f}s", file=sys.stderr)
    for f in res["kernel_faults"]:
        print(f"[bench] kernel fault: {f}", file=sys.stderr)
    for k, v in cmp.items():
        print(f"[bench] compared {k}: {v['value']} (limit {v['limit']})", file=sys.stderr)
    line = {"correct": correct, "attempted": res["attempted"], "failed": res["failed"],
            "metrics": metrics, "device": device}
    if args.trace:
        line["breakdown"] = breakdown
    line["check"] = cmp
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
