"""Knee sweep: the same cell's traffic at several fixed rates, in one process.

    python3 -m bench.sweep --config qwen3-0.6b --traffic chat --rates 1,2,3 --seconds 20

Builds the engine once, then for each rate starts an empty scheduler on the
same weights, offers the mix at that rate (warm-up as the mix says, then
``--seconds`` measured) and prints one JSON line: offered and completed
tokens per second, the TTFT and inter-token tails, and the queue left at the
end. The knee is the highest rate whose queue stays bounded and whose
completed tokens still follow the offered ones; in a window long enough for
the load to settle, the requests completed per second in its second half at
a rate above the knee measure the knee itself. A cell's rate is fixed in its
traffic file from such a sweep; the benchmark's runs never search for one.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time

from bench import run, stats, traffic as traffic_mod


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True, help="comma-separated mixes")
    ap.add_argument("--rates", required=True,
                    help="per mix, comma-separated rates; mixes separated by ';'")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)

    import jax

    if jax.devices()[0].platform != "tpu":
        print("[sweep] needs a TPU", file=sys.stderr)
        return 2
    cache_dir = run.compile_cache()
    cached = run.cached_programs(cache_dir)
    config = run.config_file(args.config)
    cfg, rc, sched = run.build(config, config["quant_policy"], args.seed)
    params = sched.params
    sv = config["serving"]
    for mix, rates in zip(args.traffic.split(","), args.rates.split(";")):
        base = run.traffic_file(mix)
        for rate in (float(r) for r in rates.split(",")):
            del sched
            gc.collect()
            sched = run.scheduler(cfg, rc, params, config, args.seed)
            run.warm_compile(sched, config, cfg.vocab_size)
            tr = dict(base, arrival=dict(base["arrival"], rate=rate))
            planned = traffic_mod.plan(tr, seed=args.seed, seconds=args.seconds,
                                       max_batch=sv["max_batch"], vocab=cfg.vocab_size)
            t0 = time.perf_counter()
            drv = run.Driver(sched, planned, t0, annotate=False)
            a = t0 + float(tr["warm"]["seconds"])
            b = a + args.seconds
            half = a + args.seconds / 2
            drv.run_until(half)
            queued_mid = sched.admission.pending()
            drv.run_until(b)
            due = [p for p in planned if not p.fill and a - t0 <= p.due < b - t0]
            ttft = stats.ttft_samples(drv.recs, a, b)
            itl = stats.itl_samples(drv.recs, a, b)
            row = {
                "mix": mix, "rate": rate,
                "offered_tok_s": sum(p.max_new for p in due) / args.seconds,
                "output_tok_s": stats.tokens_in(drv.recs, a, b) / args.seconds,
                "ttft_p50_s": stats.percentile(ttft, 50),
                "ttft_p95_s": stats.percentile(ttft, 95),
                "itl_p50_s": stats.percentile(itl, 50),
                "itl_p95_s": stats.percentile(itl, 95),
                "requests": len(ttft),
                "completed_per_s_2nd_half": sum(
                    1 for r in drv.recs if r.done is not None and half <= r.done < b)
                / (b - half),
                "queued_mid": queued_mid,
                "queued_at_end": sched.admission.pending(),
                "active_at_end": sum(s is not None for s in sched.slots),
                "ticks": sum(1 for t in drv.ticks if a <= t.t0 < b),
                "preemptions": sched.preemptions,
                "compiled_in_process": run.cached_programs(cache_dir) > cached,
            }
            print(json.dumps(row), flush=True)
            del drv
    return 0


if __name__ == "__main__":
    sys.exit(main())
