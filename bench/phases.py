"""Device idle time under the program's own tick phases, on the profiler's
clock.

While its tracer is on, the scheduler opens a ``jax.profiler.TraceAnnotation``
named ``serve/<span>`` around every scheduler-track span (obs/trace.py): the
``tick``, its phases (``admit``, ``plan``, ``cow_drain``, ``tables``,
``device_step``, ``commit``) and their sub-spans (``step_inputs``,
``step_launch``, ``step_wait``, ``logits_fetch``, ``logits_widen`` inside
``device_step``; ``logits_check``, ``sample``, ``emit`` inside ``commit``).
They land on a host line of the profile, on the same clock as the device's
module executions, so device idle time is put under the innermost span the
host was in with no clock offset.

    python3 -m bench.phases --workload <cell> --seed N --seconds S

runs one traced window of the cell as ``python3 -m bench.run --trace 1``
does and prints one JSON line: the traced run's end-to-end metrics (set them
beside an untraced run of the same seed for what tracing costs when on), the
cell's per-layer metrics and breakdown as the harness reads them, and the
readings of this module. Exits 2 where JAX finds no TPU.

Times here are nanoseconds on the profile's clock, as in bench/xtrace.py.
"""

from __future__ import annotations

import argparse
import bisect
import glob
import json
import shutil
import sys

from bench import layers, xtrace

PREFIX = "serve/"
# host work on the logits after the step: the copy to the host, the f32
# widening, the finite scan and sampling
LOGITS_HOST = ("logits_fetch", "logits_widen", "logits_check", "sample")
OUTSIDE = "tick, outside its phases"
BETWEEN = "between ticks"


def annotations(pd) -> list[tuple[float, float, str]]:
    """(start, end, name) of every ``serve/*`` annotation on the host planes
    of a ``jax.profiler.ProfileData``, the prefix cut, outer spans first."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host"):
            continue
        for ln in plane.lines:
            out += [(e.start_ns, e.start_ns + e.duration_ns, e.name[len(PREFIX):])
                    for e in ln.events if e.name.startswith(PREFIX)]
    return sorted(out, key=lambda a: (a[0], -a[1]))


def labelled(anns) -> list[tuple[float, float, str]]:
    """Each annotation labelled by where it nests: ``tick <phase>``, ``tick
    <phase>/<sub-span>``, and the tick itself as "tick, outside its phases"."""
    out, stack = [], []
    for s, e, name in anns:
        while stack and stack[-1][1] <= s:
            stack.pop()
        path = [n for _, _, n in stack] + [name]
        stack.append((s, e, name))
        if path[0] != "tick":
            label = "/".join(path)
        elif len(path) == 1:
            label = OUTSIDE
        else:
            label = "tick " + "/".join(path[1:])
        out.append((s, e, label))
    return out


def innermost(intervals) -> list[tuple[float, float, str]]:
    """Disjoint pieces of the union of labelled intervals, each under the
    innermost interval covering it (the latest to start; of two that start
    together, the shorter), in order."""
    evs = sorted(intervals, key=lambda iv: (iv[0], -iv[1]))
    points = sorted({p for s, e, _ in evs for p in (s, e)})
    out, active, j = [], [], 0
    for a, b in zip(points, points[1:]):
        while j < len(evs) and evs[j][0] <= a:
            active.append(evs[j])
            j += 1
        active = [iv for iv in active if iv[1] > a]
        if not active:
            continue
        label = max(active, key=lambda iv: (iv[0], -iv[1]))[2]
        if out and out[-1][1] == a and out[-1][2] == label:
            out[-1] = (out[-1][0], b, label)
        else:
            out.append((a, b, label))
    return out


def _busy(modules) -> list[tuple[float, float]]:
    return [(m.start, m.start + m.dur) for m in modules]


def idle_by_span(intervals, modules, lo: float, hi: float) -> list[list]:
    """Device idle seconds in [lo, hi), each instant under the innermost
    labelled interval the host was in (``labelled`` annotations, plus any
    ``compile`` spans); idle outside them all is "between ticks". Largest
    first."""
    pieces = innermost(intervals)
    starts = [p[0] for p in pieces]
    by: dict = {}
    for a, b in xtrace.gaps(_busy(modules), lo, hi):
        i = max(bisect.bisect_right(starts, a) - 1, 0)
        covered = 0.0
        while i < len(pieces) and pieces[i][0] < b:
            s, e, label = pieces[i]
            ov = min(b, e) - max(a, s)
            if ov > 0:
                by[label] = by.get(label, 0.0) + ov
                covered += ov
            i += 1
        by[BETWEEN] = by.get(BETWEEN, 0.0) + (b - a) - covered
    return [[k, v / 1e9] for k, v in sorted(by.items(), key=lambda kv: -kv[1]) if v > 0]


def device_step_idle_ms(anns, modules) -> float | None:
    """Device idle milliseconds while the host is inside ``device_step``,
    per tick (every ``tick`` annotation of the capture)."""
    ticks = sum(1 for *_, n in anns if n == "tick")
    steps = [(s, e) for s, e, n in anns if n == "device_step"]
    if not ticks or not steps:
        return None
    busy = _busy(modules)
    idle = sum(b - a for s, e in steps for a, b in xtrace.gaps(busy, s, e))
    return idle / 1e6 / ticks


def host_ms_per_tick(res: dict) -> dict:
    """Host milliseconds per tick in each scheduler-track span of the
    tracer's record, over the ticks that started in the window, each span
    counted in the tick it started in."""
    off = res["tracer_offset_s"]
    sched = [(off + e["ts"] / 1e6, e["dur"] / 1e3, e["name"]) for e in res["spans"]
             if e.get("ph") == "X" and e["pid"] == layers.PID_SCHED]
    ticks = sorted((s, s + d / 1e3) for s, d, n in sched
                   if n == "tick" and res["t_open"] <= s < res["t_end"])
    if not ticks:
        return {}
    starts = [t0 for t0, _ in ticks]
    total: dict = {}
    for s, ms, name in sched:
        i = bisect.bisect_right(starts, s) - 1
        if name != "tick" and i >= 0 and s <= ticks[i][1]:
            total[name] = total.get(name, 0.0) + ms
    return {k: v / len(ticks) for k, v in sorted(total.items())}


def logits_host_ms(res: dict) -> float | None:
    """Host milliseconds per tick in ``logits_fetch`` + ``logits_widen`` +
    ``logits_check`` + ``sample``."""
    per = host_ms_per_tick(res)
    if not any(n in per for n in LOGITS_HOST):
        return None
    return sum(per.get(n, 0.0) for n in LOGITS_HOST)


def read(res: dict, config: dict, device_kind: str, pd) -> tuple:
    """(the harness's per-layer context, this module's readings and the
    harness's breakdown) of one traced run."""
    trace = xtrace.from_profile(pd)
    ctx, offset, spans = layers.build(res, config, device_kind, trace)
    anns = annotations(pd)
    lo, hi = ctx.t_open * 1e9 + offset, ctx.t_end * 1e9 + offset
    compiles = [(s * 1e9 + offset, (s + d) * 1e9 + offset, "compile")
                for n, pid, _, s, d, _ in spans if pid == layers.PID_SCHED and n == "compile"]
    return ctx, {
        "device_step_idle_ms": device_step_idle_ms(anns, trace.modules),
        "logits_host_ms": logits_host_ms(res),
        "host_ms_per_tick": host_ms_per_tick(res),
        "idle_by_span": idle_by_span(labelled(anns) + compiles, trace.modules, lo, hi),
        "compiles_in_window": sum(1 for s, _, _ in compiles if lo <= s < hi),
        "annotated_ticks": sum(1 for *_, n in anns if n == "tick"),
        "breakdown": layers.breakdown(ctx, trace, offset, spans),
    }


def main(argv=None) -> int:
    from bench import run

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    w = run.workload(args.workload)
    config, traffic = run.config_file(w["config"]), run.traffic_file(w["traffic"])
    device = run.device_info()
    if device["platform"] != "tpu" or device["count"] < w["chips"]:
        print(f"[phases] needs {w['chips']} TPU chip(s); JAX found {device}", file=sys.stderr)
        return 2
    res = run.run_cell(args.workload, config, traffic, seed=args.seed,
                       seconds=args.seconds, trace=True)
    correct, _ = run.verdict(res, config, traffic)
    e2e, extra = run.end_to_end(res)
    try:
        from jax.profiler import ProfileData

        (path,) = glob.glob(f"{res['trace_dir']}/plugins/profile/*/*.xplane.pb")
        ctx, got = read(res, config, device["kind"], ProfileData.from_file(path))
    finally:
        shutil.rmtree(res["trace_dir"], ignore_errors=True)
    per_layer = {m["name"]: run.metric_reader(m["name"]).read(ctx)
                 for m in run.per_layer_for(args.workload)}
    line = {"workload": args.workload, "seed": args.seed, "correct": correct,
            "end_to_end": {k: v for k, (v, _) in e2e.items()}, "extra": extra,
            "per_layer": per_layer, "busy_s": ctx.busy_s, "window_s": ctx.window_s,
            **got, "device": device}
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
