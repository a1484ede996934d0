"""Per-layer metrics of a traced run, and its breakdown.

Puts the three records of a traced window on one clock: the harness's ticks
and requests (host ``perf_counter``), the program's tracer spans (tick phases
``admit``/``plan``/``cow_drain``/``device_step``/``commit``, and a
``prefill``/``decode`` span for every scheduled row with its position and
token count), and the device trace (bench/xtrace.py). Each per-layer metric
of ``BENCHMARK.json`` is then read by its own module, bench/metrics/<name>.py,
from a :class:`Ctx`; a reader that finds nothing to read returns None and the
metric is left out of the line.
"""

from __future__ import annotations

import bisect
import shutil
from dataclasses import dataclass, field

from bench import costs, xtrace

PID_SCHED, PID_REQUESTS = 1, 2   # the program tracer's process ids
PHASES = ("admit", "plan", "cow_drain", "device_step", "commit")


@dataclass
class TickView:
    t0: float                     # host seconds
    t1: float
    width: int | None = None      # step width the tick ran (None: no step)
    rows: list = field(default_factory=list)     # (pos, tokens) per scheduled row
    phases: dict = field(default_factory=dict)   # phase -> host seconds
    modules: list = field(default_factory=list)  # xtrace.Module run in this tick


@dataclass
class Ctx:
    config: dict
    policy: str
    peaks: dict
    t_open: float
    t_end: float
    window_s: float
    recs: list
    ticks: list                   # TickView of every tick that started in the window
    busy_s: float

    @property
    def chunk(self) -> int:
        return self.config["serving"]["prefill_chunk"]

    def ticks_of_width(self, width: int) -> list:
        return [t for t in self.ticks if t.width == width]


def _spans(res: dict) -> list[tuple]:
    """(name, pid, tid, start_s, dur_s, args) of the tracer's complete spans,
    on the host clock."""
    off = res["tracer_offset_s"]
    return [(e["name"], e["pid"], e["tid"], off + e["ts"] / 1e6, e["dur"] / 1e6,
             e.get("args") or {})
            for e in res["spans"] if e.get("ph") == "X"]


def build(res: dict, config: dict, device_kind: str,
          trace: xtrace.Trace) -> tuple[Ctx, float, list]:
    """The metrics' context, the host-to-profile clock offset (ns), and the
    tracer spans on the host clock."""
    t_open, t_end = res["t_open"], res["t_end"]
    ticks = [TickView(t.t0, t.t1) for t in res["ticks"] if t_open <= t.t0 < t_end]
    starts = [t.t0 for t in ticks]

    def tick_at(t: float):
        i = bisect.bisect_right(starts, t) - 1
        return ticks[i] if i >= 0 and t <= ticks[i].t1 else None

    spans = _spans(res)
    for name, pid, tid, s, d, args in spans:
        tv = tick_at(s)
        if tv is None:
            continue
        if pid == PID_SCHED and name in PHASES:
            tv.phases[name] = tv.phases.get(name, 0.0) + d
            if name == "device_step":
                tv.width = args.get("width")
        elif pid == PID_REQUESTS and name in ("prefill", "decode"):
            tv.rows.append((args["pos"], args["tokens"]))
    offset = xtrace.align(trace.annotations, starts)
    for m in trace.modules:
        tv = tick_at((m.start - offset) / 1e9)
        if tv is not None:
            tv.modules.append(m)
    lo, hi = t_open * 1e9 + offset, t_end * 1e9 + offset
    busy = xtrace.union_ns((max(m.start, lo), min(m.start + m.dur, hi))
                           for m in trace.modules if m.start + m.dur > lo and m.start < hi)
    ctx = Ctx(config=config, policy=res["policy"], peaks=costs.peaks(device_kind),
              t_open=t_open, t_end=t_end, window_s=t_end - t_open,
              recs=res["recs"], ticks=ticks, busy_s=busy / 1e9)
    return ctx, offset, spans


def module_label(m: xtrace.Module, width_of: dict) -> str:
    if m.program == "jit_step":
        return f"step width {width_of.get(m.name, '?')}"
    return m.program.removeprefix("jit_")


def breakdown(ctx: Ctx, trace: xtrace.Trace, offset: float, spans: list) -> dict:
    """The ten device ops that took most time (containers left out), and the
    device's idle time in the window by what the host was doing."""
    width_of = {m.name: t.width for t in ctx.ticks for m in t.modules}
    mods = [m for t in ctx.ticks for m in t.modules]
    by_name = {m.name: m for m in mods}
    ops = {}
    for (name, label), ns in xtrace.self_times(mods).items():
        key = f"{module_label(by_name[name], width_of)}: {label}"
        ops[key] = ops.get(key, 0.0) + ns / 1e9
    device_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:10]

    lo, hi = ctx.t_open * 1e9 + offset, ctx.t_end * 1e9 + offset
    idle = [((a - offset) / 1e9, (b - offset) / 1e9)
            for a, b in xtrace.gaps([(m.start, m.start + m.dur) for m in trace.modules], lo, hi)]
    phases = sorted((s, s + d, f"tick {n}") for n, pid, tid, s, d, _ in spans
                    if pid == PID_SCHED and n in PHASES)
    ticks = [(t.t0, t.t1, "tick") for t in ctx.ticks]
    by: dict = {}
    for a, b in idle:
        in_phase = _overlap(phases, a, b, by)
        in_tick = _overlap(ticks, a, b, None)
        by["tick, outside its phases"] = by.get("tick, outside its phases", 0.0) + in_tick - in_phase
        by["between ticks"] = by.get("between ticks", 0.0) + (b - a) - in_tick
    idle_gaps = sorted(((k, v) for k, v in by.items() if v > 0), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[k, v] for k, v in device_ops],
            "idle_gaps": [[k, v] for k, v in idle_gaps]}


def _overlap(intervals: list, a: float, b: float, by: dict | None) -> float:
    """Time of [a, b) covered by ``intervals`` (sorted, not overlapping one
    another), added to ``by`` under each interval's label."""
    i = max(bisect.bisect_right(intervals, (a,)) - 1, 0)
    total = 0.0
    while i < len(intervals) and intervals[i][0] < b:
        s, e, label = intervals[i]
        ov = min(b, e) - max(a, s)
        if ov > 0:
            total += ov
            if by is not None:
                by[label] = by.get(label, 0.0) + ov
        i += 1
    return total


def per_layer(cell: str, res: dict, config: dict, device: dict):
    """(metrics, breakdown, device additions) of a traced run."""
    from bench import run

    trace = xtrace.load(res["trace_dir"])
    try:
        ctx, offset, spans = build(res, config, device["kind"], trace)
        metrics = {}
        for m in run.per_layer_for(cell):
            value = run.metric_reader(m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        bd = breakdown(ctx, trace, offset, spans)
    finally:
        shutil.rmtree(res["trace_dir"], ignore_errors=True)
    return metrics, bd, {"busy_s": ctx.busy_s, "window_s": ctx.window_s}
