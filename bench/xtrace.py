"""Reduction of a profiler trace (``.xplane.pb``) to what the metrics read.

The device plane (``/device:TPU:0``) has a line of XLA module executions
(``jit_step(<fingerprint>)``, ``jit__argmax(...)``) and a line of XLA ops,
each op named by its HLO instruction (``%tugemm_fused_pallas.3 = ...
custom-call(...)``; a while loop's op spans the ops of its body). Kernels are
found by the instruction name the program's function gives them:
``tugemm_fused_pallas`` for the fused GEMM, ``flash_paged_decode`` for paged
attention; a custom call's event also carries the shapes it ran with, result
first, then each operand. The harness puts a ``bench/tick`` annotation around every
``Scheduler.tick()``; it lands on a host line of the same trace, on the same
clock as the device, which ties device time to ticks and, through the
harness's own clock, to the program's tracer spans.

Times here are nanoseconds on the profile's clock.
"""

from __future__ import annotations

import glob
import re
from dataclasses import dataclass, field

GEMM_KERNEL = "tugemm_fused_pallas"
ATTN_KERNEL = "flash_paged_decode"
TICK_ANNOTATION = "bench/tick"
_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")
_SHAPE = re.compile(r"\b([a-z]+[0-9]*)\[([0-9,]*)\]")
_LAYOUT = re.compile(r"\{[^{}]*\}")
CONTAINERS = ("while", "conditional", "call")


@dataclass
class Op:
    label: str      # instruction name without the leading %, e.g. fusion.52
    opcode: str
    start: float
    dur: float
    shapes: tuple = ()   # custom calls: (dtype, dims) of the result, then of each operand


@dataclass
class Module:
    name: str       # e.g. jit_step(4858271697501900204)
    start: float
    dur: float
    ops: list = field(default_factory=list)

    @property
    def program(self) -> str:
        return self.name.split("(")[0]


@dataclass
class Trace:
    modules: list            # Module, in start order, ops attached
    annotations: list        # (start, dur) of each tick annotation, in order


def parse_op(name: str) -> tuple[str, str]:
    """(label, opcode) of an XLA op event name."""
    head, _, body = name.partition(" = ")
    m = _OPCODE.search(" " + body) if body else None
    return head.lstrip("%"), (m.group(1) if m else "")


def parse_shapes(name: str) -> tuple:
    """((dtype, dims), ...) of an op's result and then its operands, read
    from the instruction text of its event (layouts left out); () where the
    text holds none."""
    body = _LAYOUT.sub("", name.partition(" = ")[2]).split("), ")[0]
    return tuple((t, tuple(int(d) for d in dims.split(",") if d))
                 for t, dims in _SHAPE.findall(body))


def from_profile(pd) -> Trace:
    """Reduce a ``jax.profiler.ProfileData`` of one chip."""
    dev = next(p for p in pd.planes if p.name.startswith("/device:TPU:0"))
    lines = {ln.name: ln for ln in dev.lines}
    modules = sorted((Module(e.name, e.start_ns, e.duration_ns)
                      for e in lines["XLA Modules"].events), key=lambda m: m.start)
    ops = []
    for e in lines["XLA Ops"].events:
        label, opcode = parse_op(e.name)
        shapes = parse_shapes(e.name) if opcode == "custom-call" else ()
        ops.append(Op(label, opcode, e.start_ns, e.duration_ns, shapes))
    ops.sort(key=lambda o: o.start)
    j = 0
    for m in modules:
        end = m.start + m.dur
        while j < len(ops) and ops[j].start < m.start:
            j += 1
        while j < len(ops) and ops[j].start < end:
            m.ops.append(ops[j])
            j += 1
    ann = []
    for plane in pd.planes:
        if not plane.name.startswith("/host"):
            continue
        for ln in plane.lines:
            ann += [(e.start_ns, e.duration_ns) for e in ln.events
                    if e.name == TICK_ANNOTATION]
    return Trace(modules, sorted(ann))


def load(trace_dir: str) -> Trace:
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    return from_profile(ProfileData.from_file(path))


def union_ns(intervals) -> float:
    """Length of the union of (start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """The parts of [lo, hi) that no interval covers."""
    out, t = [], lo
    for s, e in sorted(intervals):
        if e <= t:
            continue
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(a, b) for a, b in out if b > a]


def kernel_ops(module: Module, kernel: str) -> list:
    return [o for o in module.ops
            if o.opcode == "custom-call" and o.label.split(".")[0] == kernel]


def kernel_ns(module: Module, kernel: str) -> float:
    return sum(o.dur for o in kernel_ops(module, kernel))


def kernel_calls(module: Module, kernel: str) -> int:
    return len(kernel_ops(module, kernel))


def self_times(modules) -> dict:
    """Device time by (program, op label), containers (while loops) left out,
    so that the ops inside them are counted once."""
    out: dict = {}
    for m in modules:
        for o in m.ops:
            if o.opcode in CONTAINERS:
                continue
            key = (m.name, o.label)
            out[key] = out.get(key, 0.0) + o.dur
    return out


def align(annotations, host_ticks) -> float:
    """Offset (ns) that maps host perf_counter seconds to the profile clock,
    from the last annotations and the last host ticks, which correspond
    one to one (the profiler stops after the last tick)."""
    n = min(len(annotations), len(host_ticks))
    if n == 0:
        raise ValueError("no tick annotations in the trace")
    offs = sorted(annotations[-i][0] - host_ticks[-i] * 1e9 for i in range(1, n + 1))
    return offs[len(offs) // 2]
