"""Three-term roofline from a compiled dry-run artifact (no hardware needed).

    compute term    = HLO_FLOPs / peak_FLOP/s          (per chip)
    memory term     = HLO_bytes / HBM_bw               (per chip)
    collective term = collective_bytes / link_bw       (per chip)

``compiled.cost_analysis()`` provides per-device FLOPs and bytes accessed.
Collective bytes are NOT in cost_analysis: we parse the post-SPMD optimized
HLO (``compiled.as_text()``), build an id -> shape table from every
instruction, and charge each collective by kind:

    all-reduce         2 x result bytes    (ring reduce-scatter + all-gather)
    all-gather         1 x result bytes    (each chip receives the full result)
    reduce-scatter     1 x operand bytes   (sends its full input once around)
    all-to-all         1 x result bytes
    collective-permute 1 x result bytes

Default hardware constants are TPU v5e-class, per the assignment: 197 bf16
TFLOP/s, 819 GB/s HBM, ~50 GB/s/link ICI. :data:`HW_PROFILES` carries named
profiles per backend class and :func:`hw_profile` selects one by name or by
the running JAX backend, so the same dry-run artifact can be re-priced for
a different machine (benchmarks/roofline_all.py ``--hw``).
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field

__all__ = [
    "HW", "HW_PROFILES", "hw_profile",
    "CollectiveStats", "RooflineReport", "collective_stats", "analyze",
]


@dataclass(frozen=True)
class HW:
    peak_flops: float = 197e12      # bf16 FLOP/s per chip
    hbm_bw: float = 819e9           # bytes/s per chip
    ici_bw: float = 50e9            # bytes/s per link
    hbm_per_chip: float = 16e9      # v5e: 16 GB
    name: str = "tpu"


# Named machine classes for re-pricing the three terms. The numbers are
# representative of the class, not a specific SKU: "tpu" is the v5e
# assignment target (and the default ``HW()`` for backward compatibility);
# "gpu" is an A100-80G-class part (312 bf16 TFLOP/s, ~2 TB/s HBM2e, 600
# GB/s NVLink); "cpu" is a modern server socket (~2 f32 TFLOP/s AVX-512,
# ~100 GB/s DDR, "link" = ~30 GB/s inter-socket, 64 GB visible).
HW_PROFILES: dict[str, HW] = {
    "tpu": HW(),
    "gpu": HW(peak_flops=312e12, hbm_bw=2.0e12, ici_bw=600e9,
              hbm_per_chip=80e9, name="gpu"),
    "cpu": HW(peak_flops=2e12, hbm_bw=100e9, ici_bw=30e9,
              hbm_per_chip=64e9, name="cpu"),
}


def hw_profile(name: str | None = None) -> HW:
    """Resolve a named :class:`HW` profile.

    ``None`` / ``"auto"`` selects by the running JAX backend (tpu/gpu/cpu);
    a backend with no profile is an error, never priced as another device.
    The import is lazy so artifact-only re-pricing never initializes a
    device runtime."""
    if name in (None, "auto"):
        import jax

        name = jax.default_backend()
    prof = HW_PROFILES.get(name)
    if prof is None:
        raise KeyError(
            f"unknown hw profile {name!r}; have {sorted(HW_PROFILES)}")
    return prof


_DTYPE_BYTES = {
    "pred": 1, "s2": 1, "u2": 1, "s4": 1, "u4": 1,
    "s8": 1, "u8": 1, "f8e4m3": 1, "f8e5m2": 1, "f8e4m3fn": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16, "token": 0,
}

# one shape like bf16[16,512]{1,0} or f32[] — no tuple nesting
_SHAPE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.-]+)\s*=\s*(\([^)]*\)|[a-z0-9]+\[[0-9,]*\](?:\{[^}]*\})?)\s*([\w-]+)")
_OPERANDS = re.compile(r"%([\w.-]+)")

_COLLECTIVES = {
    "all-reduce": ("result", 2.0),
    "all-gather": ("result", 1.0),
    "reduce-scatter": ("operand", 1.0),
    "all-to-all": ("result", 1.0),
    "collective-permute": ("result", 1.0),
    "all-reduce-start": ("result", 2.0),
    "all-gather-start": ("result", 1.0),
    "collective-permute-start": ("result", 1.0),
}


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


@dataclass
class CollectiveStats:
    bytes_by_kind: dict = field(default_factory=dict)
    count_by_kind: dict = field(default_factory=dict)

    @property
    def total_bytes(self) -> float:
        return float(sum(self.bytes_by_kind.values()))

    def merge_line(self, kind: str, nbytes: float):
        self.bytes_by_kind[kind] = self.bytes_by_kind.get(kind, 0.0) + nbytes
        self.count_by_kind[kind] = self.count_by_kind.get(kind, 0) + 1


def collective_stats(hlo_text: str) -> CollectiveStats:
    """Parse optimized HLO; returns per-device collective wire bytes."""
    types: dict[str, str] = {}
    lines = hlo_text.splitlines()
    for ln in lines:
        m = _INSTR.match(ln)
        if m:
            types[m.group(1)] = m.group(2)

    stats = CollectiveStats()
    for ln in lines:
        m = _INSTR.match(ln)
        if not m:
            continue
        name, rtype, op = m.groups()
        kind = op if op in _COLLECTIVES else None
        if kind is None:
            continue
        basis, mult = _COLLECTIVES[kind]
        if basis == "result":
            nbytes = _shape_bytes(rtype)
        else:
            # first operand's type (reduce-scatter input)
            paren = ln[ln.index(op) + len(op):]
            ops = _OPERANDS.findall(paren)
            nbytes = _shape_bytes(types.get(ops[0], "")) if ops else _shape_bytes(rtype)
        stats.merge_line(kind.replace("-start", ""), mult * nbytes)
    return stats


@dataclass
class RooflineReport:
    name: str
    chips: int
    hlo_flops: float            # per device
    hlo_bytes: float            # per device
    collective_bytes: float     # per device
    model_flops: float          # global, 6·N_active·D
    compute_s: float = 0.0
    memory_s: float = 0.0
    collective_s: float = 0.0
    dominant: str = ""
    bound_s: float = 0.0
    useful_ratio: float = 0.0   # MODEL_FLOPS / (HLO_FLOPs × chips)
    mfu: float = 0.0            # MODEL_FLOPS / (bound_s × chips × peak)
    collectives: dict = field(default_factory=dict)
    memory_per_chip: float = 0.0
    xla_cost_flops: float = 0.0     # cost_analysis 'flops' (loop bodies ×1) — reference only
    unknown_trip_loops: int = 0

    def table_row(self) -> str:
        return (
            f"| {self.name} | {self.compute_s*1e3:.2f} | {self.memory_s*1e3:.2f} | "
            f"{self.collective_s*1e3:.2f} | {self.dominant} | {self.useful_ratio:.2f} | "
            f"{self.mfu*100:.1f}% |"
        )


def analyze(
    name: str,
    *,
    chips: int,
    hlo_text: str,
    model_flops: float,
    cost: dict | None = None,
    hw: HW = HW(),
    memory_per_chip: float = 0.0,
) -> RooflineReport:
    """Three-term roofline. FLOPs/bytes/collectives come from our own
    optimized-HLO parser (hlo_parse.parse_hlo) because XLA's cost_analysis
    counts while-loop (scan) bodies once; ``cost`` is kept as reference."""
    from .hlo_parse import parse_hlo

    parsed = parse_hlo(hlo_text)
    flops = parsed.flops
    nbytes = parsed.hbm_bytes

    r = RooflineReport(
        name=name,
        chips=chips,
        hlo_flops=flops,
        hlo_bytes=nbytes,
        collective_bytes=parsed.collective_bytes,
        model_flops=model_flops,
        collectives={**parsed.collectives},
        memory_per_chip=memory_per_chip,
    )
    r.xla_cost_flops = float(cost.get("flops", 0.0)) if cost else 0.0
    r.unknown_trip_loops = parsed.unknown_trip_loops
    r.compute_s = flops / hw.peak_flops
    r.memory_s = nbytes / hw.hbm_bw
    r.collective_s = parsed.collective_bytes / hw.ici_bw
    terms = {
        "compute": r.compute_s,
        "memory": r.memory_s,
        "collective": r.collective_s,
    }
    r.dominant = max(terms, key=terms.get)
    r.bound_s = max(terms.values())
    total_hlo = flops * chips
    r.useful_ratio = model_flops / total_hlo if total_hlo else 0.0
    denom = r.bound_s * chips * hw.peak_flops
    r.mfu = model_flops / denom if denom else 0.0
    return r
