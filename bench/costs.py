"""Operations and bytes of the serving step's kernels, from shapes alone.

A fused GEMM call is priced from the shapes it ran with, as the device trace
gives them. Attention and a step's useful work depend on the block, so they
come from the configuration's architecture family (bench/arch/<arch>.py),
priced from the widths of the configuration file (bench/configs/*.json), its
policy giving the precision of each GEMM. These functions count the
multiply-adds of the algorithm (2 operations each) and the bytes a call must
move at the least (inputs read once, outputs written once, weights at their
stored width). The least time a call can take on a device is the larger of
its operations over the peak for its precision and its bytes over the memory
bandwidth (bench/peaks.json).
"""

from __future__ import annotations

import json
import math
from dataclasses import dataclass
from pathlib import Path

BF16 = 2
F32 = 4


def peaks(device_kind: str) -> dict:
    table = json.loads((Path(__file__).with_name("peaks.json")).read_text())
    try:
        return table["devices"][device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r} in bench/peaks.json") from None


def gemm_bits(policy: str) -> dict:
    """GEMM-name pattern -> bits, from a QuantPolicy string such as
    ``*=int8:prequant:per_token,lm_head=bf16``."""
    out = {}
    for rule in policy.split(","):
        pat, spec = rule.split("=", 1)
        kind = spec.split(":")[0]
        out[pat] = 16 if kind == "bf16" else int(kind[3:])
    return out


def bits_for(name: str, policy: str) -> int:
    from fnmatch import fnmatch

    for pat, bits in gemm_bits(policy).items():
        if fnmatch(name, pat):
            return bits
    return 16


@dataclass(frozen=True)
class Gemm:
    name: str
    k: int
    n: int
    bits: int


DTYPE_BYTES = {"bf16": 2, "f16": 2, "f32": 4, "s32": 4, "u32": 4, "s8": 1, "u8": 1,
               "s4": 0.5, "u4": 0.5}


def gemm_call_cost(shapes: tuple) -> tuple[float, float, int]:
    """(operations, bytes, bits) of one fused GEMM call from the shapes it
    ran with (bench/xtrace.parse_shapes): result (M, N), then the activation
    (M, K), the weights as stored, and the row and column scales. Every
    operand is read once and the result written once; ``bits`` is 16 for
    float weights and 8 for integer ones (the int8 peak serves both)."""
    (_, (m, n)), (_, (_, k)), (wtype, _) = shapes[:3]
    byts = sum(DTYPE_BYTES[t] * math.prod(dims) for t, dims in shapes)
    return 2.0 * m * k * n, byts, (16 if wtype.startswith(("bf", "f")) else 8)


def least_time(ops: float, byts: float, bits: int, pk: dict) -> float:
    peak = pk["bf16_flops"] if bits == 16 else pk["int8_ops"]
    return max(ops / peak, byts / pk["hbm_bytes_per_s"])


def _family(config: dict):
    from bench import run

    return run.family(config)


def attn_cost(config: dict, rows, block_size: int, kv_bytes: int = BF16) -> tuple[float, float]:
    """(operations, bytes) of one layer's paged attention over ``rows``, each
    (pos, n): n queries at positions pos..pos+n-1 against the causal context,
    as the configuration's family prices them."""
    return _family(config).attn_cost(config, rows, block_size, kv_bytes)


def step_useful_time(config: dict, policy: str, rows, pk: dict) -> float:
    """Seconds one step's useful work over ``rows`` takes at the device's
    peaks, as the configuration's family prices it."""
    return _family(config).step_useful_time(config, policy, rows, pk)
