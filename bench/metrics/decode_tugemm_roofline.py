"""GEMM kernel (kernels/tugemm_fused.py): roofline share of the fused
quantize-GEMM-dequant kernel in width-1 (decode-only) steps (see _gemm.py)."""

from bench.metrics._gemm import roofline


def read(ctx):
    return roofline(ctx, 1)
