"""GEMM kernel (kernels/tugemm_fused.py): roofline share of the fused
quantize-GEMM-dequant kernel in steps at the prefill-chunk width (see
_gemm.py)."""

from bench.metrics._gemm import roofline


def read(ctx):
    return roofline(ctx, ctx.chunk)
