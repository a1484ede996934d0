"""Model step: the share of the chip's peak that steps at the prefill-chunk
width reach: their real tokens' work at each GEMM's peak, plus attention over
the real context, over their device time (see _step.py). Bounds the mixed
steps' kernels: it stays when a change takes a kernel off the path."""

from bench.metrics._step import step_mfu


def read(ctx):
    return step_mfu(ctx, ctx.chunk)
