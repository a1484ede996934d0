"""Model step: the share of the chip's peak that width-1 (decode-only) steps
reach, as mixed_step_mfu.py measures it for mixed steps."""

from bench.metrics._step import step_mfu


def read(ctx):
    return step_mfu(ctx, 1)
