"""Model step: the share of the chip's peak that the window's useful work
would take. Every real (unpadded) scheduled token through every GEMM at that
GEMM's precision (int8 peak for quantized ones, bf16 for the rest), the
vocabulary projection of each scheduled row, and attention over the real
causal context in bf16, summed over the window's ticks (rows from the
program's prefill/decode spans), as time at peak over the window's length."""

from bench import costs


def read(ctx):
    rows = [t.rows for t in ctx.ticks if t.rows]
    if not rows:
        return None
    useful = sum(costs.step_useful_time(ctx.config, ctx.policy, r, ctx.peaks) for r in rows)
    return 100.0 * useful / ctx.window_s
