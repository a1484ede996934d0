"""Shared by the per-width step utilization readers."""

from bench import costs


def step_mfu(ctx, width):
    """Useful work of the window's steps of ``width`` at the chip's peaks
    (bench/costs.step_useful_time) over their device time."""
    useful = spent = 0.0
    for t in ctx.ticks_of_width(width):
        steps = [m.dur for m in t.modules if m.program == "jit_step"]
        if not steps or not t.rows:
            continue
        useful += costs.step_useful_time(ctx.config, ctx.policy, t.rows, ctx.peaks)
        spent += sum(steps) / 1e9
    return 100.0 * useful / spent if spent else None
