"""Device: the share of the window in which no XLA module ran on the chip,
1 - busy / window, where busy is the union of the profiler's module
executions."""


def read(ctx):
    return 100.0 * (1.0 - ctx.busy_s / ctx.window_s) if ctx.window_s > 0 else None
