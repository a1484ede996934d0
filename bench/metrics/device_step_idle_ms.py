"""Model step (serve/scheduler.py, the serve/device_step annotation): device
milliseconds per tick in which no XLA module ran while the host was inside
device_step, on the profiler's clock (bench/phases.py)."""

from bench import phases


def read(ctx):
    return phases.device_step_idle_ms(ctx.serve, ctx.modules)
