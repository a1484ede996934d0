"""Model step (serve/scheduler.py build_mixed_step, scope serve/step): device
milliseconds of one step at the prefill-chunk width, from the profiler's
module executions, averaged over the window's steps of that width."""


def read(ctx):
    steps = [m.dur for t in ctx.ticks_of_width(ctx.chunk) for m in t.modules
             if m.program == "jit_step"]
    return 1e-6 * sum(steps) / len(steps) if steps else None
