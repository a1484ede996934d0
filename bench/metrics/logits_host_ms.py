"""Scheduler (serve/scheduler.py): host milliseconds per tick spent on the
logits after the step, in the tracer's logits_fetch, logits_widen,
logits_check and sample spans, over the window's ticks (bench/phases.py)."""

from bench import phases


def read(ctx):
    return phases.logits_host_ms(ctx.record)
