"""Scheduler (serve/scheduler.py): host milliseconds per tick spent in the
tracer's admit, plan, cow_drain and commit spans (commit includes sampling
and bringing the tokens to the host), summed over the window's ticks and
divided by their number."""

HOST_PHASES = ("admit", "plan", "cow_drain", "commit")


def read(ctx):
    ticks = [t for t in ctx.ticks if t.phases]
    if not ticks:
        return None
    total = sum(t.phases.get(p, 0.0) for t in ticks for p in HOST_PHASES)
    return 1e3 * total / len(ticks)
