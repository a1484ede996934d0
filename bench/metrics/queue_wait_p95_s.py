"""Admission (serve/admission.py): 95th percentile of the wait from a
request's due time to its admission into a slot, over every request due in
the window; one still queued at the close counts its wait so far. Admission
is read from the harness's scan of the scheduler's slots after each tick and
dated to the start of that tick."""

from bench.stats import percentile


def read(ctx):
    waits = [(r.admitted if r.admitted is not None and r.admitted < ctx.t_end else ctx.t_end)
             - r.due for r in ctx.recs if ctx.t_open <= r.due < ctx.t_end]
    return percentile(waits, 95)
