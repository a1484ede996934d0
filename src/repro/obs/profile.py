"""Device-side profiler hooks: ``jax.named_scope`` annotations + optional
``jax.profiler`` trace wiring (DESIGN.md §14).

The host tracer (obs/trace.py) records *when* the scheduler dispatched a
step; this module makes the *device* side legible: the jitted mixed step,
the speculative draft pass, and the verify pass each trace under a stable
named scope, so an XLA/perfetto device profile captured with
:func:`device_trace` lines its kernels up against the host tick timeline by
name. Scopes are trace-time only — zero runtime cost on the compiled path
and no change to the lowered program's numerics (the HLO just carries
different metadata names), which keeps the bit-exactness gate trivial.

Scope taxonomy::

    serve/step          the scheduler's ONE mixed prefill+decode step
    serve/verify        the all-logits speculative verify step
    serve/draft         the draft-policy mixed step (serve/spec.py)
    serve/fallback      the quarantined-row bf16 fallback step
    serve/logits        the lm-head projection inside any of the above

``jax.profiler.start_trace`` needs a writable logdir and is unavailable on
some backends; :func:`device_trace` then raises, so a run that asked for a
device trace never ends as if it had one. Host tracing (obs/trace.py) does
not go through here.
"""

from __future__ import annotations

from contextlib import contextmanager

import jax

__all__ = ["named_scope", "device_trace"]


def named_scope(name: str):
    """Stable alias for ``jax.named_scope`` (trace-time annotation)."""
    return jax.named_scope(name)


@contextmanager
def device_trace(logdir: str | None):
    """Wrap a block in a ``jax.profiler`` trace written to ``logdir`` when
    it is set; no-op otherwise. The captured device trace is viewable in
    Perfetto/TensorBoard and carries the serve/* named scopes above.

    Raises ``RuntimeError`` when the profiler cannot start."""
    if not logdir:
        yield
        return
    try:
        jax.profiler.start_trace(logdir)
    except Exception as e:  # noqa: BLE001 - re-raised with the logdir named
        raise RuntimeError(f"device trace could not start in {logdir!r}: {e!r}") from e
    try:
        yield
    finally:
        jax.profiler.stop_trace()
