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
not go through here; its scheduler-track spans carry their own
``serve/<phase>`` profiler annotations.

:func:`watch_compiles` counts the process's backend compiles (a
``jax.monitoring`` listener, registered once per process, so nothing is
paid per tick) and records a ``compile`` span in every live enabled tracer
it was handed.
"""

from __future__ import annotations

import weakref
from contextlib import contextmanager

import jax

from .trace import PID_SCHED, TID_TICK

__all__ = ["named_scope", "device_trace", "watch_compiles", "compile_count"]

# jax's event around every executable it obtains for a jitted or eager
# program: an XLA compile, or a load from the persistent compilation cache
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_compiles = 0
_tracers: weakref.WeakSet = weakref.WeakSet()
_listening = False


def _on_duration(event: str, duration_secs: float, **_) -> None:
    global _compiles
    if event != BACKEND_COMPILE_EVENT:
        return
    _compiles += 1
    dur = duration_secs * 1e6
    for tr in tuple(_tracers):
        # the listener fires as the compile returns, on the thread that
        # compiled: the span ends now
        tr.complete("compile", PID_SCHED, TID_TICK, tr.ts() - dur, dur)


def compile_count() -> int:
    """Backend compiles in this process since :func:`watch_compiles` first
    ran."""
    return _compiles


def watch_compiles(tracer=None) -> int:
    """Start counting backend compiles (once per process) and, while
    ``tracer`` is enabled and alive, record each as a ``compile`` span in
    it. Returns the count so far: the baseline a caller diffs
    :func:`compile_count` against to see only its own compiles."""
    global _listening
    if not _listening:
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _listening = True
    if tracer is not None and tracer.enabled:
        _tracers.add(tracer)
    return _compiles


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
