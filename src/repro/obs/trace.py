"""Span-based tracing aligned with XLA profiles.

``with span("fl.dispatch", round=t):`` opens a named span. Every span is a
``jax.profiler.TraceAnnotation``: while a profile is being captured it is
an event on the host's timeline, on the profiler's clock beside the
device's ops, named ``name`` and carrying ``ids`` (here ``round``) as
event stats; with no profiler collecting it costs about a microsecond.

Only with telemetry enabled does a span also time itself: spans nest (a
thread-local stack builds slash-joined paths) and the wall-clock duration
lands in the ``trace.span_ms`` histogram labeled by the path alone. The
ids never become labels, so the series stay bounded however many rounds
run. With telemetry off no clock is read and no stack or histogram is
touched. Spans wrap *host-side* sections only (a round's phases, the
flush call, the admission loop), never per-element work.

For sections *inside* jitted code use :func:`annotate_scope` /
``jax.named_scope`` instead: those are trace-time annotations, free at
runtime, and they name the same sections in XLA's own profile so the
host spans and the compiled regions can be correlated.
"""

from __future__ import annotations

import contextlib
import threading
import time

import jax

from repro.obs import metrics as _metrics

_state = threading.local()


def _stack() -> list[str]:
    st = getattr(_state, "stack", None)
    if st is None:
        st = _state.stack = []
    return st


@contextlib.contextmanager
def _timed_span(name: str, ids: dict, rec):
    st = _stack()
    st.append(name)
    path = "/".join(st)
    t0 = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation(name, **ids):
            yield path
    finally:
        dt_ms = (time.perf_counter() - t0) * 1e3
        st.pop()
        rec.observe("trace.span_ms", dt_ms, span=path)


def span(name: str, **ids):
    """Context manager marking one named, nestable host-side section;
    ``ids`` (e.g. ``round=t``) ride on the profiler event as stats."""
    rec = _metrics.get()
    if not rec.enabled:
        return jax.profiler.TraceAnnotation(name, **ids)
    return _timed_span(name, ids, rec)


def current_path() -> str:
    """Slash-joined path of the currently open spans ("" outside any)."""
    return "/".join(_stack())


def annotate_scope(name: str):
    """Trace-time name for a section of *jitted* code (zero runtime
    cost; shows up in XLA profiles). Thin alias of ``jax.named_scope``
    so instrument points only import ``repro.obs``."""
    return jax.named_scope(name)
