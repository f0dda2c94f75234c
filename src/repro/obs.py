"""Spans and compile counts of the program, on the profiler's clock.

``span(name, **stats)`` marks a host-driven phase: a
``jax.profiler.TraceAnnotation``, which a running profiler session
records beside the device's operations (with no session it records
nothing), and an entry on this thread's stack of open spans.

A ``jax.monitoring`` listener, registered at import, counts each
lowering (``lowered``), backend compile (``compiled``) and read from
the persistent compilation cache (``cache_loads``) under the innermost
open span, and on each lowering writes a ``repro.lowered`` marker span
on the lowering thread, so a trace shows where the host lowered.  JAX
records these events only while it traces, lowers or compiles, so a
call that compiles nothing pays nothing for the listener.

``counts()`` returns the counts keyed ``"<span>/<event>"``; an event
outside every span is keyed ``"-/<event>"``.
"""
from __future__ import annotations

import functools
import threading

import jax.monitoring
from jax.profiler import TraceAnnotation

#: The marker span written on each lowering.
LOWERED = "repro.lowered"
#: The span name of an event outside every span.
OUTSIDE = "-"

_EVENTS = {"/jax/core/compile/jaxpr_to_mlir_module_duration": "lowered",
           "/jax/core/compile/backend_compile_duration": "compiled",
           "/jax/compilation_cache/cache_hits": "cache_loads"}

_local = threading.local()
_lock = threading.Lock()
_counts: dict[str, int] = {}


def _open() -> list[str]:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


class span:
    """``with span("repro.qr"):`` — a named phase of the host's work."""

    __slots__ = ("name", "_ann", "_stack")

    def __init__(self, name: str, **stats):
        self.name = name
        self._ann = TraceAnnotation(name, **stats)

    def __enter__(self):
        self._stack = _open()
        self._stack.append(self.name)
        self._ann.__enter__()
        return self

    def __exit__(self, *exc):
        self._ann.__exit__(*exc)
        self._stack.pop()


def spanned(name: str, **stats):
    """Decorator: run each call of the function inside
    ``span(name, **stats)``."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with span(name, **stats):
                return fn(*args, **kwargs)
        return inner
    return wrap


def count(event: str) -> None:
    """Count one ``event`` under the innermost open span."""
    stack = _open()
    key = f"{stack[-1] if stack else OUTSIDE}/{event}"
    with _lock:
        _counts[key] = _counts.get(key, 0) + 1


def counts() -> dict[str, int]:
    """A copy of every count, keyed ``"<span>/<event>"``."""
    with _lock:
        return dict(_counts)


def total(event: str) -> int:
    """The count of ``event`` over all spans."""
    suffix = "/" + event
    return sum(v for k, v in counts().items() if k.endswith(suffix))


def _on_event(event, *args, **kwargs):
    name = _EVENTS.get(event)
    if name is None:
        return
    count(name)
    if name == "lowered":
        with TraceAnnotation(LOWERED):
            pass


jax.monitoring.register_event_duration_secs_listener(_on_event)
jax.monitoring.register_event_listener(_on_event)
