"""Program spans and a compile counter on the profiler's clock.

``span(name, **ids)`` marks a step of the host program. While tracing is
on it enters ``jax.profiler.TraceAnnotation("repro." + name, **ids)``, so
the span lands in the same profile as the device's operations, on the same
clock: an idle gap of the chip can be set against the span that was open
on the host at the time. Spans nest; ``ids`` carries what ties the spans of
one unit of work together (``wave=`` in the LLM engine, ``chunk=`` and
``launches=`` in the executor).

While tracing is on, a ``jax.monitoring`` listener also attributes every
backend compile (persistent-cache reads included, as JAX reports them)
and every persistent-cache read to the innermost open span of the thread
that compiled. ``counters()`` returns, per span name (``""`` outside any
span), ``{"compiles", "compile_s", "cache_reads"}``.

Off is the default: ``span`` then returns one shared no-op context, and
nothing is registered with JAX. ``enable()`` / ``disable()`` switch it.

Capture a profile with the program's spans::

    from repro import tracing
    tracing.enable()
    with jax.profiler.trace("/tmp/prof"):
        engine.generate(prompts, max_new=32)
    tracing.disable()
    tracing.counters()          # which span compiled, and for how long
"""
from __future__ import annotations

import contextlib
import threading
from typing import Dict

import jax

PREFIX = "repro."
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_READ_EVENT = "/jax/compilation_cache/cache_hits"

_on = False
_OFF = contextlib.nullcontext()
_local = threading.local()
_lock = threading.Lock()
_counts: Dict[str, Dict[str, float]] = {}


def _stack() -> list:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


class _Span:
    __slots__ = ("name", "ids", "_annotation")

    def __init__(self, name: str, ids: dict):
        self.name = name
        self.ids = ids

    def __enter__(self):
        _stack().append(self.name)
        self._annotation = jax.profiler.TraceAnnotation(PREFIX + self.name,
                                                        **self.ids)
        self._annotation.__enter__()
        return self

    def __exit__(self, *exc):
        self._annotation.__exit__(*exc)
        _stack().pop()
        return False


def span(name: str, **ids):
    """A context marking one step of the program (module doc)."""
    if not _on:
        return _OFF
    return _Span(name, ids)


def _bump(**amounts) -> None:
    stack = _stack()
    where = stack[-1] if stack else ""
    with _lock:
        row = _counts.setdefault(
            where, {"compiles": 0, "compile_s": 0.0, "cache_reads": 0})
        for k, v in amounts.items():
            row[k] += v


def _on_duration(event: str, duration: float, **_) -> None:
    if event == COMPILE_EVENT:
        _bump(compiles=1, compile_s=duration)


def _on_event(event: str, **_) -> None:
    if event == CACHE_READ_EVENT:
        _bump(cache_reads=1)


def enable() -> None:
    """Turn program spans and the compile counter on."""
    global _on
    if not _on:
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        jax.monitoring.register_event_listener(_on_event)
        _on = True


def disable() -> None:
    """Turn them off; the counts are kept until ``reset_counters``."""
    global _on
    if _on:
        _on = False
        jax.monitoring.unregister_event_duration_listener(_on_duration)
        jax.monitoring.unregister_event_listener(_on_event)


def enabled() -> bool:
    return _on


def counters() -> Dict[str, Dict[str, float]]:
    """A snapshot of the compile counts, per innermost span name."""
    with _lock:
        return {k: dict(v) for k, v in _counts.items()}


def reset_counters() -> None:
    with _lock:
        _counts.clear()
