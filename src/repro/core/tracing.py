"""Spans and counters inside the runtime, off by default.

    from repro.core import tracing
    tracing.enable()
    ...                                  # drive the job
    spans, counts = tracing.records(), tracing.counts()
    tracing.disable()

`span(name, **attrs)` is a context manager around a stretch of host
work. Off, it returns one shared no-op object: the cost is a flag
check. On, it records `Span(name, start, end, parent, attrs)` on the
`time.perf_counter` clock, `parent` being the index of the innermost
span open when it started (-1 at the top), and opens a
`jax.profiler.TraceAnnotation` of the same name, so a profiler trace
shows it on the device trace's clock. Every span name starts with
`tm:`, which tells the runtime's spans from XLA's and PJRT's.

`count(name, n)` records `Count(name, t, n)` on the same clock, so a
reader can sum a counter inside any span. Off, it does nothing.

While on, two listeners feed the records as well:
- JAX's monitoring events of the persistent compilation cache, as the
  counters `compile_cache.hits`, `compile_cache.misses` and
  `compile_cache.retrieval_s`;
- the garbage collector, whose every pause is a `tm:gc` span (attrs
  `generation` and `collected`).

Only a caller turns tracing on; no environment variable or config key
does.
"""
from __future__ import annotations

import gc
import time
from typing import Any, Dict, List, NamedTuple

import jax

CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "compile_cache.hits",
                "/jax/compilation_cache/cache_misses":
                    "compile_cache.misses"}
CACHE_DURATIONS = {"/jax/compilation_cache/cache_retrieval_time_sec":
                   "compile_cache.retrieval_s"}


class Span(NamedTuple):
    name: str
    start: float                # time.perf_counter seconds
    end: float
    parent: int                 # index in records(); -1 at the top
    attrs: Dict[str, Any]


class Count(NamedTuple):
    name: str
    t: float                    # time.perf_counter seconds
    n: float


_on = False
_spans: List[list] = []         # [name, start, end, parent, attrs]
_stack: List[int] = []          # indices of the open spans
_counts: List[Count] = []
_gc_span = None


class _Off:
    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_OFF = _Off()


class _On:
    __slots__ = ("rec", "annotation")

    def __init__(self, name: str, attrs: Dict[str, Any]):
        self.rec = [name, 0.0, 0.0, -1, attrs]
        self.annotation = jax.profiler.TraceAnnotation(name)

    def __enter__(self):
        rec = self.rec
        rec[3] = _stack[-1] if _stack else -1
        _stack.append(len(_spans))
        _spans.append(rec)
        self.annotation.__enter__()
        rec[1] = time.perf_counter()
        return None

    def __exit__(self, *exc):
        self.rec[2] = time.perf_counter()
        self.annotation.__exit__(*exc)
        _stack.pop()
        return False


def span(name: str, **attrs):
    """A span around the `with` block; see the module's docstring."""
    if not _on:
        return _OFF
    return _On(name, attrs)


def count(name: str, n: float) -> None:
    if _on:
        _counts.append(Count(name, time.perf_counter(), n))


def _on_event(event: str, **_) -> None:
    name = CACHE_EVENTS.get(event)
    if name is not None:
        count(name, 1)


def _on_duration(event: str, duration: float, **_) -> None:
    name = CACHE_DURATIONS.get(event)
    if name is not None:
        count(name, duration)


def _on_gc(phase: str, info: Dict[str, int]) -> None:
    global _gc_span
    if phase == "start":
        _gc_span = _On("tm:gc", {"generation": info["generation"]})
        _gc_span.__enter__()
    elif _gc_span is not None:
        _gc_span.rec[4]["collected"] = info["collected"]
        _gc_span.__exit__(None, None, None)
        _gc_span = None


def enable() -> None:
    """Turn spans and counters on and register the listeners."""
    global _on
    if _on:
        return
    _on = True
    jax.monitoring.register_event_listener(_on_event)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    gc.callbacks.append(_on_gc)


def disable() -> None:
    """Turn tracing off and remove the listeners; records stay."""
    global _on
    if not _on:
        return
    _on = False
    jax.monitoring.unregister_event_listener(_on_event)
    jax.monitoring.unregister_event_duration_listener(_on_duration)
    gc.callbacks.remove(_on_gc)


def reset() -> None:
    """Drop every record; call it with no span open."""
    _spans.clear()
    _stack.clear()
    _counts.clear()


def records() -> List[Span]:
    """The spans so far, in the order they started."""
    return [Span(*rec) for rec in _spans]


def counts() -> List[Count]:
    """The counter increments so far, in the order they were made."""
    return list(_counts)
