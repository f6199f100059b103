"""In-process spans and counters on the plan path (DESIGN.md §10).

Off by default.  While off, :func:`span` returns one shared null context
and :func:`count` returns at once: no clock read, no profiler call, no
object made.  While on (:func:`enable`), each span is also a
``jax.profiler.TraceAnnotation``, so the host spans land on the device
trace's clock whenever a profiler trace is being captured, and each
carries ``rid``: the id of the public call that caused it.  The root
span of a thread allocates the id; spans opened inside it inherit it.

Totals per span name (``count``, ``total_s``, ``self_s`` = duration
less the part the span's children cover) and counters are summed under
one lock, since the service runs its lanes on threads; the stack of
open spans is per thread.  A name opened inside a span of its own name
counts twice in ``total_s`` and once in ``self_s``.

    from repro import tracing
    tracing.reset(); tracing.enable()
    ...                                   # plans, updates, ...
    snap = tracing.snapshot(); tracing.disable()
    snap["spans"]["repro.backend.fetch"]["total_s"]
    snap["counters"]["backend.d2h_bytes"]
"""
from __future__ import annotations

import functools
import itertools
import threading
import time
from typing import Any, Callable, Dict, List, Optional, TypeVar, cast

__all__ = ["count", "disable", "enable", "enabled", "reset", "snapshot",
           "span", "traced"]

_F = TypeVar("_F", bound=Callable[..., Any])

_on = False
_lock = threading.Lock()
_spans: Dict[str, List[int]] = {}       # name -> [count, total_ns, self_ns]
_counters: Dict[str, int] = {}
_local = threading.local()              # .stack: the open spans
_rids = itertools.count(1)


class _Null:
    """The context :func:`span` returns while the recorder is off."""

    __slots__ = ()

    def __enter__(self) -> "_Null":
        return self

    def __exit__(self, *exc: Any) -> None:
        return None


_NULL = _Null()


class _Span:
    __slots__ = ("name", "attrs", "rid", "child_ns", "t0", "ann")

    def __init__(self, name: str, attrs: Dict[str, Any]) -> None:
        self.name = name
        self.attrs = attrs

    def __enter__(self) -> "_Span":
        from jax.profiler import TraceAnnotation

        stack: Optional[List[_Span]] = getattr(_local, "stack", None)
        if stack is None:
            stack = _local.stack = []
        self.rid = stack[-1].rid if stack else next(_rids)
        self.child_ns = 0
        self.ann = TraceAnnotation(self.name, rid=self.rid, **self.attrs)
        self.ann.__enter__()
        stack.append(self)
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc: Any) -> None:
        dt = time.perf_counter_ns() - self.t0
        stack: List[_Span] = _local.stack
        stack.pop()
        if stack:
            stack[-1].child_ns += dt
        self.ann.__exit__(*exc)
        with _lock:
            tot = _spans.get(self.name)
            if tot is None:
                tot = _spans[self.name] = [0, 0, 0]
            tot[0] += 1
            tot[1] += dt
            tot[2] += dt - self.child_ns


def span(name: str, **attrs: Any) -> Any:
    """A context manager timing ``name`` (null while the recorder is
    off); ``attrs`` become stats of the profiler event."""
    if not _on:
        return _NULL
    return _Span(name, attrs)


def traced(name: str) -> Callable[[_F], _F]:
    """Decorate a function so that each call runs inside ``span(name)``
    (a plain call while the recorder is off)."""
    def deco(fn: _F) -> _F:
        @functools.wraps(fn)
        def call(*args: Any, **kwargs: Any) -> Any:
            if not _on:
                return fn(*args, **kwargs)
            with _Span(name, {}):
                return fn(*args, **kwargs)
        return cast(_F, call)
    return deco


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to counter ``name`` (nothing while the recorder is
    off)."""
    if not _on:
        return
    with _lock:
        _counters[name] = _counters.get(name, 0) + n


def enabled() -> bool:
    """Whether the recorder is on: a call site guards work that only
    computes a counter's value with it."""
    return _on


def enable() -> None:
    global _on
    _on = True


def disable() -> None:
    global _on
    _on = False


def reset() -> None:
    """Drop every total and counter (spans still open add to the fresh
    totals when they close)."""
    with _lock:
        _spans.clear()
        _counters.clear()


def snapshot() -> Dict[str, Dict[str, Any]]:
    """``{"spans": {name: {"count", "total_s", "self_s"}}, "counters":
    {name: n}}`` over what closed since the last :func:`reset`."""
    with _lock:
        spans = {k: {"count": c, "total_s": t / 1e9, "self_s": s / 1e9}
                 for k, (c, t, s) in _spans.items()}
        return {"spans": spans, "counters": dict(_counters)}
