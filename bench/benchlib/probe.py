"""Benchmark-side spans and counters around the program's layer calls.

Installed only in a ``--trace 1`` run.  Each wrapped call becomes a
``jax.profiler.TraceAnnotation`` (so host spans and device events share
the profiler's clock) and adds its host-clock duration to a per-name
total.  Counts and totals are reset when the measured window opens and
stop when its traced part ends, so they cover what the trace covers.
"""
from __future__ import annotations

import functools
import threading
import time
from typing import Any, Dict, List, Tuple


_MISSING = object()


class Probe:
    def __init__(self, on: bool) -> None:
        self.on = on
        self._lock = threading.Lock()
        self.spans: Dict[str, List[float]] = {}     # name -> [count, s]
        self.counters: Dict[str, int] = {}
        self.frozen = False
        self._undo: List[Tuple[Any, str, Any]] = []

    # -------------------------------------------------------------- spans
    def wrap(self, owner: Any, attr: str, name: str) -> None:
        """Time every call of ``owner.attr`` as span ``name``."""
        if not self.on:
            return
        import jax

        orig = getattr(owner, attr)
        probe = self

        @functools.wraps(orig)
        def spanned(*args, **kwargs):
            t0 = time.perf_counter()
            try:
                with jax.profiler.TraceAnnotation(name):
                    return orig(*args, **kwargs)
            finally:
                probe.add(name, time.perf_counter() - t0)

        self._undo.append((owner, attr, orig))
        setattr(owner, attr, spanned)

    def add(self, name: str, seconds: float) -> None:
        with self._lock:
            if self.frozen:
                return
            s = self.spans.setdefault(name, [0, 0.0])
            s[0] += 1
            s[1] += seconds

    def total(self, name: str) -> Tuple[int, float]:
        with self._lock:
            n, s = self.spans.get(name, (0, 0.0))
        return int(n), float(s)

    # ----------------------------------------------------------- counters
    def count(self, cls: Any, attr: str, name: str) -> None:
        """Sum every increase of the program's own counter ``attr`` on
        any instance of ``cls`` into counter ``name`` (a descriptor on
        the class sees each assignment; no instance is kept alive)."""
        if not self.on:
            return
        probe = self

        class Counted:
            def __get__(self, obj, owner=None):
                return self if obj is None else obj.__dict__.get(attr, 0)

            def __set__(self, obj, value):
                old = obj.__dict__.get(attr, 0)
                obj.__dict__[attr] = value
                if value > old and not probe.frozen:
                    with probe._lock:
                        probe.counters[name] = \
                            probe.counters.get(name, 0) + value - old

        self._undo.append((cls, attr, cls.__dict__.get(attr, _MISSING)))
        setattr(cls, attr, Counted())

    def counter(self, name: str) -> int:
        with self._lock:
            return int(self.counters.get(name, 0))

    # ------------------------------------------------------------- window
    def open_window(self) -> None:
        with self._lock:
            self.spans = {}
            self.counters = {}
            self.frozen = False

    def freeze(self) -> None:
        """Stop counting: the traced part of the window has ended."""
        with self._lock:
            self.frozen = True

    def remove(self) -> None:
        for owner, attr, orig in reversed(self._undo):
            if orig is _MISSING:
                delattr(owner, attr)
            else:
                setattr(owner, attr, orig)
        self._undo = []
