"""Reduction of a profiler trace to device busy time, idle share and
the host activity behind each idle gap.

Busy time is the union of the intervals in which an operation runs on
a device; the idle share is one minus busy over the traced window.
Idle time is attributed to the innermost benchmark span (``bench.*``)
open on the host while it passed, so a long gap reads as "the host was
decoding, then assembling the plan" rather than as a bare number.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from typing import Callable, Dict, List, Optional, Sequence, Tuple

Interval = Tuple[int, int]                 # (start_ns, end_ns)
WINDOW_SPAN = "bench.window"


@dataclasses.dataclass
class Trace:
    device_ops: List[List[Tuple[int, int, str]]]  # per device: ops
    host_spans: List[Tuple[int, int, str]]        # bench.* annotations
    programs: int = 0            # device program executions recorded


def tpu_device_line(plane_name: str, line_name: str) -> bool:
    """Operations of an accelerator: its plane's ``XLA Ops`` line."""
    return plane_name.startswith("/device:") and line_name == "XLA Ops"


def load(path: str, is_device_line: Callable[[str, str], bool]
         = tpu_device_line) -> Trace:
    """Read the ``.xplane.pb`` under ``path`` (a profiler output dir)."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one trace file under {path}, "
                           f"found {len(files)}")
    pd = ProfileData.from_file(files[0])
    devices: Dict[str, List[Tuple[int, int, str]]] = {}
    spans: List[Tuple[int, int, str]] = []
    programs = 0
    for plane in pd.planes:
        for line in plane.lines:
            if plane.name.startswith("/device:") and \
                    line.name == "XLA Modules":
                programs += sum(1 for _ in line.events)
            dev = is_device_line(plane.name, line.name)
            for ev in line.events:
                s, d = int(ev.start_ns), int(ev.duration_ns)
                if dev and d > 0:
                    # an XLA op's event name is its HLO text; keep the
                    # instruction's name
                    devices.setdefault(plane.name, []).append(
                        (s, s + d, ev.name.split(" = ", 1)[0]))
                elif ev.name.startswith("bench."):
                    spans.append((s, s + d, ev.name))
    return Trace([devices[k] for k in sorted(devices)], spans, programs)


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Merge overlapping intervals."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Sequence[Interval], lo: int,
         hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def gaps(busy: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    """The idle intervals of ``[lo, hi)`` around merged ``busy``."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def segments(spans: Sequence[Tuple[int, int, str]]
             ) -> List[Tuple[int, int, str]]:
    """The host timeline cut where any span opens or closes, each piece
    labelled with the innermost (shortest) span open over it."""
    spans = sorted((s, e, n) for s, e, n in spans if n != WINDOW_SPAN)
    cuts = sorted({t for s, e, _ in spans for t in (s, e)})
    out: List[Tuple[int, int, str]] = []
    active: List[Tuple[int, int, str]] = []
    k = 0
    for lo, hi in zip(cuts, cuts[1:]):
        while k < len(spans) and spans[k][0] <= lo:
            active.append(spans[k])
            k += 1
        active = [sp for sp in active if sp[1] > lo]
        if active:
            out.append((lo, hi, min(active,
                                    key=lambda sp: sp[1] - sp[0])[2]))
    return out


def attribute(spans: Sequence[Tuple[int, int, str]],
              idle: Sequence[Interval]) -> Dict[str, int]:
    """Idle nanoseconds by the innermost host span open during them
    (``host.other`` where none is)."""
    out: Dict[str, int] = {}
    segs = segments(spans)
    k = 0
    for lo, hi in idle:
        covered = 0
        while k < len(segs) and segs[k][1] <= lo:
            k += 1
        j = k
        while j < len(segs) and segs[j][0] < hi:
            s, e, name = segs[j]
            d = min(e, hi) - max(s, lo)
            if d > 0:
                out[name] = out.get(name, 0) + d
                covered += d
            j += 1
        if hi - lo > covered:
            out["host.other"] = out.get("host.other", 0) + hi - lo - covered
    return out


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                 # mean over devices
    idle_pct: float
    device_ops: List[List]        # [[name, seconds], ...] top 10
    idle_gaps: List[List]         # [[host span, idle seconds], ...] top 10


def summarize(tr: Trace, window: Optional[Interval] = None,
              top: int = 10) -> Summary:
    """Busy time, idle share and the breakdown over ``window`` (default:
    the ``bench.window`` span; else the extent of all events)."""
    if window is None:
        marks = [(s, e) for s, e, n in tr.host_spans if n == WINDOW_SPAN]
        if marks:
            window = marks[0]
        else:
            every = [iv[:2] for ops in tr.device_ops for iv in ops] + \
                [iv[:2] for iv in tr.host_spans]
            window = (min(s for s, _ in every), max(e for _, e in every))
    lo, hi = window
    span = max(hi - lo, 1)
    busy_total = 0
    ops: Dict[str, int] = {}
    idle: Dict[str, int] = {}
    for dev in tr.device_ops:
        busy = union(clip([(s, e) for s, e, _ in dev], lo, hi))
        busy_total += sum(e - s for s, e in busy)
        for s, e, name in dev:
            s2, e2 = max(s, lo), min(e, hi)
            if e2 > s2:
                ops[name] = ops.get(name, 0) + e2 - s2
        for lab, ns in attribute(tr.host_spans,
                                 gaps(busy, lo, hi)).items():
            idle[lab] = idle.get(lab, 0) + ns
    n_dev = max(len(tr.device_ops), 1)
    busy_s = busy_total / n_dev / 1e9
    window_s = span / 1e9

    def ranked(d: Dict[str, int]) -> List[List]:
        return [[k, v / 1e9 / n_dev]
                for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return Summary(window_s, busy_s, 100.0 * (1.0 - busy_s / window_s),
                   ranked(ops), ranked(idle))
