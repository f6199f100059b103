"""What the harness hands a traffic load, and what it gets back."""
from __future__ import annotations

import dataclasses
import sys
from typing import Any, Callable, Dict, List, Optional

from .checks import Checker
from .probe import Probe


@dataclasses.dataclass
class Context:
    workload: str
    config: Dict[str, Any]        # bench/configs/<config>.json
    traffic: Dict[str, Any]       # bench/traffic/<traffic>.json
    seed: int
    seconds: int
    probe: Probe                  # spans/counters (on with --trace 1)
    checker: Checker
    # the harness's window: open returns its start (perf_counter); with
    # --trace 1 the profiler, spans and counters cover it
    open_window: Callable[[], float]
    close_window: Callable[[], None]

    def log(self, msg: str) -> None:
        print(msg, file=sys.stderr, flush=True)


@dataclasses.dataclass
class Outcome:
    """A load's measured window and what it counted in it."""

    window_start: float           # perf_counter at the window's open
    window_s: float               # length of the measured window
    attempted: int
    failed: int
    end_to_end: Dict[str, float]  # every end-to-end metric but setup_s
    # per-layer inputs: counts and totals over the window
    plans: int = 0
    counters: Dict[str, float] = dataclasses.field(default_factory=dict)
    # (operations, bytes) of the scan work served in the window
    work: Optional[List[float]] = None
    # run the reference after the window and the device reading
    check: Any = None
