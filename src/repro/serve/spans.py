"""Host spans on the profiler's clock, and per-owner phase clocks.

``span(name, **args)`` enters ``jax.profiler.TraceAnnotation(name,
**args)``, which writes an event onto the host plane of a running
``jax.profiler`` trace (the clock the device's events are put on, to
within about a millisecond) and records nothing otherwise. A span made by
``PhaseClock.span`` also adds its elapsed ``time.perf_counter_ns()`` to
that clock's seconds and count for its name, so the host's time per
phase is readable without a trace
(``ContinuousEngine.stats()["host_phase_seconds"]``, exported as
``msb_engine_phase_seconds_total``). A span costs a few microseconds, so
the spans stay in the code with no switch.

Span names (DESIGN.md Sec. 13, "Metrics"):

  * engine thread, one ``EngineLoop`` iteration: ``engine.commands``,
    ``engine.schedule``, then, once the scheduler chose work,
    ``engine.step`` (args ``kind``, ``rows``) with children
    ``engine.prepare``, ``engine.dispatch`` (the jitted call, which only
    enqueues), ``engine.wait`` (the blocking device-to-host fetch) and
    ``engine.apply``; then ``engine.stream`` and ``engine.housekeeping``;
  * front door: ``frontdoor.detokenize`` (one batch of the detokenize
    thread), ``frontdoor.write`` (one SSE write and its drain);
  * quantizer (``core/quantize.py``): ``quantize.prepare``,
    ``quantize.solve``, ``quantize.encode``.
"""
from __future__ import annotations

import time
from typing import Dict, Optional

from jax.profiler import TraceAnnotation


class PhaseClock:
    """Cumulative host seconds and entries per span name, for one owner.

    Each name is written by one thread; a reader on another thread takes
    ``snapshot()``, a copy made under the GIL."""

    def __init__(self):
        self.seconds: Dict[str, float] = {}
        self.counts: Dict[str, int] = {}

    def span(self, name: str, **args) -> "Span":
        return Span(name, self, **args)

    def add(self, name: str, ns: int):
        self.seconds[name] = self.seconds.get(name, 0.0) + ns * 1e-9
        self.counts[name] = self.counts.get(name, 0) + 1

    def snapshot(self) -> Dict[str, float]:
        return dict(self.seconds)


class Span:
    """Context manager of one span."""

    __slots__ = ("name", "clock", "args", "_ann", "_t0")

    def __init__(self, name: str, clock: Optional[PhaseClock] = None,
                 **args):
        self.name, self.clock, self.args = name, clock, args
        self._ann = None
        self._t0 = 0

    def __enter__(self) -> "Span":
        self._ann = TraceAnnotation(self.name, **self.args)
        self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter_ns() - self._t0
        self._ann.__exit__(*exc)
        if self.clock is not None:
            self.clock.add(self.name, dt)
        return False


def span(name: str, **args) -> Span:
    """A span named ``name`` on the trace alone, for work that no phase
    clock counts (``PhaseClock.span`` for work that one does)."""
    return Span(name, None, **args)
