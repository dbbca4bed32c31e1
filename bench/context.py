"""What a run hands to the per-layer metric readers.

A reader (``bench/metrics/<name>.py``) defines ``read(ctx)`` and returns a
number, or ``None`` when the run holds nothing for it to read; the harness
then leaves the metric out of the result.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

from . import trace as tr


@dataclasses.dataclass
class Context:
    arch: dict                          # the configuration's published numbers
    engine: dict                        # the configuration's engine settings
    peaks: dict                         # the device kind's row of PEAKS
    counters: Dict[str, float] = dataclasses.field(default_factory=dict)
    samples: Dict[str, List[float]] = dataclasses.field(default_factory=dict)
    trace: Optional[tr.DeviceTrace] = None
    trace_window: Tuple[float, float] = (0.0, 0.0)    # ns, trace's clock

    @property
    def trace_seconds(self) -> float:
        return (self.trace_window[1] - self.trace_window[0]) * 1e-9

    def busy_seconds(self) -> float:
        return tr.busy_ns(self.trace.ops, self.trace_window) * 1e-9

    def modules(self, *prefixes: str) -> List[tr.Event]:
        """Module runs inside the traced window, by name prefix."""
        lo, hi = self.trace_window
        return [e for e in tr.matching(self.trace.modules, prefixes)
                if e.start >= lo and e.end <= hi]
