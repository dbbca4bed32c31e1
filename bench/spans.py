"""The program's host spans, read from a profiler trace, and what they say
about the device's idle time.

The program marks its host work with ``jax.profiler.TraceAnnotation``
(``src/repro/serve/spans.py``, ``core/quantize.py``): ``engine.*`` on the
engine thread (and on the step worker of a supervised engine),
``frontdoor.*`` on the detokenize and HTTP threads, ``quantize.*`` in the
quantizer; a harness may add ``bench.*``. The profiler writes them on the
``/host:CPU`` plane, one line per thread, on the clock of the device
planes (``trace.load``) to within about a millisecond, so a device idle
gap of more than a few milliseconds can be named by the host work that
covers it.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

from . import trace as tr

PREFIXES = ("engine.", "quantize.", "bench.", "frontdoor.")


@dataclasses.dataclass
class Span:
    name: str
    start: float                        # ns, the trace's clock
    dur: float                          # ns
    line: int                           # thread line on the host plane
    args: Dict[str, object]

    @property
    def end(self) -> float:
        return self.start + self.dur


def load_spans(path: str, prefixes: Sequence[str] = PREFIXES
               ) -> List[Span]:
    """The spans on the host plane whose names start with ``prefixes``,
    with their args, sorted by start."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(tuple(prefixes)):
                    out.append(Span(e.name, e.start_ns, e.duration_ns, i,
                                    dict(e.stats)))
    return sorted(out, key=lambda s: (s.start, -s.dur))


def _innermost_cover(spans: Sequence[Span], lo: float, hi: float
                     ) -> Dict[str, float]:
    """ns of ``[lo, hi]`` under each span name, counting at each instant
    only the innermost span there (the one that started last)."""
    inside = [s for s in spans if s.end > lo and s.start < hi]
    cuts = sorted({lo, hi} | {max(lo, min(hi, t)) for s in inside
                              for t in (s.start, s.end)})
    cover: Dict[str, float] = {}
    for a, b in zip(cuts, cuts[1:]):
        over = [s for s in inside if s.start <= a and s.end >= b]
        if over:
            name = max(over, key=lambda s: (s.start, -s.dur)).name
            cover[name] = cover.get(name, 0.0) + (b - a)
    return cover


def gap_label(spans: Sequence[Span], start: float, length: float
              ) -> Optional[str]:
    """``host:<span>`` for the idle gap ``[start, start + length]``: the
    innermost span that covers most of it, taken from the first group of
    ``PREFIXES`` whose spans cover at least half of it (engine work before
    the front door's threads); None where no group does."""
    for prefix in PREFIXES:
        group = [s for s in spans if s.name.startswith(prefix)]
        cover = _innermost_cover(group, start, start + length)
        if 2 * sum(cover.values()) >= length:
            return "host:" + max(cover.items(), key=lambda kv: kv[1])[0]
    return None


def breakdown(dev: tr.DeviceTrace, window: tr.Interval,
              spans: Sequence[Span], top: int = 10) -> dict:
    """``trace.breakdown`` with each idle gap that a span covers named
    ``host:<span>``; a gap no span covers keeps its old label."""
    out = tr.breakdown(dev, window, top)
    gaps = sorted(tr.idle_gaps(dev.ops, window), key=lambda g: -g[1])[:top]
    for row, (start, length) in zip(out["idle_gaps"], gaps):
        row[0] = gap_label(spans, start, length) or row[0]
    return out


def idle_under(dev: tr.DeviceTrace, window: tr.Interval,
               spans: Sequence[Span], prefix: str = "engine."
               ) -> Tuple[float, float]:
    """(idle ns in ``window``, the part of it under a ``prefix`` span)."""
    cover = [(s.start, s.end) for s in spans if s.name.startswith(prefix)]
    idle = covered = 0.0
    for start, length in tr.idle_gaps(dev.ops, window):
        idle += length
        covered += tr.union_length(
            [(max(a, start), min(b, start + length)) for a, b in cover
             if b > start and a < start + length])
    return idle, covered


def _child(spans: Sequence[Span], parent: Span, name: str
           ) -> Optional[Span]:
    for s in spans:
        if s.name == name and s.line == parent.line and \
                parent.start <= s.start and s.end <= parent.end:
            return s
    return None


def steps_in(spans: Sequence[Span], window: tr.Interval) -> List[Span]:
    """The ``engine.step`` spans wholly inside ``window``."""
    return [s for s in spans if s.name == "engine.step"
            and window[0] <= s.start and s.end <= window[1]]


def host_ms_per_dispatch(spans: Sequence[Span], window: tr.Interval
                         ) -> Optional[float]:
    """The host's serial time per decode dispatch: the mean, over each two
    consecutive ``engine.step`` spans inside ``window`` that are both
    decode steps, of the time from the end of the first one's
    ``engine.wait`` to the start of the second one's ``engine.dispatch``.
    None with no such pair."""
    steps = steps_in(spans, window)
    times = []
    for a, b in zip(steps, steps[1:]):
        if a.args.get("kind") != "decode" or b.args.get("kind") != "decode":
            continue
        wait = _child(spans, a, "engine.wait")
        dispatch = _child(spans, b, "engine.dispatch")
        if wait is not None and dispatch is not None:
            times.append(dispatch.start - wait.end)
    if not times:
        return None
    return sum(times) / len(times) * 1e-6


PREFILL_KINDS = ("prefill", "prefill_packed")


def prefill_share(spans: Sequence[Span], window: tr.Interval
                  ) -> Optional[float]:
    """Share of ``window`` (%) inside ``engine.step`` spans of a prefill
    kind (``PREFILL_KINDS``), clipped to the window; None when no engine
    span was read."""
    lo, hi = window
    if hi <= lo or not any(s.name.startswith("engine.") for s in spans):
        return None
    spent = sum(max(0.0, min(s.end, hi) - max(s.start, lo))
                for s in spans if s.name == "engine.step"
                and s.args.get("kind") in PREFILL_KINDS)
    return 100.0 * spent / (hi - lo)


def stream_delay_ms(sum0: float, count0: int, sum1: float, count1: int
                    ) -> Optional[float]:
    """Mean ``msb_stream_delay_seconds`` between two readings of its sum
    and count, in ms; None when nothing was observed between them."""
    if count1 <= count0:
        return None
    return (sum1 - sum0) / (count1 - count0) * 1e3


def module_gaps_ms(dev: tr.DeviceTrace, window: tr.Interval, prefix: str
                   ) -> Optional[float]:
    """Mean device-side gap (ms) between consecutive runs of the programs
    named ``prefix`` inside ``window``, where no other program ran between
    them; None with no such pair."""
    mods = [m for m in dev.modules
            if window[0] <= m.start and m.end <= window[1]]
    gaps = [b.start - a.end for a, b in zip(mods, mods[1:])
            if tr.module_short(a.name).startswith(prefix)
            and tr.module_short(b.name).startswith(prefix)]
    if not gaps:
        return None
    return sum(gaps) / len(gaps) * 1e-6
