"""Reduction of a profiler trace to device metrics.

The JAX profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
On a TPU each chip is a plane ``/device:TPU:<n>`` with a line
``XLA Modules`` (one event per program run, named ``jit_<fn>(<hash>)``) and
a line ``XLA Ops`` (one event per HLO operation, named by the operation's
whole HLO text, e.g. ``%msb_matmul.1 = f32[32,8192]{...} custom-call(
bf16[32,2048]{...} %x.1, u8[2048,4096]{...} ..., f32[128,8,2048]{...}
...)``). Times are nanoseconds from the start of the trace.

Busy time is the union of the operation intervals, so overlapping
operations count once; the idle share is 1 - busy / window.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]          # (start_ns, end_ns)


@dataclasses.dataclass
class Event:
    name: str
    start: float                        # ns
    dur: float                          # ns

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass
class DeviceTrace:
    """The device events of one chip."""
    plane: str
    modules: List[Event]
    ops: List[Event]


def find_xplane(directory: str) -> str:
    paths = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under "
                                f"{directory}, found {len(paths)}")
    return paths[0]


def load(path: str) -> Tuple[List[DeviceTrace], Optional[Tuple[int, int]]]:
    """Device planes of a trace file, in chip order, and the profile's
    start and stop as wall-clock ns since the epoch (None where the file
    does not say). Event times count from that start."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out, span = [], None
    for plane in pd.planes:
        if plane.name == "Task Environment":
            st = {k: v for k, v in plane.stats}
            if "profile_start_time" in st and "profile_stop_time" in st:
                span = (int(st["profile_start_time"]),
                        int(st["profile_stop_time"]))
        if not re.fullmatch(r"/device:TPU:\d+", plane.name):
            continue
        lines = {ln.name: ln for ln in plane.lines}

        def events(line):
            if line not in lines:
                return []
            return sorted((Event(e.name, e.start_ns, e.duration_ns)
                           for e in lines[line].events),
                          key=lambda e: e.start)

        out.append(DeviceTrace(plane.name, events("XLA Modules"),
                               events("XLA Ops")))
    out.sort(key=lambda t: int(t.plane.rsplit(":", 1)[1]))
    return out, span


def host_window(span: Optional[Tuple[int, int]], wall0_ns: int,
                wall1_ns: int) -> Interval:
    """The host's measured stretch ``[wall0, wall1]`` (``time.time_ns()``,
    the clock the profiler stamps its start with) on the trace's clock,
    inside the profile. The profiler's own start and stop fall outside it,
    so their edges are not read as idle device time."""
    if span is None:
        return 0.0, float(wall1_ns - wall0_ns)
    start, stop = span
    return (float(max(0, wall0_ns - start)),
            float(min(stop, wall1_ns) - start))


def union_length(intervals: Sequence[Interval]) -> float:
    """Total length covered by the union of ``intervals``."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def busy_ns(events: Sequence[Event], window: Optional[Interval] = None
            ) -> float:
    """Union of the events' intervals, clipped to ``window``."""
    iv = []
    for e in events:
        s, t = e.start, e.end
        if window is not None:
            s, t = max(s, window[0]), min(t, window[1])
        if t > s:
            iv.append((s, t))
    return union_length(iv)


def idle_gaps(events: Sequence[Event], window: Interval
              ) -> List[Tuple[float, float]]:
    """(start, length) of each stretch of the window with no event."""
    iv = sorted((max(e.start, window[0]), min(e.end, window[1]))
                for e in events if e.end > window[0] and e.start < window[1])
    gaps, cur = [], window[0]
    for s, t in iv:
        if s > cur:
            gaps.append((cur, s - cur))
        cur = max(cur, t)
    if window[1] > cur:
        gaps.append((cur, window[1] - cur))
    return gaps


def op_short(name: str) -> str:
    """``%msb_matmul.1 = f32[...] ...`` -> ``msb_matmul``."""
    m = re.match(r"%?([\w\-]+?)(\.\d+)*\s*=", name)
    return m.group(1) if m else name.split(" ", 1)[0]


def module_short(name: str) -> str:
    """``jit__paged_horizon_step(123)`` -> ``jit__paged_horizon_step``."""
    return name.split("(", 1)[0]


def matching(events: Sequence[Event], prefixes: Sequence[str]
             ) -> List[Event]:
    """Module events whose short name starts with one of ``prefixes``."""
    return [e for e in events
            if module_short(e.name).startswith(tuple(prefixes))]


def module_of_ops(modules: Sequence[Event], ops: Sequence[Event]
                  ) -> List[Optional[str]]:
    """For each op (sorted by start), the short name of the module event
    that contains its start, or None."""
    out, i = [], 0
    for op in ops:
        while i < len(modules) and modules[i].end < op.start:
            i += 1
        if i < len(modules) and modules[i].start <= op.start:
            out.append(module_short(modules[i].name))
        else:
            out.append(None)
    return out


def self_ns(ops: Sequence[Event]) -> List[float]:
    """Each op's duration less the part its nested ops cover (a ``while``
    op holds the ops of its loop body on the same line)."""
    out = [e.dur for e in ops]
    stack: List[int] = []
    for i, e in enumerate(ops):
        while stack and ops[stack[-1]].end <= e.start:
            stack.pop()
        if stack and e.end <= ops[stack[-1]].end:
            out[stack[-1]] -= e.dur
        stack.append(i)
    return out


def breakdown(tr: DeviceTrace, window: Interval, top: int = 10) -> dict:
    """The contract's ``breakdown``: the device operations that took most
    time (self time, by module and operation) and the longest idle gaps,
    each named by the programs around it; in seconds."""
    owners = module_of_ops(tr.modules, tr.ops)
    by: Dict[str, float] = {}
    for op, mod, own in zip(tr.ops, owners, self_ns(tr.ops)):
        k = f"{mod or 'no module'}:{op_short(op.name)}"
        by[k] = by.get(k, 0.0) + own
    ops = sorted(by.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(idle_gaps(tr.ops, window), key=lambda g: -g[1])[:top]
    mods = tr.modules

    def around(t):
        before = [m for m in mods if m.end <= t]
        after = [m for m in mods if m.start >= t]
        b = module_short(before[-1].name) if before else "start"
        a = module_short(after[0].name) if after else "end"
        return f"host, unattributed: after {b}, before {a}"

    return {"device_ops": [[k, v * 1e-9] for k, v in ops],
            "idle_gaps": [[around(s), g * 1e-9] for s, g in gaps]}


# -- the kernel's operations and bytes, from the shapes it was called with --

_SHAPE = re.compile(r"(bf16|f32|f16|u8|s8|s32|u32)\[([\d,]*)\]")
_BYTES = {"bf16": 2, "f16": 2, "f32": 4, "u8": 1, "s8": 1, "s32": 4,
          "u32": 4}


def hlo_shapes(text: str) -> List[Tuple[str, Tuple[int, ...]]]:
    """(dtype, dims) of the result and each operand, in order."""
    return [(t, tuple(int(d) for d in dims.split(",") if d))
            for t, dims in _SHAPE.findall(text)]


def msb_call_cost(text: str) -> Tuple[float, float]:
    """(FLOPs, bytes) of one ``msb_matmul`` call from its HLO text.

    Operands: x (M, K), packed codes (K, N/2) uint8, codebooks (one
    8-entry row per 64 weights), optional bias; result (M, N). FLOPs are
    2 M K N; bytes read the codes, codebooks, activations and bias once and
    write the result once.
    """
    shapes = hlo_shapes(text.split(" custom_call_target", 1)[0])
    (out_t, out_d), (x_t, x_d), (_, packed_d) = shapes[0], shapes[1], \
        shapes[2]
    m, k = x_d
    n = packed_d[1] * 2
    nbytes = 0
    for t, d in shapes:
        size = 1
        for v in d:
            size *= v
        nbytes += size * _BYTES[t]
    return 2.0 * m * k * n, float(nbytes)


def roofline_seconds(flops: float, nbytes: float, peaks: dict
                     ) -> Tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    t_c = flops / peaks["bf16_flops"]
    t_m = nbytes / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
