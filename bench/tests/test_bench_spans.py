"""The program's host spans read from a trace (``bench/spans.py``): the
readings on synthetic spans worked out by hand, and span loading and gap
naming on a small trace recorded on a TPU v5e from a tiny engine
(``bench/tests/tiny.py``) served over HTTP: a packed prefill wave and two
decode dispatches (``bench/span_probe.py --parts fixture``, cut with
``--trim`` to what the readers read)."""
import dataclasses
from pathlib import Path

import pytest

from bench import spans as bs
from bench import trace as tr

FIXTURE = str(Path(__file__).parent / "data" / "tpu_spans.xplane.pb")
OLD_FIXTURE = str(Path(__file__).parent / "data" / "tpu_fixture.xplane.pb")
MS = 1e6


def _step(t0, kind, line=0, prep=1, disp=1, wait=10, apply=2):
    """The scheduler's span at ``t0`` (ms), then one engine.step with its
    children, in order."""
    out = [bs.Span("engine.schedule", t0 * MS, 0.5 * MS, line, {})]
    t = s0 = t0 + 0.5
    for name, d in (("engine.prepare", prep), ("engine.dispatch", disp),
                    ("engine.wait", wait), ("engine.apply", apply)):
        out.append(bs.Span(name, t * MS, d * MS, line, {}))
        t += d
    step = bs.Span("engine.step", s0 * MS, (t - s0) * MS, line,
                   {"kind": kind, "rows": 4})
    return [step] + out, t


def _loop(kinds, gap=3.0):
    """Engine-loop iterations: a step, then ``engine.stream`` and
    ``engine.housekeeping`` filling ``gap`` ms before the next step."""
    spans, t = [], 0.0
    for kind in kinds:
        s, t = _step(t, kind)
        spans += s
        spans.append(bs.Span("engine.stream", t * MS, gap / 3 * MS, 0, {}))
        spans.append(bs.Span("engine.housekeeping", (t + gap / 3) * MS,
                             2 * gap / 3 * MS, 0, {}))
        t += gap
    return sorted(spans, key=lambda s: (s.start, -s.dur)), t


def test_host_ms_per_dispatch_reads_decode_pairs():
    spans, end = _loop(["decode", "decode", "prefill_packed", "decode",
                        "decode"])
    # wait end -> next dispatch start: apply 2 + stream/housekeeping 3 +
    # schedule 0.5 + prepare 1
    assert bs.host_ms_per_dispatch(spans, (0, end * MS)) == pytest.approx(6.5)
    # pairs with a prefill step between are not read
    assert bs.host_ms_per_dispatch(spans[:7], (0, end * MS)) is None
    assert bs.host_ms_per_dispatch([], (0, end * MS)) is None
    # steps cut by the window's edges are not read
    assert bs.host_ms_per_dispatch(spans, (1 * MS, 30 * MS)) is None


def test_prefill_share_clips_to_the_window():
    spans, end = _loop(["decode", "prefill", "decode"])
    step_ms = 1 + 1 + 10 + 2
    assert bs.prefill_share(spans, (0, end * MS)) == pytest.approx(
        100 * step_ms / end)
    # half of the prefill step inside the window
    mid = (0.5 + step_ms + 3 + 0.5 + step_ms / 2) * MS
    assert bs.prefill_share(spans, (mid, end * MS)) == pytest.approx(
        100 * (step_ms / 2) / (end - mid / MS))
    # only prefill kinds count: a step of any other kind is not prefill
    odd = [dataclasses.replace(s, args={"kind": "idle"})
           if s.args.get("kind") == "prefill" else s for s in spans]
    assert bs.prefill_share(odd, (0, end * MS)) == 0.0
    packed, _ = _loop(["decode", "prefill_packed", "decode"])
    assert bs.prefill_share(packed, (0, end * MS)) == pytest.approx(
        100 * step_ms / end)
    assert bs.prefill_share(spans, (0, 0)) is None
    frontdoor = [bs.Span("frontdoor.write", 0, MS, 1, {})]
    assert bs.prefill_share(frontdoor, (0, end * MS)) is None


def test_stream_delay_is_the_windows_mean():
    assert bs.stream_delay_ms(1.0, 10, 1.5, 110) == pytest.approx(5.0)
    assert bs.stream_delay_ms(1.0, 10, 1.0, 10) is None


def test_gap_takes_the_innermost_span_covering_most_of_it():
    step = bs.Span("engine.step", 0, 100, 0, {"kind": "decode"})
    wait = bs.Span("engine.wait", 10, 60, 0, {})
    stream = bs.Span("engine.stream", 100, 20, 0, {})
    write = bs.Span("frontdoor.write", 0, 200, 1, {})
    spans = [step, wait, stream, write]
    assert bs.gap_label(spans, 20, 10) == "host:engine.wait"
    # 0-10 and 70-100 under the step alone, 10-70 under the wait
    assert bs.gap_label(spans, 0, 100) == "host:engine.wait"
    assert bs.gap_label(spans, 60, 50) == "host:engine.step"
    assert bs.gap_label(spans, 95, 30) == "host:engine.stream"
    # engine spans come first; the front door names only what they leave
    assert bs.gap_label(spans, 150, 20) == "host:frontdoor.write"
    assert bs.gap_label(spans, 300, 5) is None
    # spans that cover under half of a gap do not name it
    assert bs.gap_label(spans[:3], 110, 30) is None
    assert bs.gap_label(spans[:3], 110, 20) == "host:engine.stream"


def test_breakdown_names_gaps_and_keeps_the_rest():
    ops = [tr.Event("%fusion.1 = f32[]", 0, 40),
           tr.Event("%fusion.2 = f32[]", 50, 40),
           tr.Event("%copy.3 = f32[]", 120, 10)]
    mods = [tr.Event("jit_a(1)", 0, 90), tr.Event("jit_b(2)", 120, 10)]
    dev = tr.DeviceTrace("/device:TPU:0", mods, ops)
    spans = [bs.Span("engine.wait", 85, 40, 0, {})]
    out = bs.breakdown(dev, (0, 140), spans)
    assert out["device_ops"] == tr.breakdown(dev, (0, 140))["device_ops"]
    assert out["idle_gaps"] == [
        ["host:engine.wait", 30 * 1e-9],
        ["host, unattributed: after start, before jit_b", 10 * 1e-9],
        ["host, unattributed: after jit_b, before end", 10 * 1e-9]]
    idle, under = bs.idle_under(dev, (0, 140), spans)
    assert (idle, under) == (50, 30)
    assert bs.module_gaps_ms(dev, (0, 140), "jit_") == pytest.approx(30e-6)
    assert bs.module_gaps_ms(dev, (0, 140), "jit_a") is None


def test_old_trace_has_no_spans_and_keeps_its_labels():
    assert bs.load_spans(OLD_FIXTURE) == []
    devs, span = tr.load(OLD_FIXTURE)
    window = (0.0, float(span[1] - span[0]))
    assert bs.breakdown(devs[0], window, []) == tr.breakdown(devs[0], window)


# -- the recorded trace --------------------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    spans = bs.load_spans(FIXTURE)
    devs, _ = tr.load(FIXTURE)
    engine = [s for s in spans if s.name.startswith("engine.")]
    # the engine loop's stretch: its first span's start to its last's end
    window = (engine[0].start, max(s.end for s in engine))
    return spans, devs[0], window


def test_recorded_spans_and_their_args(recorded):
    spans, _, window = recorded
    names = {s.name for s in spans}
    assert {"engine.step", "engine.schedule", "engine.prepare",
            "engine.dispatch", "engine.wait", "engine.apply",
            "engine.commands", "engine.stream", "engine.housekeeping",
            "frontdoor.detokenize", "frontdoor.write"} <= names
    steps = bs.steps_in(spans, window)
    assert [s.args["kind"] for s in steps].count("decode") >= 2
    for step in steps:
        assert step.args["rows"] >= 1
        # the readers use the step's own phases; the scheduler's span may
        # lie just before the step or inside it
        kids = [s for s in spans if s.line == step.line
                and step.start <= s.start and s.end <= step.end
                and s is not step and s.name != "engine.schedule"]
        assert [k.name for k in kids] == [
            "engine.prepare", "engine.dispatch", "engine.wait",
            "engine.apply"]
    # the front door's threads are not the engine's
    lines = {s.line for s in spans if s.name.startswith("engine.")}
    assert not lines & {s.line for s in spans
                        if s.name.startswith("frontdoor.")}


def test_recorded_programs_run_inside_their_steps(recorded):
    spans, dev, window = recorded
    steps = bs.steps_in(spans, window)
    # the recording starts inside a step: read the programs of whole steps
    runs = [m for m in dev.modules
            if steps[0].start <= m.start < steps[-1].end]
    assert runs
    for m in runs:
        step, = [s for s in steps if s.start <= m.start <= s.end]
        kind = "decode" if tr.module_short(m.name) == \
            "jit__paged_horizon_step" else "prefill_packed"
        assert step.args["kind"] == kind


def test_recorded_idle_gaps_are_named(recorded):
    spans, dev, window = recorded
    idle, under = bs.idle_under(dev, window, spans)
    assert idle > 0 and under >= 0.9 * idle
    gaps = bs.breakdown(dev, window, spans)["idle_gaps"]
    assert gaps and all(label.startswith("host:engine.")
                        for label, _ in gaps if _ > 1e-4)
    # by hand: the end of each decode step's wait to the next one's dispatch
    steps = bs.steps_in(spans, window)
    by_hand = []
    for a, b in zip(steps, steps[1:]):
        if a.args["kind"] == b.args["kind"] == "decode":
            wait, = [s for s in spans if s.name == "engine.wait"
                     and a.start <= s.start <= a.end]
            disp, = [s for s in spans if s.name == "engine.dispatch"
                     and b.start <= s.start <= b.end]
            by_hand.append((disp.start - wait.end) * 1e-6)
    assert by_hand
    assert bs.host_ms_per_dispatch(spans, window) == pytest.approx(
        sum(by_hand) / len(by_hand))
    assert 0 < bs.prefill_share(spans, window) < 100
