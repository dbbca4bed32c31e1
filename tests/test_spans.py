"""Host spans and phase clocks (serve/spans.py): the helper, the engine's
``host_phase_seconds``, the supervisor's aggregation across rebuilds, the
per-program retrace counts (and ``chip_smoke.py``'s reading of them), and
the ``/metrics`` families they feed."""
import dataclasses
import glob
import json
import os
import socket
import tempfile
import time

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import smoke_config
from repro.core import QuantPolicy, quantize_params
from repro.core.quantize import quantize_blockwise
from repro.models import Model
from repro.serve import (APIServer, ContinuousEngine, EngineSupervisor,
                         FaultEvent, FaultPlan, ServeMetrics,
                         jit_trace_count, jit_trace_counts)
from repro.serve.spans import PhaseClock, span
from chip_smoke import retraced_since

STEP_PHASES = ("engine.schedule", "engine.step", "engine.prepare",
               "engine.dispatch", "engine.wait", "engine.apply")
LOOP_PHASES = ("engine.commands", "engine.stream", "engine.housekeeping")
PROMPTS = [list(range(1, 9)), [3, 5, 7, 2], [10, 11, 12, 13, 14, 15]]


@pytest.fixture(scope="module")
def qsetup():
    cfg = smoke_config("internlm2-1.8b")
    cfg = dataclasses.replace(cfg, vocab_size=64, vocab_round=64)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    params = jax.tree_util.tree_map(lambda p: p.astype(jnp.float32), params)
    qparams, _ = quantize_params(params, QuantPolicy(
        bits=4, block=64, solver="kmeans", min_size=1024))
    return model, qparams


def _engine(model, params, **kw):
    kw.setdefault("max_batch", 4)
    kw.setdefault("page_size", 4)
    kw.setdefault("num_pages", 64)
    kw.setdefault("prefill_chunk", 8)
    return ContinuousEngine(model, params, **kw)


def _host_events(fn):
    """Run ``fn`` under a CPU profiler trace; the host plane's events as
    ``(name, stats dict)``."""
    from jax.profiler import ProfileData
    tmp = tempfile.mkdtemp(prefix="spans-test-")
    jax.profiler.start_trace(tmp)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)
    return [(e.name, dict(e.stats))
            for plane in ProfileData.from_file(path).planes
            if plane.name == "/host:CPU"
            for line in plane.lines for e in line.events
            if e.name.startswith(("engine.", "frontdoor.", "quantize."))]


# -- the helper ----------------------------------------------------------------

def test_phase_clock_accumulates_per_name():
    clock = PhaseClock()
    for _ in range(3):
        with clock.span("a"):
            time.sleep(0.002)
    with clock.span("b", kind="x", rows=2):
        pass
    assert clock.counts == {"a": 3, "b": 1}
    assert clock.seconds["a"] >= 0.006
    assert 0 <= clock.seconds["b"] < clock.seconds["a"]
    snap = clock.snapshot()
    with clock.span("a"):
        pass
    assert snap["a"] < clock.seconds["a"]       # a copy, not a view
    with span("c"):                             # no clock: trace only
        pass
    assert "c" not in clock.seconds


def test_spans_land_on_the_host_plane_with_their_args():
    clock = PhaseClock()

    def work():
        with clock.span("engine.step", kind="decode", rows=32):
            with clock.span("engine.wait"):
                jnp.ones(8).block_until_ready()
        with span("frontdoor.write"):
            pass

    events = dict(_host_events(work))
    assert events["engine.step"] == {"kind": "decode", "rows": 32}
    assert "engine.wait" in events and "frontdoor.write" in events
    assert clock.counts == {"engine.step": 1, "engine.wait": 1}


def test_quantizer_spans():
    w = jax.random.normal(jax.random.PRNGKey(1), (8, 128))
    names = [n for n, _ in _host_events(
        lambda: jax.block_until_ready(quantize_blockwise(w, solver="dp")))]
    for phase in ("quantize.prepare", "quantize.solve", "quantize.encode"):
        assert names.count(phase) == 1, phase


# -- the engine ----------------------------------------------------------------

@pytest.mark.parametrize("horizon", [1, 4])
def test_engine_reports_every_phase(qsetup, horizon):
    model, params = qsetup
    eng = _engine(model, params, decode_horizon=horizon)
    for p in PROMPTS:
        eng.submit(np.asarray(p), 6)
    eng.run()
    st = eng.stats()
    phases = st["host_phase_seconds"]
    assert set(phases) == set(STEP_PHASES)
    assert all(v > 0 for v in phases.values())
    assert eng.phases.counts["engine.step"] == st["steps"]
    assert eng.phases.counts["engine.dispatch"] == st["host_syncs"]
    assert eng.phases.counts["engine.wait"] == st["host_syncs"]
    # the children lie inside the step; the scheduler's choice precedes it
    inner = sum(phases[p] for p in STEP_PHASES[2:])
    assert inner <= phases["engine.step"]
    assert eng.phases.counts["engine.schedule"] >= st["steps"]
    eng.close()


def test_a_step_with_no_work_chosen_leaves_no_step_span(qsetup):
    """With work queued but none the scheduler can run (admission or a
    reservation blocked), ``step()`` returns False and only the
    scheduler's span is counted: ``engine.step`` counts equal ``steps``,
    and ``prefill_share`` never reads such a step as prefill."""
    model, params = qsetup
    eng = _engine(model, params, decode_horizon=4)
    eng.submit(np.asarray(PROMPTS[0]), 6)
    schedule, blocked = eng.scheduler.schedule, [2]

    def blocked_then_schedule():
        if blocked[0]:
            blocked[0] -= 1
            return None
        return schedule()

    eng.scheduler.schedule = blocked_then_schedule
    for _ in range(2):
        assert eng.has_work and eng.step() is False
    assert eng.stats()["steps"] == 0
    assert eng.phases.counts == {"engine.schedule": 2}
    events = _host_events(eng.step)
    assert [(n, a) for n, a in events if n == "engine.step"] == [
        ("engine.step", {"kind": "prefill_packed", "rows": 1})]
    eng.run()
    assert eng.phases.counts["engine.step"] == eng.stats()["steps"]
    assert eng.phases.counts["engine.schedule"] == \
        eng.stats()["steps"] + 2            # the two blocked
    eng.close()


def test_supervisor_phases_survive_rebuilds(qsetup):
    """A rebuilt engine's clocks restart at zero; the supervisor's
    aggregated host_phase_seconds never regress (``set_to`` would raise)."""
    model, params = qsetup
    plan = FaultPlan([FaultEvent("apply", 4, "crash"),
                      FaultEvent("step", 8, "crash")])
    sup = EngineSupervisor(
        lambda: _engine(model, params, faults=plan), watchdog=True)
    metrics = ServeMetrics()
    for p in PROMPTS:
        sup.submit(np.asarray(p), 10)
    prev = {}
    while sup.has_work:
        if not sup.step():
            break
        metrics.sync_engine(sup)
        phases = sup.stats()["host_phase_seconds"]
        for k, v in prev.items():
            assert phases[k] >= v, k
        prev = phases
    assert sup.n_restarts == 2
    assert set(prev) == set(STEP_PHASES)
    for phase, v in prev.items():
        assert metrics.phase_seconds.value(phase=phase) == v
    sup.close()


def test_retrace_counts_name_the_program(qsetup):
    model, params = qsetup
    total, by_name = jit_trace_count(), jit_trace_counts()
    assert total == sum(by_name.values())
    # a static model of its own traces afresh
    fresh = Model(dataclasses.replace(model.cfg, decode_chunk=977))
    eng = _engine(fresh, params, decode_horizon=4)
    eng.submit(np.asarray(PROMPTS[0]), 6)
    eng.run()
    after = jit_trace_counts()
    grew = retraced_since(by_name, after)
    assert set(grew) == {"_paged_prefill_packed", "_paged_horizon_step"}
    assert all(n >= 1 for n in grew.values())
    assert jit_trace_count() == sum(after.values())
    assert retraced_since(after, jit_trace_counts()) == {}


# -- the front door -------------------------------------------------------------

def _stream(host, port, payload):
    """One streamed completion over a raw socket; its SSE payloads."""
    body = json.dumps(payload).encode()
    with socket.create_connection((host, port), timeout=120) as s:
        s.sendall((f"POST /v1/completions HTTP/1.1\r\nHost: t\r\n"
                   f"Content-Length: {len(body)}\r\n\r\n").encode() + body)
        buf = b""
        while True:
            chunk = s.recv(65536)
            if not chunk:
                break
            buf += chunk
    frames = buf.split(b"\r\n\r\n", 1)[1].decode().split("\n\n")
    return [json.loads(f[len("data: "):]) for f in frames
            if f.startswith("data: {")]


def _get(host, port, path):
    with socket.create_connection((host, port), timeout=30) as s:
        s.sendall(f"GET {path} HTTP/1.1\r\nHost: t\r\n\r\n".encode())
        buf = b""
        while True:
            chunk = s.recv(65536)
            if not chunk:
                break
            buf += chunk
    return buf.split(b"\r\n\r\n", 1)[1].decode()


def test_metrics_render_phases_and_stream_delay(qsetup):
    model, params = qsetup
    srv = APIServer(_engine(model, params, decode_horizon=2))
    host, port = srv.serve_background()
    try:
        frames = _stream(host, port, {"prompt": [1, 2, 3], "max_tokens": 9,
                                      "stream": True})
        n_token_frames = sum(1 for f in frames
                             if f["choices"][0]["token_ids"])
        assert n_token_frames >= 2
        delay = srv.metrics.stream_delay
        deadline = time.monotonic() + 10
        while delay.count() < n_token_frames and \
                time.monotonic() < deadline:
            time.sleep(0.01)
        assert delay.count() == n_token_frames
        assert 0 < delay.sum() < 10 * n_token_frames
        text = _get(host, port, "/metrics")
    finally:
        srv.close()
    assert "# TYPE msb_engine_phase_seconds_total counter" in text
    assert "# TYPE msb_stream_delay_seconds histogram" in text
    assert f"msb_stream_delay_seconds_count {n_token_frames}" in text
    for phase in STEP_PHASES + LOOP_PHASES:
        assert f'msb_engine_phase_seconds_total{{phase="{phase}"}}' in text
