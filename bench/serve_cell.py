"""Serving cells: a configuration served by ``APIServer`` over HTTP.

Set-up makes the weights on the device from the seed (``weights.py``),
builds ``ContinuousEngine(execution="packed")`` with the configuration's
engine settings behind ``APIServer(warmup=True)``, and waits until every
dispatch shape is compiled. The window then drives ``POST /v1/completions``
with the mix's schedule (``traffic.py``, ``client.py``). After the window
the served tokens of a sample of requests are judged against the plain
float32 reference (``reference.py``).
"""
from __future__ import annotations

import dataclasses
import math
import shutil
import statistics
import tempfile
import time
from typing import Dict, List, Optional

import numpy as np

from . import flops as fl
from . import stats as st
from . import trace as tr
from . import traffic as traffic_mod
from .client import LoadClient, Record
from .context import Context
from .device import log

TRACE_SECONDS = 10.0      # the traced part of a --trace 1 window
TRACE_LEAD_S = 1.0        # it starts this long after the window does
TAIL_CAP_S = 45.0         # longest wait, past the close, for due requests
BURST_GAP_S = 0.05        # a pause this long between arrivals ends a burst
BURST_MAX_S = 0.25        # and a burst lasts no longer than this
CLOSE_CAP_S = 15.0        # longest wait, past w1, for the closing burst
FILL_CAP_S = 240.0        # longest wait for a batch mix to fill the batch
SAMPLE_REQUESTS = 6       # requests judged against the reference
WIDTHS = {"d_model": "hidden_size", "n_layers": "num_hidden_layers",
          "n_heads": "num_attention_heads",
          "n_kv_heads": "num_key_value_heads", "d_ff": "intermediate_size",
          "vocab_size": "vocab_size", "tie_embeddings": "tie_word_embeddings",
          "rope_theta": "rope_theta", "norm_eps": "rms_norm_eps"}


def build_model(conf: dict):
    """The program's model for a configuration file, checked against the
    file's published numbers."""
    from repro.configs import get_config
    from repro.models import Model
    prog = conf["program"]
    cfg = dataclasses.replace(get_config(prog["registry"]),
                              **prog.get("overrides", {}))
    pub = conf["published"]
    for ours, theirs in WIDTHS.items():
        if getattr(cfg, ours) != pub[theirs]:
            raise ValueError(f"{conf['name']}: program {ours}="
                             f"{getattr(cfg, ours)!r}, published {theirs}="
                             f"{pub[theirs]!r}")
    if cfg.embed_scale != pub.get("embedding_multiplier", 1.0):
        raise ValueError(f"{conf['name']}: embed_scale {cfg.embed_scale}")
    q_scale = pub.get("attention_multiplier", 0.0)
    if cfg.query_scale != q_scale:
        raise ValueError(f"{conf['name']}: query_scale {cfg.query_scale}")
    return Model(cfg)


class Server:
    """The system under test, set up from a configuration and a seed."""

    def __init__(self, conf: dict, seed: int):
        import jax
        from repro.serve import APIServer, ContinuousEngine
        from . import weights as W
        self.conf = conf
        self.model = build_model(conf)
        self.shapes = W.DenseShapes(conf["published"])
        t = time.monotonic()
        params = W.packed_params(seed, self.shapes, self.model.param_shapes(),
                                 self.model.cfg.dtype)
        jax.block_until_ready(params)
        log(f"weights from seed {seed}: {self.shapes.n_weights()} weights, "
            f"packed, in {time.monotonic() - t:.3f} s")
        t = time.monotonic()
        self.engine = ContinuousEngine(self.model, params, execution="packed",
                                       **conf["engine"])
        self.srv = APIServer(self.engine, warmup=True,
                             max_tokens_cap=conf["engine"]["max_seq"])
        self.host, self.port = self.srv.serve_background()
        loop = self.srv.engine_loop
        deadline = time.monotonic() + 900
        while loop.warming or self.engine.warmup_entries == 0:
            if loop.died is not None or not loop.alive:
                raise RuntimeError(f"engine loop died in warmup: "
                                   f"{loop.died!r}")
            if time.monotonic() > deadline:
                raise TimeoutError("warmup did not finish")
            time.sleep(0.05)
        log(f"engine built and warmed ({self.engine.warmup_entries} "
            f"dispatch shapes, warmup {self.engine.warmup_seconds:.3f} s) "
            f"in {time.monotonic() - t:.3f} s")

    def stats(self) -> Optional[dict]:
        try:
            return self.engine.stats()
        except RuntimeError:        # a dict resized by the engine thread
            return None

    def kv_use(self) -> tuple:
        """(tokens held by live sequences, pages referenced, pages in the
        pool), read from the cache's host bookkeeping."""
        c = self.engine.cache
        pool = c.num_pages - 1
        return (int(c.seq_lens.sum()),
                pool - c.n_free_pages - c.n_cached_pages, pool)

    def swap_weights(self, seed: int):
        """New weights from ``seed`` in the same compiled programs (for the
        calibration, which reads many seeds in one process)."""
        import jax
        from . import weights as W
        params = W.packed_params(seed, self.shapes, self.model.param_shapes(),
                                 self.model.cfg.dtype)
        jax.block_until_ready(params)
        self.engine.params = params

    def close(self):
        self.srv.close()
        if self.srv.engine_loop.died is not None:
            raise RuntimeError(f"engine loop died: "
                               f"{self.srv.engine_loop.died!r}")
        self.engine.close()
        self.engine.cache.pools = None
        self.engine.params = None
        self.engine = self.srv = None


@dataclasses.dataclass
class Window:
    """What one window of traffic produced."""
    requests: list
    records: Dict[int, Record]
    w0: float
    w1: float
    due_in_window: List[int]
    counters: Dict[str, float]
    samples: Dict[str, List[float]]
    lateness: List[float]
    traced: Optional[tuple] = None     # (DeviceTrace, window on its clock,
                                       #  host h0, host h1)


def run_window(server: Server, mix: dict, seed: int, seconds: float,
               trace: bool = False, tail_cap_s: float = TAIL_CAP_S
               ) -> Window:
    from repro.serve import jit_trace_count
    vocab = server.conf["published"]["vocab_size"]
    max_batch = server.conf["engine"]["max_batch"]
    requests = traffic_mod.generate(mix, seed, vocab, seconds, max_batch)
    by_index = {r.index: r for r in requests}
    batch_full = mix["window"] == "batch_decoding"
    state = {"w0": None, "series": []}

    def sampler(now):
        s = server.stats()
        if s is not None:
            state["series"].append(
                (now, s["running"], s["tokens_out"], s["decode_steps"],
                 s["admissions"]) + server.kv_use())
        if batch_full and state["w0"] is None:
            decoding = sum(1 for r in client.records.values()
                           if r.events and not r.done)
            if decoding >= max_batch:
                state["w0"] = now

    client = LoadClient(server.host, server.port, sampler=sampler)
    t0 = time.monotonic() + 0.05
    if not batch_full:
        state["w0"] = t0 + float(mix["window"]["lead_in_s"])
    client.start(requests, t0)
    try:
        start = time.monotonic()
        note = start + 30
        while state["w0"] is None:
            now = time.monotonic()
            if now > start + FILL_CAP_S:
                raise TimeoutError("the batch never filled with decoding "
                                   "requests")
            if now > note:
                note += 30
                streaming = sum(1 for r in list(client.records.values())
                                if r.events and not r.done)
                log(f"filling the batch: {now - start:.0f} s, "
                    f"{streaming} requests streaming, engine {server.stats()}")
            time.sleep(0.005)
        w0 = state["w0"]
        _sleep_until(w0)
        s0, n_traces0 = server.stats() or {}, jit_trace_count()
        w1 = w0 + seconds
        traced = None
        if trace:
            _sleep_until(w0 + min(TRACE_LEAD_S, seconds / 4))
            traced = _trace(min(TRACE_SECONDS, seconds / 2))
        _sleep_until(w1)
        s1, n_traces1 = server.stats() or {}, jit_trace_count()
        due = [r.index for r in requests if w0 <= t0 + r.due_s < w1]
        end = w1 + CLOSE_CAP_S      # the rate closes with a whole burst
        while time.monotonic() < end and _closing_burst(
                client.records, w1, time.monotonic()) is None and not \
                _all_done(client.records, len(requests)):
            time.sleep(0.01)
        if not batch_full:          # wait, with load still on, for them
            end = w1 + tail_cap_s
            while time.monotonic() < end and not all(
                    i in client.records and client.records[i].done
                    for i in due):
                time.sleep(0.05)
    finally:
        client.stop()
    if n_traces1 != n_traces0:
        log(f"WARNING: {n_traces1 - n_traces0} dispatches traced inside "
            "the window")
    prompt_sent = sum(len(by_index[i].prompt)
                      for i, rec in client.records.items()
                      if rec.sent is not None and w0 <= rec.sent < w1)
    counters = {
        "prefix_positions_saved": (s1.get("prefix_positions_saved", 0)
                                   - s0.get("prefix_positions_saved", 0)),
        "prompt_tokens_sent": prompt_sent,
        "preemptions": s1.get("preemptions", 0) - s0.get("preemptions", 0),
        "traces_in_window": n_traces1 - n_traces0}
    series = [x for x in state["series"] if w0 <= x[0] < w1]
    samples = {"running": [x[1] for x in series]}
    if series:
        (_, _, tok0, dec0, adm0, *_), last = series[0], series[-1]
        counters.update(
            tokens_out=last[2] - tok0, decode_dispatches=last[3] - dec0,
            admissions=last[4] - adm0,
            live_tokens_mean=sum(x[5] for x in series) / len(series),
            live_tokens_max=max(x[5] for x in series),
            pages_used_mean=sum(x[6] for x in series) / len(series),
            pages_used_max=max(x[6] for x in series), pool_pages=last[7])
    if traced is not None:
        _, _, h0, h1 = traced
        inside = [x for x in series if h0 <= x[0] < h1]
        if len(inside) > 1:
            counters["trace_decode_dispatches"] = inside[-1][3] - inside[0][3]
            # each admission samples its first token in prefill
            counters["trace_decode_tokens"] = (
                inside[-1][2] - inside[0][2]
                - (inside[-1][4] - inside[0][4]))
    return Window(requests, dict(client.records), w0, w1, due, counters,
                  samples, list(client.lateness), traced)


def _all_done(records, n: int) -> bool:
    """Every one of the ``n`` requests sent and answered or failed."""
    recs = list(records.values())
    return len(recs) == n and all(r.done for r in recs)


def _arrivals(records, since: float) -> list:
    """(time, tokens) of every token-bearing event from ``since`` on."""
    return sorted((t, c) for r in list(records.values())
                  for t, c in zip(list(r.events), list(r.counts))
                  if t >= since)


def _closing_burst(records, w1: float, now: float = float("inf")):
    """The first burst of tokens that ends at or after ``w1`` and is over
    by ``now``, as ``(first, last, tokens)``; None while there is none."""
    for b in st.bursts(_arrivals(records, w1 - BURST_MAX_S), BURST_GAP_S,
                       BURST_MAX_S):
        if b[1] >= w1:
            over = now - b[1] >= BURST_GAP_S or now - b[0] >= BURST_MAX_S
            return b if over else None
    return None


def _sleep_until(t):
    while True:
        d = t - time.monotonic()
        if d <= 0:
            return
        time.sleep(min(d, 0.05))


def _trace(seconds: float):
    """Trace ``seconds`` of the device; the device's events, the host's
    stretch ``[h0, h1]`` on the trace's clock, and h0 and h1."""
    import jax
    tmp = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        jax.profiler.start_trace(tmp)
        h0, wall0 = time.monotonic(), time.time_ns()
        _sleep_until(h0 + seconds)
        h1, wall1 = time.monotonic(), time.time_ns()
        jax.profiler.stop_trace()
        devs, span = tr.load(tr.find_xplane(tmp))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    window = tr.host_window(span, wall0, wall1)
    log(f"traced {(window[1] - window[0]) * 1e-9:.6f} s of the window, "
        f"{window[0] * 1e-6:.3f} ms after the profile's start")
    return devs[0], window, h0, h1


# -- end-to-end metrics --------------------------------------------------------

def end_to_end(win: Window) -> Dict[str, float]:
    recs = win.records
    out = {}
    # tokens arrive in bursts, one per decode dispatch, and the window
    # opens at one (a batch mix's last first token). It closes with the
    # first whole burst that ends at or after w1, which it counts, so it
    # spans whole dispatches; with no such burst it closes at w1, and a
    # stall anywhere, the end included, lowers the rate
    burst = _closing_burst(recs, win.w1)
    close = burst[1] if burst else win.w1
    tokens = sum(c for t, c in _arrivals(recs, win.w0) if t <= close)
    out["output_tokens_per_s"] = tokens / (close - win.w0)
    due = [recs.get(i) for i in win.due_in_window]
    if due:
        ttft = [math.inf if r is None or r.error or r.first is None
                else r.first - r.due for r in due]
        out["ttft_p95_ms"] = _ms(st.percentile(ttft, 95))
        gaps = [g for r in due if r is not None for g in st.gaps(r.events)]
        gaps += [math.inf for r in due if r is None or r.error]
        out["itl_p95_ms"] = _ms(st.percentile(gaps, 95))
    return out


def _ms(x: float) -> float:
    """Seconds to ms; a missing tail reads as the longest wait allowed."""
    return (TAIL_CAP_S if math.isinf(x) else x) * 1e3


def attempted_failed(win: Window):
    """Requests due in the window where the schedule has one (an open
    loop), else every request sent; a failure is an error or a request that
    was neither answered nor cut off at the end."""
    ids = win.due_in_window or list(win.records)
    recs = [win.records.get(i) for i in ids]
    failed = sum(1 for r in recs if r is None or r.error is not None
                 or (not r.cancelled and not r.done))
    return len(ids), failed


def report_load(win: Window):
    seen = [b for b in st.bursts(_arrivals(win.records, win.w0), BURST_GAP_S,
                                 BURST_MAX_S) if b[0] < win.w1]
    log("bursts in the window (s after w0, tokens): " + " ".join(
        f"{b[0] - win.w0:.3f}:{b[2]}" for b in seen))
    burst = _closing_burst(win.records, win.w1)
    if burst:
        log(f"closing burst: {burst[2]} tokens over "
            f"{(burst[1] - burst[0]) * 1e3:.3f} ms, ending "
            f"{(burst[1] - win.w1) * 1e3:.3f} ms after w1")
    late = sorted(win.lateness)
    if late:
        log(f"generator lateness over {len(late)} sends: median "
            f"{statistics.median(late) * 1e3:.3f} ms, p99 "
            f"{st.percentile(late, 99) * 1e3:.3f} ms, max "
            f"{late[-1] * 1e3:.3f} ms")
    errs = [r.error for r in win.records.values() if r.error]
    done = sum(1 for r in win.records.values() if r.finish)
    log(f"requests sent {len(win.records)}, finished {done}, failed "
        f"{len(errs)}, due in the window {len(win.due_in_window)}; "
        f"counters {win.counters}")
    for e in errs[:3]:
        log(f"failure: {e}")


def whole_bursts(win: Window, h0: float, h1: float):
    """``(t_a, t_b)``: the ends of the first and last bursts of tokens that
    lie inside ``[h0, h1]``, so that ``(t_a, t_b]`` holds whole dispatch
    periods; None with fewer than two."""
    inside = [b for b in st.bursts(_arrivals(win.records, h0), BURST_GAP_S,
                                   BURST_MAX_S) if b[1] < h1]
    if len(inside) < 2:
        return None
    return inside[0][1], inside[-1][1]


def trace_flops(arch: dict, win: Window, t_a: float, t_b: float) -> float:
    """Model operations of every token that arrived in ``(t_a, t_b]``
    (prompt positions with a request's first token), from the client's
    record of when each token arrived."""
    by_index = {r.index: r for r in win.requests}
    total = 0.0
    for idx, rec in win.records.items():
        p = len(by_index[idx].prompt)
        i = 0
        for t, c in zip(rec.events, rec.counts):
            if t_a < t <= t_b:
                if i == 0:
                    total += fl.span_flops(arch, 0, p)
                    total += fl.span_flops(arch, p, p + c - 1)
                else:
                    total += fl.span_flops(arch, p + i - 1, p + i + c - 1)
            i += c
    return total


def context(conf: dict, peaks: dict, win: Window) -> Context:
    ctx = Context(conf["published"], conf["engine"], peaks,
                  counters=dict(win.counters), samples=dict(win.samples))
    if win.traced is not None:
        dev, window, h0, h1 = win.traced
        ctx.trace, ctx.trace_window = dev, window
        span = whole_bursts(win, h0, h1)
        if span is not None:
            ctx.counters["burst_seconds"] = span[1] - span[0]
            ctx.counters["burst_flops"] = trace_flops(conf["published"],
                                                      win, *span)
    return ctx


# -- correctness ---------------------------------------------------------------

def sample(win: Window, seed: int, n: int = SAMPLE_REQUESTS):
    """The requests to judge: the one with the most served tokens, and
    others drawn from the seed, among those that served any."""
    served = sorted((i for i, r in win.records.items()
                     if r.tokens and r.error is None),
                    key=lambda i: (-len(win.records[i].tokens), i))
    if not served:
        return []
    rng = np.random.default_rng(int(seed) % (1 << 64) ^ 0x5EED)
    rest = list(rng.permutation(served[1:]))[: n - 1]
    return [served[0]] + [int(i) for i in rest]


def judge(conf: dict, seed: int, win: Window, ref=None) -> Dict[str, dict]:
    """Each number compared, beside its limit."""
    from .reference import Reference, served_gaps
    limits = conf["limits"]
    by_index = {r.index: r for r in win.requests}
    short = sum(1 for i, r in win.records.items()
                if r.finish == "length"
                and len(r.tokens) != by_index[i].max_tokens)
    short += sum(1 for r in win.records.values()
                 if r.finish not in (None, "length"))
    picked = sample(win, seed)
    checks = {"short_answers": {"value": short, "limit": 0}}
    if not picked:
        checks["tokens_judged"] = {"value": 0, "limit": 1}
        return checks
    ref = ref or Reference(conf["published"])
    t = time.monotonic()
    gaps, n_tok = served_gaps(
        ref, seed, [by_index[i].prompt for i in picked],
        [win.records[i].tokens for i in picked], conf["engine"]["max_seq"])
    log(f"reference over {len(picked)} requests ({n_tok} served tokens) "
        f"in {time.monotonic() - t:.3f} s ({ref.seconds}); widest gap per "
        f"request {gaps}")
    checks["max_gap"] = {"value": max(gaps), "limit": limits["max_gap"]}
    checks["tokens_judged"] = {"value": n_tok, "limit": 1}
    return checks


def passed(checks: Dict[str, dict]) -> bool:
    ok = checks["short_answers"]["value"] <= checks["short_answers"]["limit"]
    ok &= checks["tokens_judged"]["value"] >= checks["tokens_judged"]["limit"]
    if "max_gap" in checks:
        ok &= checks["max_gap"]["value"] <= checks["max_gap"]["limit"]
    return bool(ok)
