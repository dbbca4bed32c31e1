#!/usr/bin/env python3
"""Chip smoke: internlm2-1.8b at its published widths, served through the
packed MSB path on one TPU.

    python3 chip_smoke.py [--seed 0]      # one chip: the serving path
    python3 chip_smoke.py --tp 4          # four chips: tensor parallel only

One process, no children. The one-chip run builds the model at full width
from random weights (``--seed``), quantizes it with the paper's DP solver
(4-bit, 64-element blocks), packs it into ``ContinuousEngine(execution=
"packed")`` with a ~32k-token paged KV cache, starts ``APIServer`` with AOT
warmup and sends a few requests over HTTP. It fails, and prints no ``ok``
line, unless

  * every request is answered with its full token count;
  * the HTTP tokens equal a direct ``ContinuousEngine`` run of the same
    prompts on the same device and program;
  * the packed kernel path's last-position logits for one prompt agree with
    the simulated path (dequantize + einsum) within ``LOGIT_RTOL``, with the
    same argmax;
  * no dispatch is traced after warmup.

``--tp 4`` runs only the tensor-parallel phase: the same prompts through
``ContinuousEngine(mesh=make_tp_mesh(4))`` against a tp=1 engine on device
0, prefill logits within ``LOGIT_RTOL``, greedy-token agreement printed.

Every number printed is a smoke figure, not a benchmark. Without a TPU the
script exits non-zero.
"""
import argparse
import http.client
import json
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from importlib.metadata import PackageNotFoundError, version

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "internlm2-1.8b"

# Two runs of the same weights on two code paths differ only in where
# bf16 rounds: bf16 keeps 8 significant bits (unit roundoff 2^-8 = 0.0039),
# and each of the 24 layers rounds the outputs of its 7 projections. If
# those roundings differ independently, the logits drift by about
# sqrt(24 * 7) * 2^-8 = 0.05 of their norm. A wrong layout, scale or shard
# gives a distance near 1.
LOGIT_RTOL = 0.05

# One-chip serving config. 2049 pages of 16 tokens: page 0 is the scratch
# page, the other 2048 hold 32768 tokens, 3 GiB of bf16 K/V at 96 KiB per
# token (24 layers x K,V x 8 heads x 128 x 2 bytes).
ENGINE = dict(max_batch=8, page_size=16, num_pages=2049, max_seq=1024,
              prefill_chunk=128, decode_horizon=8)

# (prompt length, new tokens, streamed). Requests 0 and 1 share a 256-token
# prefix; request 0 runs alone first, so request 1 can hit its pages.
SHARED_PREFIX = 256
REQUESTS = [(296, 64, True), (328, 48, False), (384, 32, True),
            (200, 40, False), (448, 56, True), (300, 64, False)]


def log(msg):
    print(f"[smoke] {msg}", flush=True)


def tpu_devices(n):
    """The first ``n`` TPU devices, or exit non-zero."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX platform "
                 f"{devs[0].platform!r}); nothing to run")
    if len(devs) < n:
        sys.exit(f"chip_smoke: needs {n} TPU chips, found {len(devs)}")
    return devs


def make_prompts(seed, vocab):
    rng = np.random.default_rng(seed)
    shared = rng.integers(0, vocab, (SHARED_PREFIX,))
    prompts = []
    for i, (n, _, _) in enumerate(REQUESTS):
        if i < 2:
            p = np.concatenate([shared,
                                rng.integers(0, vocab, (n - SHARED_PREFIX,))])
        else:
            p = rng.integers(0, vocab, (n,))
        prompts.append(p.astype(np.int32))
    return prompts


def build(seed):
    """Full-width model, bf16 weights from ``seed``, quantized. Returns
    (model, qparams, seconds by phase)."""
    import jax
    from repro.configs import get_config
    from repro.core import QuantPolicy, quantize_params
    from repro.models import Model

    cfg = get_config(ARCH)
    log(f"model {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"heads {cfg.n_heads}/{cfg.n_kv_heads} of dim {cfg.head_dim_}, "
        f"d_ff {cfg.d_ff}, vocab {cfg.vocab_size} (padded "
        f"{cfg.vocab_padded}), {cfg.dtype.__name__} weights from seed {seed}")
    model = Model(cfg)
    t0 = time.monotonic()
    params = jax.jit(lambda key: jax.tree_util.tree_map(
        lambda p: p.astype(cfg.dtype), model.init(key)))(
            jax.random.PRNGKey(seed))
    jax.block_until_ready(params)
    t1 = time.monotonic()
    qparams, report = quantize_params(
        params, QuantPolicy(bits=4, block=64, solver="dp"))
    jax.block_until_ready(qparams)
    t2 = time.monotonic()
    n_weights = sum(int(np.prod(shape)) for shape, _ in report.values())
    log(f"quantized {len(report)} tensors, {n_weights} weights "
        "(4-bit MSB, 64-element blocks, dp solver)")
    return model, qparams, {"init": t1 - t0, "quantize": t2 - t1}


def peak_bytes(dev):
    return (dev.memory_stats() or {}).get("peak_bytes_in_use",
                                          "not reported")


def calls_kernel(lowered):
    """Whether a lowered program calls a Pallas TPU kernel."""
    return "tpu_custom_call" in lowered.as_text()


def rel_distance(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def check_logits(name, got, ref, vocab, same_argmax):
    """Fail unless the first ``vocab`` entries of ``got`` (the real tokens;
    padded columns are -inf, and a tp shard pads the vocab further) are
    finite and within LOGIT_RTOL of ``ref``, and, if ``same_argmax``, have
    its argmax; log the distance and the reference's top-2 margin."""
    got = np.asarray(got, np.float32)[:vocab]
    ref = np.asarray(ref, np.float32)[:vocab]
    if not (np.isfinite(got).all() and np.isfinite(ref).all()):
        raise AssertionError(f"{name}: non-finite logits")
    d = rel_distance(got, ref)
    top2 = np.sort(ref)[-2:]
    log(f"{name}: relative L2 distance {d:.6f} (limit {LOGIT_RTOL}), "
        f"argmax {int(got.argmax())} vs {int(ref.argmax())}, reference "
        f"top-2 margin {float(top2[1] - top2[0]):.6f}")
    if not d <= LOGIT_RTOL:
        raise AssertionError(f"{name}: logits differ by {d:.6f}")
    if same_argmax and got.argmax() != ref.argmax():
        raise AssertionError(f"{name}: argmax differs")


# -- HTTP client ---------------------------------------------------------------

def complete(host, port, prompt, max_tokens, stream):
    """One /v1/completions request. Returns (token ids, finish reason)."""
    body = json.dumps({"prompt": prompt.tolist(), "max_tokens": max_tokens,
                       "stream": stream}).encode()
    conn = http.client.HTTPConnection(host, port, timeout=600)
    try:
        conn.request("POST", "/v1/completions", body,
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        raw = resp.read().decode()
    finally:
        conn.close()
    if resp.status != 200:
        raise AssertionError(f"HTTP {resp.status}: {raw[:500]}")
    if not stream:
        choice = json.loads(raw)["choices"][0]
        return choice["token_ids"], choice["finish_reason"]
    frames = [f for f in raw.split("\n\n") if f]
    if not frames or frames[-1] != "data: [DONE]":
        raise AssertionError("stream did not end with data: [DONE]")
    toks, reason = [], None
    for f in frames[:-1]:
        if not f.startswith("data: "):
            raise AssertionError(f"bad SSE frame {f[:80]!r}")
        choice = json.loads(f[6:])["choices"][0]
        toks += choice["token_ids"]
        reason = choice["finish_reason"] or reason
    return toks, reason


def await_warm(srv, timeout_s=900):
    loop = srv.engine_loop
    deadline = time.monotonic() + timeout_s
    while loop.warming or loop.engine.stats()["warmup_traces"] == 0:
        if loop.died is not None or not loop.alive:
            raise RuntimeError(f"engine loop died in warmup: {loop.died!r}")
        if time.monotonic() > deadline:
            raise TimeoutError("warmup did not finish")
        time.sleep(0.1)


def run_direct(model, params, prompts):
    """The same requests through a direct engine of the same config, in the
    same order: request 0 alone, then the rest together."""
    from repro.serve import ContinuousEngine
    eng = ContinuousEngine(model, params, execution="packed", **ENGINE)
    rid0 = eng.submit(prompts[0], REQUESTS[0][1])
    out = eng.run()
    rids = [rid0] + [eng.submit(p, r[1])
                     for p, r in zip(prompts[1:], REQUESTS[1:])]
    out.update(eng.run())
    eng.close()
    return [out[r].tolist() for r in rids]


def retraced_since(before, after):
    """Traces per dispatch function between two ``jit_trace_counts()``
    readings, the functions that traced none left out."""
    return {k: n - before.get(k, 0) for k, n in after.items()
            if n > before.get(k, 0)}


# -- phases --------------------------------------------------------------------

def one_chip(seed):
    import jax
    from repro.serve import APIServer, ContinuousEngine, jit_trace_counts

    dev = tpu_devices(1)[0]
    model, qparams, secs = build(seed)
    prompts = make_prompts(seed, model.cfg.vocab_size)
    peaks = {"quantize": peak_bytes(dev)}

    # the simulated path's logits first, while no KV pool holds memory
    prefill = jax.jit(lambda p, t: model.prefill(p, {"tokens": t})[0][0])
    toks = jax.numpy.asarray(prompts[0][None])
    sim_logits = np.asarray(prefill(qparams, toks))
    peaks["simulated prefill"] = peak_bytes(dev)

    t0 = time.monotonic()
    eng = ContinuousEngine(model, qparams, execution="packed", **ENGINE)
    jax.block_until_ready(eng.params)
    secs["pack"] = time.monotonic() - t0
    if eng.execution != "packed":
        raise AssertionError(f"engine runs {eng.execution!r}")
    kv_gib = sum(x.nbytes for x in jax.tree_util.tree_leaves(
        eng.cache.pools)) / 2**30
    w_gib = sum(x.nbytes for x in jax.tree_util.tree_leaves(
        eng.params)) / 2**30
    log(f"engine: packed weights {w_gib:.3f} GiB, KV pool {kv_gib:.3f} GiB "
        f"({ENGINE['num_pages'] - 1} pages x {ENGINE['page_size']} tokens)")

    # packed kernel path against the simulated path, one prompt, same chip
    lowered = prefill.lower(eng.params, toks)
    if not calls_kernel(lowered):
        raise AssertionError("packed prefill does not call the MSB kernel")
    packed_logits = np.asarray(lowered.compile()(eng.params, toks))
    check_logits("packed kernel vs simulated, last-position logits",
                 packed_logits, sim_logits, model.cfg.vocab_size,
                 same_argmax=True)
    del qparams, sim_logits

    srv = APIServer(eng, warmup=True)
    host, port = srv.serve_background()
    try:
        await_warm(srv)
        secs["warmup"] = eng.warmup_seconds
        peaks["warmup"] = peak_bytes(dev)
        log(f"warmup: {eng.warmup_entries} dispatch shapes compiled")
        traces0 = jit_trace_counts()
        got = [None] * len(REQUESTS)

        def send(i):
            n_new, stream = REQUESTS[i][1], REQUESTS[i][2]
            got[i] = complete(host, port, prompts[i], n_new, stream)

        send(0)
        with ThreadPoolExecutor(len(REQUESTS) - 1) as pool:
            list(pool.map(send, range(1, len(REQUESTS))))
        traced = retraced_since(traces0, jit_trace_counts())
    finally:
        srv.close()
    if srv.engine_loop.died is not None:
        raise RuntimeError(f"engine loop died: {srv.engine_loop.died!r}")
    for i, ((toks_i, reason), (_, n_new, stream)) in enumerate(
            zip(got, REQUESTS)):
        if len(toks_i) != n_new or reason != "length":
            raise AssertionError(f"request {i}: {len(toks_i)}/{n_new} "
                                 f"tokens, finish {reason!r}")
    st = eng.stats()
    eng.close()                         # page audit: zero leaked pages
    n_served = sum(len(t) for t, _ in got)
    log(f"served {len(got)} requests over HTTP "
        f"({sum(r[2] for r in REQUESTS)} streamed), {n_served} tokens, "
        f"all full length; prefix hits {st['prefix_hits']}, "
        f"{st['prefix_positions_saved']} positions reused")
    if traced:
        raise AssertionError(f"dispatches traced after warmup: {traced}")

    params = eng.params
    eng.cache.pools = None              # free the server's KV pool first
    direct = run_direct(model, params, prompts)
    for i, (toks_i, _) in enumerate(got):
        if toks_i != direct[i]:
            raise AssertionError(f"request {i}: HTTP tokens differ from "
                                 "the direct engine")
    traced = retraced_since(traces0, jit_trace_counts())
    if traced:
        raise AssertionError(f"direct run traced dispatches: {traced}")
    log(f"HTTP tokens identical to a direct ContinuousEngine run "
        f"({len(direct)} requests); traces after warmup, through both: 0")

    for name in ("init", "quantize", "pack", "warmup"):
        log(f"set-up {name}: {secs[name]:.3f} s (smoke figure, "
            "not a benchmark)")
    peaks["end"] = peak_bytes(dev)
    log(f"peak_bytes_in_use after each phase: {peaks} (smoke figures, "
        "not a benchmark)")
    log(f"tokens served: {n_served} (smoke figure, not a benchmark)")


def tensor_parallel(seed, tp):
    import jax
    from repro.launch.mesh import make_tp_mesh
    from repro.serve import ContinuousEngine

    tpu_devices(tp)
    model, qparams, secs = build(seed)
    prompts = make_prompts(seed, model.cfg.vocab_size)
    # decode_horizon=1 samples on host, so the prefill logits are visible;
    # a smaller pool: both engines share device 0
    kw = dict(ENGINE, decode_horizon=1, num_pages=513)

    def serve(mesh):
        eng = ContinuousEngine(model, qparams, execution="packed", mesh=mesh,
                               **kw)
        first = {}
        sample = eng._sample_and_advance

        def record(seq, logits):
            first.setdefault(seq.req.req_id, np.asarray(logits))
            return sample(seq, logits)

        eng._sample_and_advance = record
        rids = [eng.submit(p, r[1]) for p, r in zip(prompts, REQUESTS)]
        t0 = time.monotonic()
        out = eng.run()
        seconds = time.monotonic() - t0
        eng.close()
        return ([out[r].tolist() for r in rids], [first[r] for r in rids],
                eng.tp_report if mesh is not None else {}, seconds)

    toks1, logits1, _, s1 = serve(None)
    toks4, logits4, report, s4 = serve(make_tp_mesh(tp))
    del qparams
    log(f"tp={tp} plan: {len(report)} groups sharded, "
        f"{sorted(set(report.values()))}")
    for i, (a, b) in enumerate(zip(logits4, logits1)):
        check_logits(f"request {i} prefill logits, tp={tp} vs tp=1", a, b,
                     model.cfg.vocab_size, same_argmax=False)
    same = sum(a == b for a, b in zip(toks4, toks1))
    agree = [next((j for j, (x, y) in enumerate(zip(a, b)) if x != y),
                  len(a)) for a, b in zip(toks4, toks1)]
    log(f"greedy tokens: {same}/{len(toks1)} requests identical at tp={tp}; "
        f"tokens before the first difference: {agree} of "
        f"{[r[1] for r in REQUESTS]}")
    log(f"set-up init {secs['init']:.3f} s, quantize {secs['quantize']:.3f} "
        f"s; run incl. compile tp=1 {s1:.3f} s, tp={tp} {s4:.3f} s (smoke "
        "figures, not a benchmark)")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tp", type=int, default=1,
                    help="run only the tensor-parallel phase on this many "
                         "chips")
    args = ap.parse_args()

    from repro.launch.compile_cache import use_compile_cache
    cache_dir = use_compile_cache()
    import jax
    devs = tpu_devices(args.tp)
    try:
        libtpu = version("libtpu")
    except PackageNotFoundError:
        libtpu = "not installed"
    log(f"device: platform {devs[0].platform}, kind {devs[0].device_kind}, "
        f"count {len(devs)}; jax {jax.__version__}, libtpu {libtpu}; "
        f"compile cache {cache_dir}")
    if args.tp > 1:
        tensor_parallel(args.seed, args.tp)
    else:
        one_chip(args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)


if __name__ == "__main__":
    main()
