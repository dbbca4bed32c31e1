"""The quantize cell: the paper's grouping solver on a released checkpoint.

Set-up makes every layer's projection matrices of the configuration in
bfloat16 on the device from the seed, in one jitted call, and quantizes the
first layer once so that every shape is compiled. The window then runs the
program's ``quantize_blockwise`` (the mix's bits, block and solver) on one
matrix at a time, layer after layer, each step ending in
``block_until_ready``; it closes with the first step that ends after
``seconds``. A sample of blocks drawn from the seed, from every step, is
judged after the window against the plain NumPy solver
(``solver_ref.py``).
"""
from __future__ import annotations

import dataclasses
import shutil
import tempfile
import time
from typing import Dict, List, Optional

import numpy as np

from . import solver_ref
from . import trace as tr
from .context import Context
from .device import log
from .serve_cell import TRACE_LEAD_S, TRACE_SECONDS


class Quantizer:
    """The bf16 inputs and the program's solver call, set up from a seed."""

    def __init__(self, conf: dict, mix: dict, seed: int):
        import jax
        import jax.numpy as jnp
        from . import weights as W
        self.mix = mix
        shapes = W.DenseShapes(conf["published"])
        self.names = list(shapes.layer_mats)
        self.n_layers = shapes.n_layers
        std = shapes.std

        def make(lo, hi):
            root = W.key_from_words(lo, hi)
            out = {}
            for name, shape in shapes.layer_mats.items():
                for layer in range(shapes.n_layers):
                    k = jax.random.fold_in(W.leaf_key(root, name), layer)
                    out[f"{name}/{layer}"] = (
                        std * jax.random.normal(k, shape)).astype(
                            jnp.bfloat16)
            return out

        t = time.monotonic()
        self.inputs = jax.jit(make)(*W.seed_words(seed))
        jax.block_until_ready(self.inputs)
        log(f"inputs: {len(self.inputs)} bf16 matrices from seed {seed} "
            f"in {time.monotonic() - t:.3f} s")
        t = time.monotonic()
        for i in range(len(self.names)):       # every shape once
            self.step(i)
        log(f"solver warmed on layer 0 in {time.monotonic() - t:.3f} s")

    def order(self, i: int):
        return self.names[i % len(self.names)], (i // len(self.names)) \
            % self.n_layers

    def step(self, i: int):
        import jax
        from repro.core.quantize import quantize_blockwise
        name, layer = self.order(i)
        w = self.inputs[f"{name}/{layer}"]
        q = quantize_blockwise(w, bits=self.mix["bits"],
                               block=self.mix["block"],
                               solver=self.mix["solver"])
        jax.block_until_ready(q)
        return q


@dataclasses.dataclass
class Window:
    steps: List[int]                 # step indices run in the window
    weights: int
    w0: float
    w1: float
    outputs: Dict[int, object]       # step -> QTensor
    blocks_in_trace: int = 0
    traced: Optional[tuple] = None   # (DeviceTrace, window on its clock)


def run_window(qz: Quantizer, seconds: float, trace: bool = False
               ) -> Window:
    import jax
    i = len(qz.names)                   # layer 0 warmed the shapes
    steps, outputs, weights = [], {}, 0
    tracing = None
    blocks_in_trace = 0
    traced = None
    w0 = time.monotonic()
    now = w0
    while now < w0 + seconds:
        if trace and tracing is None and traced is None \
                and now >= w0 + min(TRACE_LEAD_S, seconds / 4):
            tracing = tempfile.mkdtemp(prefix="bench-trace-")
            jax.profiler.start_trace(tracing)
            t_trace, wall0 = time.monotonic(), time.time_ns()
        q = qz.step(i)
        steps.append(i)
        outputs[i] = q
        weights += int(np.prod(q.codes.shape))
        if tracing is not None:
            blocks_in_trace += int(np.prod(q.codes.shape)) // qz.mix["block"]
        now = time.monotonic()
        if tracing is not None and (now >= t_trace + min(TRACE_SECONDS,
                                                         seconds / 2)
                                    or now >= w0 + seconds):
            traced = _stop_trace(tracing, wall0)
            tracing = None
        i += 1
    return Window(steps, weights, w0, now, outputs, blocks_in_trace, traced)


def _stop_trace(directory, wall0: int):
    """Stop the trace begun at ``wall0`` (``time.time_ns()``); the device's
    events and the host's traced steps on the trace's clock."""
    import jax
    wall1 = time.time_ns()
    try:
        jax.profiler.stop_trace()
        devs, span = tr.load(tr.find_xplane(directory))
    finally:
        shutil.rmtree(directory, ignore_errors=True)
    return devs[0], tr.host_window(span, wall0, wall1)


def end_to_end(win: Window) -> Dict[str, float]:
    return {"quantize_weights_per_s": win.weights / (win.w1 - win.w0)}


def context(conf: dict, peaks: dict, win: Window) -> Context:
    ctx = Context(conf["published"], {}, peaks,
                  counters={"blocks_in_trace": win.blocks_in_trace})
    if win.traced is not None:
        ctx.trace, ctx.trace_window = win.traced
    return ctx


def sampled_blocks(qz: Quantizer, win: Window, seed: int, per_step: int):
    """(input blocks, program codes, program codebooks) of ``per_step``
    blocks drawn from the seed in every step of the window."""
    rng = np.random.default_rng(int(seed) % (1 << 64) ^ 0xB10C)
    block = qz.mix["block"]
    ws, cs, ss = [], [], []
    for i in win.steps:
        name, layer = qz.order(i)
        w = np.asarray(qz.inputs[f"{name}/{layer}"]).astype(np.float32)
        q = win.outputs[i]
        wb = w.reshape(-1, block)
        pick = rng.choice(len(wb), size=min(per_step, len(wb)),
                          replace=False)
        ws.append(wb[pick])
        cs.append(np.asarray(q.codes).reshape(-1, block)[pick])
        ss.append(np.asarray(q.scales).reshape(len(wb), -1)[pick])
    return np.concatenate(ws), np.concatenate(cs), np.concatenate(ss)


def judge(conf: dict, qz: Quantizer, win: Window, seed: int
          ) -> Dict[str, dict]:
    t = time.monotonic()
    w, codes, scales = sampled_blocks(qz, win, seed,
                                      qz.mix["sample_blocks_per_step"])
    excess = solver_ref.excess(w, codes, scales)
    log(f"reference solver over {len(w)} blocks from {len(win.steps)} "
        f"steps in {time.monotonic() - t:.3f} s; median excess "
        f"{float(np.median(excess)):.3e}")
    return {"max_excess": {"value": float(np.max(excess)),
                           "limit": conf["limits"]["max_excess"]},
            "blocks_judged": {"value": int(len(w)), "limit": 1}}


def passed(checks: Dict[str, dict]) -> bool:
    return bool(checks["max_excess"]["value"]
                <= checks["max_excess"]["limit"]
                and checks["blocks_judged"]["value"]
                >= checks["blocks_judged"]["limit"])
