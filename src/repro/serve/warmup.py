"""AOT warmup: compile every reachable dispatch shape at load time.

The engine's data plane is a finite set of jit traces, fully determined by
its config (DESIGN.md Sec. 16):

  * packed prefill — one trace per bucket in ``prefill_buckets`` (or the
    single legacy ``(1, prefill_chunk)`` chunk trace when packing is off)
  * decode — one trace per power-of-two batch bucket up to ``max_batch``,
    through the fused horizon scan when ``decode_horizon > 1`` and the
    plain step otherwise

``enumerate_traces`` lists that set; ``warm_engine`` executes one **all-pad
dummy dispatch** per entry through the engine's real jitted callables.
Dummy inputs carry ``q_pos = -1`` / ``seg_ids = -1`` / ``slots = -1`` /
``kv_lens = 0`` with the scratch block table, so every KV write lands on
the reserved scratch page (and hot row 0 on the quantized pools) and the
dispatch is semantically a no-op — but it populates the *call-site* jit
cache, which an offline ``lower().compile()`` would not, and it exercises
the exact aval set steady-state serving uses. Pools are reassigned from
the returned tree, since the dispatches donate them.

After ``warm_engine`` returns, a serving run that stays inside the
config's shape envelope performs zero new traces — the property the
trace-count probe (``continuous.jit_trace_count``) lets tests and the
``msb_traces_compiled_total`` metric assert.

The overload brownout ladder (``serve.overload``, DESIGN.md Sec. 17)
preserves this: its horizon cap is a *dynamic* clamp on the per-sequence
token budget (the static horizon-scan trace is untouched) and its wave
cap only shrinks the segment count, which selects a smaller —
already-warmed — covering bucket. Level changes therefore never add to
the reachable trace set enumerated here.
"""
from __future__ import annotations

import time
from typing import Dict, List, Tuple

import jax.numpy as jnp
import numpy as np


def enumerate_traces(engine) -> List[Tuple[str, int]]:
    """The reachable (kind, size) dispatch set for ``engine``'s config.

    Kinds: ``prefill_packed`` (size = bucket token length), ``prefill``
    (size = prefill_chunk; only reachable with packing off), ``decode`` /
    ``decode_horizon`` (size = batch bucket).
    """
    entries: List[Tuple[str, int]] = []
    if engine.prefill_buckets:
        entries += [("prefill_packed", b) for b in engine.prefill_buckets]
    else:
        entries.append(("prefill", engine.prefill_chunk))
    kind = "decode_horizon" if engine.decode_horizon > 1 else "decode"
    b = 1
    while True:
        entries.append((kind, b))
        if b >= engine.max_batch:
            break
        b *= 2
    return entries


def _warm_one(engine, kind: str, size: int):
    """One all-pad dummy dispatch of the given shape; blocks on the result
    so compile time is paid here, not on the first request."""
    cache = engine.cache
    if kind == "prefill_packed":
        s = engine.max_batch
        bt = cache.table_rows([-1] * s)
        out, cache.pools = engine._prefill_fn(
            cache.pools, engine.params,
            jnp.zeros((size,), jnp.int32), jnp.full((size,), -1, jnp.int32),
            jnp.full((size,), -1, jnp.int32), jnp.zeros((s,), jnp.int32),
            bt, jnp.full((s,), -1, jnp.int32), jnp.zeros((s,), jnp.int32),
            jnp.zeros((s,), jnp.int32))
    elif kind == "prefill":
        bt = cache.table_rows([-1])
        out, cache.pools = engine._step_fn(
            cache.pools, engine.params, jnp.zeros((1, size), jnp.int32),
            jnp.full((1, size), -1, jnp.int32), jnp.zeros((1,), jnp.int32),
            bt, jnp.full((1,), -1, jnp.int32))
    elif kind == "decode":
        bt = cache.table_rows([-1] * size)
        out, cache.pools = engine._step_fn(
            cache.pools, engine.params, jnp.zeros((size, 1), jnp.int32),
            jnp.full((size, 1), -1, jnp.int32), jnp.zeros((size,), jnp.int32),
            bt, jnp.full((size,), -1, jnp.int32))
    elif kind == "decode_horizon":
        bt = cache.table_rows([-1] * size)
        out, _valid, cache.pools = engine._horizon_fn(
            cache.pools, engine.params, jnp.zeros((size,), jnp.int32),
            jnp.full((size,), -1, jnp.int32), jnp.zeros((size,), jnp.int32),
            jnp.full((size,), -1, jnp.int32), bt,
            jnp.full((size,), -1, jnp.int32))
    else:
        raise ValueError(f"unknown warmup kind {kind!r}")
    np.asarray(out)                  # block until the dispatch retires


def warm_engine(engine) -> Dict[str, object]:
    """Warm every reachable trace of ``engine``; returns a report dict:
    ``seconds`` (wall time), ``entries`` (shapes warmed), ``traces`` (probe
    delta — 0 when a sibling engine already compiled the shared module-jit
    set), ``shapes`` (the enumerated list)."""
    from .continuous import jit_trace_count
    t0 = time.monotonic()
    n0 = jit_trace_count()
    entries = enumerate_traces(engine)
    for kind, size in entries:
        _warm_one(engine, kind, size)
    return {"seconds": time.monotonic() - t0, "entries": len(entries),
            "traces": jit_trace_count() - n0, "shapes": entries}
