"""Continuous-batching serving engine over a paged KV cache.

Step-driven API: ``submit()`` enqueues requests, each ``step()`` runs one
scheduler-chosen unit of work (a prefill chunk or a packed decode batch),
``collect()`` drains finished outputs. The data plane is a handful of jit
traces of one function (``Model.paged_step``):

  * prefill trace:  tokens (1, prefill_chunk) — one sequence, chunked
  * decode  traces: tokens (2^k, 1), 2^k <= max_batch — the decoding set
    padded to the next power of two (bucketed shapes bound retraces at
    log2(max_batch)+1 while keeping padding waste under 2x at low
    concurrency)

All shapes are static; inactive rows / chunk tails carry q_pos == -1 and
scatter into the reserved scratch page, so no retracing ever happens once
the buckets are warm. With the default ``decode_horizon=1`` greedy sampling
happens on host from the returned last-token logits, which is what makes
output token-identical to the static ``ServeEngine`` (same model math, same
argmax).

``decode_horizon=H`` (DESIGN.md Sec. 12) inverts that host/device contract
on the decode hot path: one jitted dispatch runs H decode iterations as a
``lax.scan`` with greedy sampling *on device*, each iteration feeding its
argmax back through the carry and writing K/V through the paged path.
Per-row stop masks retire rows that hit eos or exhaust their budget
mid-horizon (their remaining iterations are exact no-ops via the scratch-
page convention), the scheduler reserves the whole horizon lease up front
so page boundaries are crossed without host help, and only (B, H) sampled
tokens + done masks cross back — never (B, vocab) logits. Greedy outputs
are token-identical to ``decode_horizon=1`` for every execution mode and
mesh size (tested); preemption, prefix registration and ``fork_request``
semantics are unchanged.

Automatic prefix caching (on by default; DESIGN.md Sec. 11): committed
full KV pages register under a rolling content hash of their token chain,
admission longest-prefix-matches new requests against the registry, and
``fork_request()`` shares a live request's pages n ways by refcount. A
thousand requests behind one system prompt prefill it once; the metrics
``n_prefix_hits`` / ``n_prefix_positions_saved`` account for the reuse.
Greedy outputs are token-identical with the cache on or off — matched
pages hold exactly the K/V the skipped prefill would have written.

``kv_bits=8|4`` (DESIGN.md Sec. 15) stores committed KV pages MSB-quantized
(per-head block-wise group scales, the paper's codec applied to the cache):
pages are quantized on device the moment a dispatch completes them, the one
partial page per sequence stays full-precision in a per-slot hot row, and
attention dequantizes fused into the page gather. A 4-bit page pool holds
~4-6x the sequences of a bf16 pool before preemption; 8-bit is greedy
token-identical on the smoke models, 4-bit bounded-drift. Scheduling,
prefix caching (token-hash keyed, so matching is representation-agnostic),
forks, preemption and supervision are unchanged.

``mesh=`` runs the whole data plane tensor-parallel (DESIGN.md Sec. 10):
params partition along N/K/experts/vocab, the page pools by KV head, and
every step is one ``shard_map`` dispatch with manual psum/all_gather
collectives. The scheduler, allocator and sampling stay on host and
unsharded — greedy output is token-identical across TP sizes.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .paged_cache import PagedKVCache
from .scheduler import DECODE, FINISHED, Request, Scheduler, Sequence
from .spans import PhaseClock

# Trace-count probe (DESIGN.md Sec. 16): a Python-side counter bumped at
# the top of every traced dispatch body, by the dispatch function's name.
# jit runs the Python body once per trace, never per call, so after AOT
# warmup a steady-state serving run must leave these numbers unchanged —
# the property the warmup tests and the ``msb_traces_compiled_total``
# metric assert. Process-global on purpose: module-level jits share their
# compile cache across engines.
_N_TRACES: Dict[str, int] = {}


def _note_trace(name: str):
    _N_TRACES[name] = _N_TRACES.get(name, 0) + 1


def jit_trace_count() -> int:
    """Traced-dispatch events so far in this process (monotonic)."""
    return sum(_N_TRACES.values())


def jit_trace_counts() -> Dict[str, int]:
    """``jit_trace_count()`` by dispatch function name, so a retrace
    names the program that retraced."""
    return dict(_N_TRACES)


# Module-level jits, model static (frozen dataclass, hashable): every engine
# for the same model shares one compile cache, and the pools are donated so
# the per-step cache update is in place (every backend, CPU included,
# aliases donated buffers). kv_bits is static (16 native pools, 8|4 the dual
# quantized pools of DESIGN.md Sec. 15); slots maps batch rows to engine
# slots so quantized writes hit the right hot row (-1 pads land on the
# scratch row).
@functools.partial(jax.jit, static_argnums=(0, 1), donate_argnums=(2,))
def _paged_step(model, kv_bits, pools, params, tokens, q_pos, kv_lens,
                block_tables, slots):
    _note_trace("_paged_step")
    return model.paged_step(params, pools, tokens, q_pos, kv_lens,
                            block_tables, kv_bits=kv_bits, slots=slots)


@functools.partial(jax.jit, static_argnums=(0, 1), donate_argnums=(2,))
def _paged_prefill_packed(model, kv_bits, pools, params, tokens, seg_ids,
                          q_pos, kv_lens, block_tables, slots, last_idx,
                          seg_off):
    _note_trace("_paged_prefill_packed")
    return model.paged_prefill_packed(params, pools, tokens, seg_ids, q_pos,
                                      kv_lens, block_tables, slots, last_idx,
                                      seg_off, kv_bits=kv_bits)


# decode-horizon dispatch: pools is positional arg 3 here (model, the
# static horizon and kv_bits precede it), hence the shifted donation index
@functools.partial(jax.jit, static_argnums=(0, 1, 2), donate_argnums=(3,))
def _paged_horizon_step(model, horizon, kv_bits, pools, params, tokens,
                        start_pos, n_left, eos_ids, block_tables, slots):
    _note_trace("_paged_horizon_step")
    return model.paged_decode_horizon(params, pools, tokens, start_pos,
                                      block_tables, n_left, eos_ids, horizon,
                                      kv_bits=kv_bits, slots=slots)


@dataclasses.dataclass
class ContinuousEngine:
    model: object
    params: object
    max_batch: int = 8
    page_size: int = 16
    num_pages: int = 128
    max_seq: Optional[int] = None          # bounds block-table width
    max_pages_per_seq: Optional[int] = None
    prefill_chunk: int = 32
    parallel: object = None
    execution: Optional[str] = None   # "packed" | "simulated" | None=auto
    mesh: object = None               # tensor-parallel device mesh
    prefix_cache: bool = True         # automatic cross-request prefix reuse
    decode_horizon: int = 1           # fused decode steps per dispatch
    kv_bits: int = 16                 # committed-page precision: 16 | 8 | 4
    max_waiting: Optional[int] = None  # backpressure: bound on waiting queue
    faults: object = None             # FaultPlan (testing); None = NO_FAULTS
    prefill_packing: bool = True      # pack prompts into ragged dispatches
    prefill_buckets: object = None    # packed lengths; None = derived ladder

    def __post_init__(self):
        from .engine import resolve_execution
        if self.mesh is not None and self.parallel is not None:
            raise ValueError("pass either mesh= (manual TP) or parallel= "
                             "(GSPMD), not both")
        # reject unsupported models before the O(params) pack pass
        if not self.model.supports_paged():
            raise ValueError(
                f"{self.model.cfg.name}: paged serving needs a decoder-only "
                "attention stack (ssm/xlstm/enc-dec caches are not paged)")
        self.execution, self.params = resolve_execution(self.execution,
                                                        self.params)
        self.decode_horizon = int(self.decode_horizon)
        if self.decode_horizon < 1:
            raise ValueError(f"decode_horizon must be >= 1, "
                             f"got {self.decode_horizon}")
        self.kv_bits = int(self.kv_bits)
        if self.kv_bits not in (16, 8, 4):
            raise ValueError(f"kv_bits must be 16, 8 or 4, "
                             f"got {self.kv_bits}")
        if self.faults is None:
            from .faults import NO_FAULTS
            self.faults = NO_FAULTS
        mpps = self.max_pages_per_seq
        if mpps is None and self.max_seq is not None:
            mpps = -(-self.max_seq // self.page_size)
        self.cache = PagedKVCache(
            self.model, num_pages=self.num_pages, page_size=self.page_size,
            max_seqs=self.max_batch, max_pages_per_seq=mpps,
            prefix_cache=self.prefix_cache, faults=self.faults,
            kv_bits=self.kv_bits)
        # packed ragged prefill (DESIGN.md Sec. 16): the bucket ladder is
        # the set of packed token lengths — each is one jit trace, so the
        # default caps it at three powers-of-two steps from prefill_chunk;
        # pass prefill_buckets= to widen/narrow the set explicitly
        if self.prefill_packing:
            if self.prefill_buckets is None:
                c = self.prefill_chunk
                self.prefill_buckets = (c, 2 * c, 4 * c)
            self.prefill_buckets = tuple(
                sorted(int(b) for b in self.prefill_buckets))
            if self.prefill_buckets[0] < 1:
                raise ValueError("prefill_buckets must be positive")
        else:
            self.prefill_buckets = None
        self.scheduler = Scheduler(self.cache, self.max_batch,
                                   self.prefill_chunk,
                                   decode_horizon=self.decode_horizon,
                                   max_waiting=self.max_waiting,
                                   prefill_buckets=self.prefill_buckets)
        if self.mesh is not None:
            self._init_tensor_parallel()
        elif self.parallel is None:
            self._step_fn = functools.partial(_paged_step, self.model,
                                              self.kv_bits)
            self._horizon_fn = functools.partial(
                _paged_horizon_step, self.model, self.decode_horizon,
                self.kv_bits)
            self._prefill_fn = functools.partial(
                _paged_prefill_packed, self.model, self.kv_bits)
        else:                              # parallel objects aren't hashable
            def _gspmd_step(pools, p, toks, qpos, kvl, bt, sl):
                _note_trace("_gspmd_step")
                return self.model.paged_step(
                    p, pools, toks, qpos, kvl, bt, self.parallel,
                    kv_bits=self.kv_bits, slots=sl)

            def _gspmd_horizon(pools, p, toks, sp, nl, eos, bt, sl):
                _note_trace("_gspmd_horizon")
                return self.model.paged_decode_horizon(
                    p, pools, toks, sp, bt, nl, eos, self.decode_horizon,
                    self.parallel, kv_bits=self.kv_bits, slots=sl)

            def _gspmd_prefill(pools, p, toks, segs, qpos, kvl, bt, sl, li,
                               so):
                _note_trace("_gspmd_prefill")
                return self.model.paged_prefill_packed(
                    p, pools, toks, segs, qpos, kvl, bt, sl, li, so,
                    self.parallel, kv_bits=self.kv_bits)

            self._step_fn = jax.jit(_gspmd_step)
            self._horizon_fn = jax.jit(_gspmd_horizon)
            self._prefill_fn = jax.jit(_gspmd_prefill)
        self._next_id = 0
        self._seqs: Dict[int, Sequence] = {}
        self._finished: Dict[int, np.ndarray] = {}
        self._stream_off: Dict[int, int] = {}   # tokens already streamed
        self.n_aborts = 0             # abort_request cancellations
        self.n_steps = 0
        self.n_decode_steps = 0       # decode dispatches (any horizon)
        self.n_host_syncs = 0         # blocking device->host transfers
        self.n_tokens_out = 0
        self.n_work_positions = 0     # device token-positions incl. padding
        self.n_forks = 0              # fork_request children that shared pages
        self.n_prefill_dispatches = 0  # prefill device dispatches (any kind)
        self.n_prefill_segments = 0    # sequences served across them
        # segments-per-packed-dispatch samples, drained by the metrics sync
        # into the msb_prefill_packed_segments histogram (bounded if no one
        # drains, e.g. a bench driving the engine directly)
        self.packed_segment_obs: List[int] = []
        self.warmup_seconds = 0.0     # wall seconds spent in warmup()
        self.warmup_entries = 0       # dispatch shapes warmed
        # crash blame: request ids in the work unit the current (or most
        # recently crashed) step dispatched — a prefill names one sequence,
        # a decode names the batch; () before any work is scheduled. The
        # supervisor reads this to attribute a crash (DESIGN.md Sec. 14).
        self.last_step_rids: Tuple[int, ...] = ()
        self.last_step_kind: str = ""
        # host time per phase of step() (serve/spans.py); stats() reports
        # it as host_phase_seconds
        self.phases = PhaseClock()

    def _init_tensor_parallel(self):
        """Shard params + page pools over ``mesh`` and build the shard_map
        step (DESIGN.md Sec. 10).

        Packed codes/codebooks partition along N (column-parallel QKV/up/
        gate, vocab) and K (row-parallel o/down, psum inside the step); the
        K/V page pools partition along the KV-head dim whenever the head
        counts divide the mesh's model axis; the block tables, token batch
        and logits stay replicated, so the scheduler/allocator control
        plane is untouched.
        """
        from jax.sharding import NamedSharding, PartitionSpec as P
        from ..core.policy import tp_localize, tp_partition_params
        from ..parallel.sharding import TPShard, from_mesh
        ctx = from_mesh(self.mesh)
        tp = TPShard(axis=ctx.tp_axis, size=ctx.tp_size)
        self.tp = tp
        cfg = self.model.cfg
        self.params, pspecs, self.tp_report = tp_partition_params(
            self.params, tp.size, cfg=cfg, axis=tp.axis)
        self.params = jax.device_put(
            self.params,
            jax.tree_util.tree_map(lambda s: NamedSharding(self.mesh, s),
                                   pspecs))
        heads_ok = (tp.size > 1 and cfg.n_heads % tp.size == 0
                    and cfg.n_kv_heads % tp.size == 0)

        # per-leaf pool specs: groups never cross heads in the KV codec, so
        # every pool representation shards cleanly along its KV-head dim —
        # native k/v and quantized codes/hot rows carry it at axis 3
        # (n_periods, pages|rows, page_size, KV, hd), the per-page scale
        # codebooks at axis 2 (n_periods, pages, KV, n_blocks, G)
        def leaf_spec(path, leaf):
            if not heads_ok:
                return P()
            name = str(getattr(path[-1], "key", ""))
            if name.endswith("_scales"):
                return P(None, None, tp.axis, None, None)
            return P(None, None, None, tp.axis, None)

        pool_spec = jax.tree_util.tree_map_with_path(leaf_spec,
                                                     self.cache.pools)
        self.cache.pools = jax.tree_util.tree_map(
            lambda leaf, s: jax.device_put(leaf, NamedSharding(self.mesh, s)),
            self.cache.pools, pool_spec)
        model, rep, kv_bits = self.model, P(), self.kv_bits

        def local_step(pools, params, tokens, q_pos, kv_lens, bt, slots):
            _note_trace("local_step")
            return model.paged_step(tp_localize(params), pools, tokens,
                                    q_pos, kv_lens, bt, parallel=tp,
                                    kv_bits=kv_bits, slots=slots)

        # outputs are replicated by construction (psum / all_gather), which
        # the vma checker cannot prove through axis_index head slicing
        fn = jax.shard_map(
            local_step, mesh=self.mesh, check_vma=False,
            in_specs=(pool_spec, pspecs, rep, rep, rep, rep, rep),
            out_specs=(rep, pool_spec))
        self._step_fn = jax.jit(fn, donate_argnums=(0,))

        # packed ragged prefill under the same mesh: control arrays (seg
        # ids, positions, tables, offsets) are replicated; only the pools
        # and params are sharded, so the packed gather runs per rank on its
        # KV-head slice exactly like the unpacked step
        def local_prefill(pools, params, tokens, seg_ids, q_pos, kv_lens,
                          bt, slots, last_idx, seg_off):
            _note_trace("local_prefill")
            return model.paged_prefill_packed(
                tp_localize(params), pools, tokens, seg_ids, q_pos, kv_lens,
                bt, slots, last_idx, seg_off, parallel=tp, kv_bits=kv_bits)

        pfn = jax.shard_map(
            local_prefill, mesh=self.mesh, check_vma=False,
            in_specs=(pool_spec, pspecs, rep, rep, rep, rep, rep, rep, rep,
                      rep),
            out_specs=(rep, pool_spec))
        self._prefill_fn = jax.jit(pfn, donate_argnums=(0,))

        # the decode-horizon scan lives *inside* the shard_map body, so H
        # fused iterations (collectives included) are still one dispatch
        horizon = self.decode_horizon

        def local_horizon(pools, params, tokens, start_pos, n_left, eos, bt,
                          slots):
            _note_trace("local_horizon")
            return model.paged_decode_horizon(
                tp_localize(params), pools, tokens, start_pos, bt, n_left,
                eos, horizon, parallel=tp, kv_bits=kv_bits, slots=slots)

        hfn = jax.shard_map(
            local_horizon, mesh=self.mesh, check_vma=False,
            in_specs=(pool_spec, pspecs, rep, rep, rep, rep, rep, rep),
            out_specs=(rep, rep, pool_spec))
        self._horizon_fn = jax.jit(hfn, donate_argnums=(0,))

    # -- API ----------------------------------------------------------------
    def submit(self, prompt, max_new_tokens, eos_id=None,
               priority="standard", deadline_ms=None,
               deadline_at=None) -> int:
        """Enqueue one request; returns its id (the ``collect()`` key).

        Non-blocking and device-free: nothing is scheduled or transferred
        until a ``step()``. Raises ``ValueError`` if ``prompt`` plus
        ``max_new_tokens`` can never fit the page pool (admission control —
        an accepted request is guaranteed to eventually complete, through
        preemption if need be), and ``Saturated`` when backpressure is on
        (``max_waiting=``) and the waiting queue or page-demand bound is
        exceeded, or a brownout level sheds ``priority``'s class — a
        transient condition the caller should retry (HTTP 429). Generation
        stops after ``max_new_tokens`` or on the first ``eos_id`` (which is
        included in the output).

        ``priority`` ("interactive" | "standard" | "batch") orders
        admission, preemption victims and brownout shedding (DESIGN.md
        Sec. 17); ``deadline_ms`` (relative, from now) orders admission
        within the class (EDF) and protects a nearly-due sequence from
        preemption — it never aborts work. ``deadline_at`` is the absolute
        ``time.monotonic()`` form the supervisor uses on replay so a crash
        does not extend a request's deadline.
        """
        req_id = self._next_id
        self._next_id += 1
        now = time.monotonic()
        if deadline_at is None and deadline_ms is not None:
            deadline_at = now + float(deadline_ms) / 1000.0
        req = Request(req_id, np.asarray(prompt, np.int32).reshape(-1),
                      int(max_new_tokens), eos_id, priority=priority,
                      deadline=deadline_at, submitted_at=now)
        self._seqs[req_id] = self.scheduler.submit(req)
        return req_id

    def would_accept(self, prompt_len, max_new_tokens,
                     priority="standard") -> Optional[Exception]:
        """Mutation-free admission probe: ``None`` when a ``submit`` of this
        size issued right now would be accepted, else the exception it would
        raise (``ValueError`` = can never fit, ``scheduler.Saturated`` =
        busy or class shed under brownout, retry later). Safe to call from
        a thread other than the one driving ``step()`` — it only reads
        counters, and ``submit`` re-validates, so a stale answer costs one
        exception, never state."""
        return self.scheduler.would_accept(
            int(prompt_len) + int(max_new_tokens), priority=priority)

    def step(self) -> bool:
        """Run one scheduler-chosen unit of work (one prefill chunk or one
        packed decode batch = one jitted device dispatch, covering up to
        ``decode_horizon`` fused decode iterations per row); returns False
        when no submitted work remains. Safe to interleave with ``submit``
        — new requests join from the next step. Greedy sampling happens on
        host at ``decode_horizon=1`` and on device inside the fused scan
        otherwise; both are the same f32 argmax, so outputs are
        reproducible across ``execution`` modes, TP meshes and horizons."""
        # blame is reset *before* the step fault-site fires so a crash here
        # (pre-schedule) attributes to no specific request
        self.last_step_rids = ()
        self.last_step_kind = ""
        if self.faults.armed:
            self.faults.fire("step")
        # engine.step opens only once the scheduler chose work, so a step
        # blocked on admission or reservation leaves engine.schedule alone
        with self.phases.span("engine.schedule"):
            work = self.scheduler.schedule()
        if work is None:
            return False
        self.last_step_kind = work[0]
        if work[0] == "prefill":
            self.last_step_rids = (work[1].req.req_id,)
        elif work[0] == "prefill_packed":
            self.last_step_rids = tuple(s.req.req_id for s, _, _ in work[1])
        else:
            self.last_step_rids = tuple(s.req.req_id for s in work[1])
        self.n_steps += 1
        with self.phases.span("engine.step", kind=work[0],
                              rows=len(self.last_step_rids)):
            if work[0] == "prefill":
                self._run_prefill(*work[1:])
            elif work[0] == "prefill_packed":
                self._run_prefill_packed(*work[1:])
            else:
                self._run_decode(work[1])
        return True

    def fork_request(self, req_id, n=1, max_new_tokens=None,
                     eos_id=None) -> List[int]:
        """Fork an unfinished request into ``n`` independent continuations;
        returns their new request ids (``collect()`` keys).

        Each child continues from the parent's current position — prompt
        plus everything sampled so far — with a fresh ``max_new_tokens``
        budget (the parent's unless overridden) and keeps decoding
        independently of the parent. A running parent's KV pages are shared
        by refcount via ``PagedKVCache.fork`` (device copy only for the
        final partial page); if the pool cannot host a fork right now the
        child falls back to the waiting queue as a plain resubmission of
        the parent's tokens, where admission-time prefix matching recovers
        the sharing — either way no slot or page leaks. Under greedy
        sampling every child reproduces the parent's own continuation,
        which is what makes the API testable; it exists for divergent
        continuations (n-best with different budgets/eos, speculative
        branches) once non-greedy sampling lands."""
        seq = self._seqs.get(req_id)
        if seq is None:
            raise KeyError(f"unknown request id {req_id}")
        if seq.state == FINISHED:
            raise ValueError(f"request {req_id} already finished; its pages "
                             "are released (resubmit its tokens instead)")
        budget = (seq.req.max_new_tokens if max_new_tokens is None
                  else int(max_new_tokens))
        # same admission-control gate as submit(): a child whose fork-point
        # prompt plus fresh budget can never fit would otherwise be admitted
        # on pool headroom alone and self-preempt forever at the
        # max_pages_per_seq reserve, livelocking the queue behind it
        why = self.cache.capacity_error(len(seq.tokens) + budget)
        if why is not None:
            raise ValueError(f"fork of request {req_id}: {why}")
        new_ids: List[int] = []
        for _ in range(int(n)):
            new_id = self._next_id
            self._next_id += 1
            req = Request(new_id, seq.tokens.copy(), budget,
                          seq.req.eos_id if eos_id is None else eos_id,
                          priority=seq.req.priority,
                          submitted_at=time.monotonic())
            child = Sequence(req)
            dst = self.cache.fork(seq.slot) if seq.slot >= 0 else None
            if dst is not None:
                child.slot = dst
                child.cache_len = int(self.cache.seq_lens[dst])
                self.scheduler.running.append(child)
                self.n_forks += 1
            else:
                self.scheduler.waiting.append(child)
            self._seqs[new_id] = child
            new_ids.append(new_id)
        return new_ids

    def abort_request(self, req_id) -> bool:
        """Cancel a request at any point in its lifecycle (client
        disconnect, server timeout). Frees everything it holds: a waiting
        request leaves the queue; a running one releases its slot — all its
        pages, including any outstanding decode-horizon lease, return to
        the allocator, and pages it adopted from (or registered into) the
        prefix cache are decref'd onto the reclaimable LRU. Pool accounting
        returns to baseline: nothing leaks (negative-tested).

        Returns True if the request was cancelled, False if it had already
        finished — in which case its uncollected output is *dropped* (the
        caller no longer wants it). Raises ``KeyError`` for ids never
        submitted or already collected/streamed. Must be called from the
        thread driving ``step()`` (the engine is single-threaded; a server
        serializes aborts through its engine loop)."""
        seq = self._seqs.pop(req_id, None)
        if seq is None:
            raise KeyError(f"unknown request id {req_id}")
        self._stream_off.pop(req_id, None)
        self._finished.pop(req_id, None)
        ok = self.scheduler.abort(seq)
        if ok:
            self.n_aborts += 1
        return ok

    def collect(self) -> Dict[int, np.ndarray]:
        """Drain outputs finished since the last ``collect()``: a dict
        ``req_id -> int32 generated tokens`` (prompt not included). Each
        finished request is returned exactly once; uncollected results are
        held, never dropped."""
        out, self._finished = self._finished, {}
        return out

    def stream_updates(self) -> Dict[int, Tuple[List[int], bool]]:
        """Per-token streaming drain: ``{req_id: (new_tokens, finished)}``
        for every request that produced tokens (or finished) since the last
        call. The streaming complement to ``collect()`` — call it after
        each ``step()`` to observe tokens as they are sampled instead of
        waiting for completion. Tokens are reported exactly once and in
        order (``generated`` is append-only, even across preemption, so
        offsets never rewind); with ``decode_horizon=H`` up to H tokens
        arrive per call. A finished request is reported with
        ``finished=True`` exactly once and then fully retired: it leaves
        the ``collect()`` buffer too, so use one drain style per request,
        not both."""
        out: Dict[int, Tuple[List[int], bool]] = {}
        for rid in list(self._seqs):
            seq = self._seqs[rid]
            off = self._stream_off.get(rid, 0)
            new = [int(t) for t in seq.generated[off:]]
            done = seq.state == FINISHED
            if new or done:
                out[rid] = (new, done)
            if done:
                del self._seqs[rid]
                self._stream_off.pop(rid, None)
                self._finished.pop(rid, None)
            elif new:
                self._stream_off[rid] = off + len(new)
        return out

    # -- metrics -------------------------------------------------------------
    @property
    def has_work(self) -> bool:
        """True while any submitted request is unfinished. The generic
        driving predicate for engine owners (``EngineLoop``, benches) —
        the ``EngineSupervisor`` mirrors it, so loops written against
        ``has_work`` drive a raw engine and a supervised one alike."""
        return self.scheduler.has_work

    def stats(self) -> Dict[str, object]:
        """Counter snapshot for metrics exporters. Monotonic counts plus
        the two instantaneous gauges (``queue_depth``, ``running``). The
        ``EngineSupervisor`` exposes the same schema aggregated across
        engine rebuilds — ``ServeMetrics.sync_engine`` consumes either."""
        s = self.scheduler
        return {
            "tokens_out": self.n_tokens_out,
            "steps": self.n_steps,
            "decode_steps": self.n_decode_steps,
            "host_syncs": self.n_host_syncs,
            "work_positions": self.n_work_positions,
            "aborts": self.n_aborts,
            "preemptions": s.n_preemptions,
            "admissions": s.n_admissions,
            "prefix_hits": s.n_prefix_hits,
            "prefix_positions_saved": s.n_prefix_tokens,
            "forks": self.n_forks,
            "prefill_dispatches": self.n_prefill_dispatches,
            "prefill_segments": self.n_prefill_segments,
            "admission_waves": s.n_admission_waves,
            "warmup_seconds": self.warmup_seconds,
            "warmup_traces": self.warmup_entries,
            "preemptions_by_class": dict(s.n_preemptions_by_class),
            "admissions_by_class": dict(s.n_admissions_by_class),
            "sheds_by_class": dict(s.n_sheds_by_class),
            "queue_depth": len(s.waiting),
            "running": len(s.running),
            "host_phase_seconds": self.phases.snapshot(),
        }

    def drain_observations(self) -> Dict[str, List[int]]:
        """Histogram samples accumulated since the last drain:
        ``admission_queue_depth`` (one per admission wave — NOT per prefill
        chunk) and ``packed_segments`` (one per packed prefill dispatch).
        The metrics sync consumes these; each sample is returned once."""
        s = self.scheduler
        out = {"admission_queue_depth": s.queue_depth_obs,
               "packed_segments": self.packed_segment_obs}
        s.queue_depth_obs = []
        self.packed_segment_obs = []
        return out

    def warmup(self) -> Dict[str, object]:
        """AOT-warm every dispatch shape reachable from this engine's
        config (serve/warmup.py) so steady-state serving never traces.
        Returns the warmup report and records it in ``stats()``."""
        from .warmup import warm_engine
        report = warm_engine(self)
        self.warmup_seconds += report["seconds"]
        self.warmup_entries += report["entries"]
        return report

    def close(self, check: bool = True):
        """Tear down the engine. With ``check=True`` (default) the page
        allocator's full invariant suite runs first — refcounts, free
        list, lease extents, registry/LRU consistency — and, when no
        request is live, the pool must be back at its post-init baseline:
        zero leaked pages (``PageStateError`` otherwise)."""
        if check:
            self.cache.check_invariants(
                expect_idle=not self.scheduler.has_work)

    @property
    def n_prefix_hits(self):
        """Admissions that longest-prefix-matched the page registry."""
        return self.scheduler.n_prefix_hits

    @property
    def n_prefix_positions_saved(self):
        """Token positions adopted from the prefix cache instead of being
        prefilled (``cache_len`` started at the matched page boundary)."""
        return self.scheduler.n_prefix_tokens

    def run(self):
        """Drive until all submitted work is complete; return all outputs."""
        done: Dict[int, np.ndarray] = {}
        while self.scheduler.has_work:
            if not self.step():
                break
            done.update(self.collect())
        done.update(self.collect())
        return done

    # -- work kinds ----------------------------------------------------------
    def _run_prefill(self, seq, chunk_tokens, start):
        c = self.prefill_chunk
        n = len(chunk_tokens)
        self.n_prefill_dispatches += 1
        self.n_prefill_segments += 1
        with self.phases.span("engine.prepare"):
            tokens = np.zeros((1, c), np.int32)
            tokens[0, :n] = chunk_tokens
            q_pos = np.full((1, c), -1, np.int32)
            q_pos[0, :n] = start + np.arange(n)
            kv_lens = np.asarray([start + n], np.int32)
        logits = self._dispatch([seq.slot], tokens, q_pos, kv_lens)
        if self.faults.armed:
            self.faults.fire("apply")   # device written, host not yet
        with self.phases.span("engine.apply"):
            seq.cache_len = start + n
            self.cache.commit(seq.slot, seq.cache_len)
            self.cache.register_prefix(seq.slot, seq.tokens[:seq.cache_len])
            if seq.cache_len == len(seq.tokens):    # prompt fully in cache
                if not seq.is_done():               # e.g. max_new_tokens=0
                    self._sample_and_advance(seq, logits[0])
                seq.state = DECODE
                self._maybe_finish(seq)

    def _run_prefill_packed(self, segs, bucket):
        """One ragged dispatch over up to ``max_batch`` segments' chunks
        (DESIGN.md Sec. 16). The packed row concatenates each segment's
        tokens (per-token seg ids route KV writes and gathers to the
        segment's own pages), the bucket pads the row to a pre-compiled
        length, and each segment's next-token logits come back at its
        ``last_idx``. Post-dispatch bookkeeping is the unpacked path's, per
        segment: commit, prefix registration, and sample-or-continue."""
        t = bucket
        s_max = self.max_batch
        with self.phases.span("engine.prepare"):
            tokens = np.zeros((t,), np.int32)
            seg_ids = np.full((t,), -1, np.int32)
            q_pos = np.full((t,), -1, np.int32)
            kv_lens = np.zeros((s_max,), np.int32)
            slots = np.full((s_max,), -1, np.int32)
            last_idx = np.zeros((s_max,), np.int32)
            seg_off = np.zeros((s_max,), np.int32)
            off = 0
            for i, (seq, start, n) in enumerate(segs):
                toks = seq.tokens
                tokens[off:off + n] = toks[start:start + n]
                seg_ids[off:off + n] = i
                q_pos[off:off + n] = start + np.arange(n)
                kv_lens[i] = start + n
                slots[i] = seq.slot
                seg_off[i] = off
                last_idx[i] = off + n - 1
                off += n
            self.n_prefill_dispatches += 1
            self.n_prefill_segments += len(segs)
            if len(self.packed_segment_obs) < 4096:
                self.packed_segment_obs.append(len(segs))
            self.n_work_positions += t
            bt = self.cache.table_rows([int(s) for s in slots])
            args = [jnp.asarray(a) for a in (tokens, seg_ids, q_pos,
                                             kv_lens)]
            args += [bt] + [jnp.asarray(a) for a in (slots, last_idx,
                                                     seg_off)]
        with self.phases.span("engine.dispatch"):
            logits, self.cache.pools = self._prefill_fn(
                self.cache.pools, self.params, *args)
        self.n_host_syncs += 1          # blocking (S, vocab) logits fetch
        with self.phases.span("engine.wait"):
            logits = np.asarray(logits)
        if self.faults.armed:
            self.faults.fire("apply")   # device written, host not yet
        with self.phases.span("engine.apply"):
            for i, (seq, start, n) in enumerate(segs):
                seq.cache_len = start + n
                self.cache.commit(seq.slot, seq.cache_len)
                self.cache.register_prefix(seq.slot,
                                           seq.tokens[:seq.cache_len])
                if seq.cache_len == len(seq.tokens):    # prompt in cache
                    if not seq.is_done():           # e.g. max_new_tokens=0
                        self._sample_and_advance(seq, logits[i])
                    seq.state = DECODE
                    self._maybe_finish(seq)

    def _decode_bucket(self, seqs):
        """Shared decode-batch shape policy: pad to the next power of two
        and gather each row's slot + last sampled (not-yet-cached) token.
        Both decode paths build on this so the bucket rounding and the
        last-token convention have one source of truth."""
        b = 1
        while b < len(seqs):
            b *= 2
        slots = [-1] * b
        last = np.zeros((b,), np.int32)
        for i, seq in enumerate(seqs):
            slots[i] = seq.slot
            last[i] = seq.generated[-1]
        return b, slots, last

    def _run_decode(self, seqs):
        self.n_decode_steps += 1
        if self.decode_horizon > 1:
            return self._run_decode_horizon(seqs)
        with self.phases.span("engine.prepare"):
            b, slots, last = self._decode_bucket(seqs)
            tokens = last[:, None]
            q_pos = np.full((b, 1), -1, np.int32)
            kv_lens = np.zeros((b,), np.int32)
            for i, seq in enumerate(seqs):
                q_pos[i, 0] = seq.n_total - 1
                kv_lens[i] = seq.n_total
        logits = self._dispatch(slots, tokens, q_pos, kv_lens)
        if self.faults.armed:
            self.faults.fire("apply")   # device written, host not yet
        with self.phases.span("engine.apply"):
            for i, seq in enumerate(seqs):
                seq.cache_len = seq.n_total
                self.cache.commit(seq.slot, seq.cache_len)
                # decode advances one position per step, so a page fills
                # (and becomes registrable) exactly on the boundary commits
                if self.prefix_cache and \
                        seq.cache_len % self.page_size == 0:
                    self.cache.register_prefix(seq.slot,
                                               seq.tokens[:seq.cache_len])
                self._sample_and_advance(seq, logits[i])
                self._maybe_finish(seq)

    def _run_decode_horizon(self, seqs):
        """One fused dispatch = up to ``decode_horizon`` decode iterations
        with on-device greedy sampling (DESIGN.md Sec. 12). Only (B, H)
        tokens + done masks come back; the host applies them in bulk —
        commit to the row's final extent, registration catches every page
        boundary crossed inside the horizon (``register_prefix`` is
        incremental over newly filled pages), and finish/eos semantics are
        unchanged because ``valid`` row masks are exact prefix masks."""
        h = self.decode_horizon
        # brownout horizon clamp (DESIGN.md Sec. 17): the *static* trace
        # horizon h never changes — a reduced effective horizon only lowers
        # the dynamic per-row budget below, so the same compiled scan
        # retires fewer tokens per dispatch. Schedule-only, trace-free,
        # and token-identical (greedy output is horizon-independent).
        eff = self.scheduler.effective_horizon
        with self.phases.span("engine.prepare"):
            b, slots, tokens = self._decode_bucket(seqs)
            start_pos = np.full((b,), -1, np.int32)
            n_left = np.zeros((b,), np.int32)
            eos = np.full((b,), -1, np.int32)
            for i, seq in enumerate(seqs):
                start_pos[i] = seq.n_total - 1
                n_left[i] = min(seq.req.max_new_tokens - len(seq.generated),
                                eff)
                if seq.req.eos_id is not None:
                    eos[i] = seq.req.eos_id
            self.n_work_positions += b * h
            bt = self.cache.table_rows(slots)
            args = [jnp.asarray(a) for a in (tokens, start_pos, n_left, eos)]
            args += [bt, jnp.asarray(np.asarray(slots, np.int32))]
        with self.phases.span("engine.dispatch"):
            out_tok, valid, self.cache.pools = self._horizon_fn(
                self.cache.pools, self.params, *args)
        with self.phases.span("engine.wait"):
            out_tok, valid = np.asarray(out_tok), np.asarray(valid)
        self.n_host_syncs += 1
        if self.faults.armed:
            self.faults.fire("apply")   # device written, host not yet
        with self.phases.span("engine.apply"):
            for i, seq in enumerate(seqs):
                k = int(valid[i].sum())     # valid is a prefix mask per row
                for t in out_tok[i, :k]:
                    seq.generated.append(int(t))
                self.n_tokens_out += k
                # the dispatch wrote K/V for each *input* token: positions
                # n_total-1 .. n_total-2+k of the pre-dispatch sequence —
                # the final sampled token is, as ever, not yet in the cache
                seq.cache_len = seq.n_total - 1
                self.cache.commit(seq.slot, seq.cache_len)
                if self.prefix_cache:
                    self.cache.register_prefix(seq.slot,
                                               seq.tokens[:seq.cache_len])
                self._maybe_finish(seq)

    # -- helpers --------------------------------------------------------------
    def _dispatch(self, slots, tokens, q_pos, kv_lens):
        with self.phases.span("engine.prepare"):
            self.n_work_positions += tokens.size
            bt = self.cache.table_rows(slots)
            args = [jnp.asarray(a) for a in (tokens, q_pos, kv_lens)]
            args += [bt, jnp.asarray(np.asarray(slots, np.int32))]
        with self.phases.span("engine.dispatch"):
            logits, self.cache.pools = self._step_fn(
                self.cache.pools, self.params, *args)
        self.n_host_syncs += 1          # blocking (B, vocab) logits fetch
        with self.phases.span("engine.wait"):
            return np.asarray(logits)

    def _sample_and_advance(self, seq, logits):
        seq.generated.append(int(np.argmax(logits)))
        self.n_tokens_out += 1

    def _maybe_finish(self, seq):
        if seq.is_done():
            self._finished[seq.req.req_id] = np.asarray(seq.generated,
                                                        np.int32)
            self.scheduler.finish(seq)
