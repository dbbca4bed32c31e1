"""Static-batch serving engine: prefill -> ring-buffer decode.

The engine demonstrates the paper's deployment story end-to-end: params may
be a mixed pytree with MSB ``QTensor`` leaves (quantize-on-load via
core.policy). ``execution="packed"`` (default on TPU) rewrites them once at
load into kernel-layout ``PackedQTensor`` so every forward streams 4-bit
codes through the fused Pallas matmul; ``execution="simulated"`` keeps the
per-layer dequantize of the paper's bf16 simulation (Sec. 4.1). Both modes
produce identical greedy tokens (DESIGN.md Sec. 9).

This is the non-batched (fixed batch, lockstep decode) fallback; production
traffic goes through ``serve.continuous.ContinuousEngine``, which adds
request scheduling, a paged KV cache (DESIGN.md §8), and automatic
cross-request prefix caching + ``fork_request`` page sharing (§11) — none
of which exist here: every ``generate`` call prefills its full prompts. It
also covers the decoder-only architectures paging does not (ssm/xlstm
recurrent state).
"""
from __future__ import annotations

import dataclasses
import logging
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

log = logging.getLogger(__name__)


def resolve_execution(execution, params):
    """Resolve the engine ``execution`` mode and (maybe) pack params.

    ``"packed"`` rewrites QTensor leaves to kernel-layout ``PackedQTensor``
    once at load (core.policy.pack_params) so every forward streams 4-bit
    codes; ``"simulated"`` keeps per-call dequantize (paper-parity bf16
    math, Sec. 4.1). Default: packed on TPU, simulated elsewhere — the
    jnp packed fallback is correct off-TPU but pays unpack cost per call.
    """
    if execution is None:
        backend = jax.default_backend()
        execution = "packed" if backend == "tpu" else "simulated"
        log.warning("execution=None on backend %r: serving %r", backend,
                    execution)
    if execution == "packed":
        from ..core.policy import pack_params
        params, _ = pack_params(params)
    elif execution != "simulated":
        raise ValueError(f"execution must be 'packed' or 'simulated', "
                         f"got {execution!r}")
    return execution, params


@dataclasses.dataclass
class ServeEngine:
    """Static-batch serving engine.

    Contract: ``generate(prompts, n)`` runs one prefill + n lockstep greedy
    decode steps and returns exactly the argmax token sequence of the
    underlying model — independent of ``execution`` mode and of ``mesh``
    (tensor parallelism changes where the math runs, not which tokens come
    out). ``params`` may be any mixed pytree of dense arrays and MSB
    ``QTensor`` leaves; the engine never mutates the caller's tree in
    place.

    ``mesh``: optional device mesh for tensor parallelism. Params are
    partitioned once at load (``core.policy.tp_partition_params``) and
    prefill/decode run under ``shard_map`` with manual collectives; the KV
    ring cache shards by head exactly when the attention projections
    themselves sharded. ``parallel`` (a ``ParallelContext``) remains the
    GSPMD alternative; the two are mutually exclusive.
    """
    model: object
    params: object
    max_seq: int
    parallel: object = None
    execution: Optional[str] = None   # "packed" | "simulated" | None=auto
    mesh: object = None               # tensor-parallel device mesh

    def __post_init__(self):
        if self.mesh is not None and self.parallel is not None:
            raise ValueError("pass either mesh= (manual TP) or parallel= "
                             "(GSPMD), not both")
        self.execution, self.params = resolve_execution(self.execution,
                                                        self.params)
        if self.mesh is not None:
            self._init_tensor_parallel()
        else:
            self._prefill = jax.jit(
                lambda p, b: self.model.prefill(p, b, self.parallel))
            self._decode = jax.jit(
                lambda p, c, t, pos: self.model.decode_step(p, c, t, pos,
                                                            self.parallel))
        self._score = jax.jit(
            lambda p, b: self.model.loss(p, b, self.parallel))
        # n_tokens and the sampling branch are static; temperature itself is
        # traced, so sweeping it never retraces the scan
        self._scan_generate = jax.jit(self._scan_generate_impl,
                                      static_argnums=(6, 7))

    def _init_tensor_parallel(self):
        """Build shard_map'd prefill/decode over ``mesh`` (DESIGN.md §10).

        The KV ring cache shards by head only when the attention weights
        themselves sharded (the planner's all-or-nothing head rule);
        otherwise attention is replicated and only MLP / MoE / unembedding
        run tensor-parallel. Logits come back replicated either way.
        """
        from jax.sharding import NamedSharding, PartitionSpec as P
        from ..core.policy import tp_localize, tp_partition_params
        from ..parallel.sharding import TPShard, from_mesh
        ctx = from_mesh(self.mesh)
        tp = TPShard(axis=ctx.tp_axis, size=ctx.tp_size)
        self.tp = tp
        self.params, pspecs, self.tp_report = tp_partition_params(
            self.params, tp.size, cfg=self.model.cfg, axis=tp.axis)
        self.params = jax.device_put(
            self.params,
            jax.tree_util.tree_map(lambda s: NamedSharding(self.mesh, s),
                                   pspecs))
        attn_sharded = any(v == "heads" for v in self.tp_report.values())

        def cache_spec(defs):
            if isinstance(defs, dict):
                return {k: cache_spec(v) for k, v in defs.items()}
            _shape, _dt, axes = defs
            return P(*[tp.axis if a == "heads" and attn_sharded else None
                       for a in axes])

        cspecs = cache_spec(self.model.cache_defs(1, 1))
        model, rep = self.model, P()

        def local_prefill(params, batch):
            return model.prefill(tp_localize(params), batch, tp)

        def local_decode(params, cache, tokens, pos):
            return model.decode_step(tp_localize(params), cache, tokens,
                                     pos, tp)

        # outputs are replicated by construction (psum / all_gather), which
        # the vma checker cannot prove through axis_index head slicing
        self._prefill = jax.jit(jax.shard_map(
            local_prefill, mesh=self.mesh, check_vma=False,
            in_specs=(pspecs, rep), out_specs=(rep, cspecs)))
        self._decode = jax.jit(jax.shard_map(
            local_decode, mesh=self.mesh, check_vma=False,
            in_specs=(pspecs, cspecs, rep, rep), out_specs=(rep, cspecs)))

    def _grow_cache(self, cache, prompt_len):
        """Re-home prefill caches (length P) into max_seq ring buffers.

        K/V leaves are identified by their position in the cache pytree
        (the ``attn`` subtree's ``k``/``v`` entries, per Model.cache_defs) —
        not by shape, which false-positives whenever an unrelated state leaf
        happens to have dim 2 == prompt_len. ``xattn`` leaves are static
        encoder K/V and must NOT grow (decode's cross branch attends every
        cache row, so zero-padding would corrupt it)."""
        s = self.max_seq

        def grow(path, leaf):
            keys = [p.key for p in path if hasattr(p, "key")]
            if (len(keys) >= 2 and keys[-1] in ("k", "v")
                    and keys[-2] == "attn"):             # (P, B, S, KV, hd)
                pad = [(0, 0)] * leaf.ndim
                pad[2] = (0, s - prompt_len)
                return jnp.pad(leaf, pad)
            return leaf

        new = {"layers": jax.tree_util.tree_map_with_path(grow,
                                                          cache["layers"])}
        if "pos" in cache:
            pos = jnp.full((cache["pos"].shape[0], s), -1, jnp.int32)
            new["pos"] = jax.lax.dynamic_update_slice_in_dim(
                pos, cache["pos"], 0, 1)
        return new

    def generate(self, prompts, n_tokens, temperature=0.0, rng=None):
        """prompts: (B, P) int32. Returns (B, n_tokens) greedy/temp samples.

        The decode loop is one jitted ``lax.scan`` with sampling *on
        device* — greedy argmax or temperature ``jax.random.categorical``,
        both inside the scan body — so serving ``n_tokens`` costs one
        prefill dispatch plus one scan dispatch instead of ``n_tokens``
        per-step host round trips. Tokens (and temperature samples, for a
        given ``rng``) are identical to the per-step loop this replaced
        (``_generate_stepwise``, kept for the identity test): same sample →
        decode → advance ops in the same order, only the dispatch boundary
        moved."""
        prompts = jnp.asarray(prompts, jnp.int32)
        b, p = prompts.shape
        assert p + n_tokens <= self.max_seq
        logits, cache = self._prefill(self.params, {"tokens": prompts})
        cache = self._grow_cache(cache, p)
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        temp = jnp.asarray(float(temperature) if temperature > 0 else 1.0,
                           jnp.float32)
        return self._scan_generate(self.params, logits, cache,
                                   jnp.full((b,), p, jnp.int32), rng, temp,
                                   int(n_tokens), bool(temperature > 0))

    def _scan_generate_impl(self, params, logits, cache, cur, rng, temp,
                            n_tokens, use_temp):
        def body(carry, _):
            logits, cache, cur, rng = carry
            if use_temp:
                rng, sub = jax.random.split(rng)
                tok = jax.random.categorical(sub, logits / temp, axis=-1)
            else:
                tok = jnp.argmax(logits, axis=-1)
            logits, cache = self._decode(params, cache,
                                         tok[:, None].astype(jnp.int32), cur)
            return (logits, cache, cur + 1, rng), tok
        _, toks = jax.lax.scan(body, (logits, cache, cur, rng), None,
                               length=n_tokens)
        return toks.T                             # (n, B) -> (B, n)

    def _generate_stepwise(self, prompts, n_tokens, temperature=0.0,
                           rng=None):
        """The pre-scan per-step Python loop (one dispatch + one host sync
        per token). Retained as the identity oracle for ``generate`` — not
        a serving path."""
        prompts = jnp.asarray(prompts, jnp.int32)
        b, p = prompts.shape
        assert p + n_tokens <= self.max_seq
        logits, cache = self._prefill(self.params, {"tokens": prompts})
        cache = self._grow_cache(cache, p)
        out = []
        cur = jnp.full((b,), p, jnp.int32)
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        for i in range(n_tokens):
            if temperature > 0:
                rng, sub = jax.random.split(rng)
                tok = jax.random.categorical(sub, logits / temperature, axis=-1)
            else:
                tok = jnp.argmax(logits, axis=-1)
            out.append(tok)
            logits, cache = self._decode(self.params, cache,
                                         tok[:, None].astype(jnp.int32), cur)
            cur = cur + 1
        return jnp.stack(out, axis=1)

    def score(self, tokens):
        """Mean next-token NLL of ``tokens`` (B, S) under the model."""
        batch = {"tokens": tokens[:, :-1],
                 "labels": tokens[:, 1:].astype(jnp.int32)}
        loss, _ = self._score(self.params, batch)
        return float(loss)
