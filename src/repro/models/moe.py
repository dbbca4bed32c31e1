"""Mixture-of-Experts layer with all-to-all expert parallelism.

Sharding scheme (DESIGN.md Sec. 4): inside ``shard_map`` the token stream
arrives sharded over BOTH the data axes and the ``model`` axis (sequence-
parallel residual stream), so each rank routes only T/(dp*tp) tokens:

  route local tokens -> dispatch buffer (E, C, D)
    -> all-to-all over `model` (split experts, concat capacity)
    -> resident-expert FFN (E/tp experts, FSDP-gathered weights)
    -> all-to-all back -> combine with gates

The output stays sequence-parallel — no psum. Expert weights are stored
(E, D, F) sharded [experts -> model, D/F -> data]; the ZeRO-3 per-layer
bf16 all-gather happens inside the shard_map (its transpose is the
reduce-scatter of the expert grads).

Experts are zero-padded to a multiple of the tp size when needed
(granite-moe: 40 -> 48); padded experts are masked out of routing.

A mathematically identical single-device path (e_offset=0, no collectives)
serves smoke tests and the EP-vs-local equivalence test.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _route(x2d, router_w, e_total, n_real, k, capacity):
    """Top-k routing + per-expert positions for local tokens.

    Returns (gates (T,k), eidx (T,k), pos (T,k), keep (T,k), aux_loss).
    Padded experts (id >= n_real) are masked out of the softmax.
    """
    t = x2d.shape[0]
    logits = jnp.einsum("td,de->te", x2d.astype(jnp.float32),
                        router_w.astype(jnp.float32))
    if n_real < e_total:
        logits = jnp.where(jnp.arange(e_total) < n_real, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, eidx = jax.lax.top_k(probs, k)                      # (T, k)
    gates = gates / jnp.maximum(jnp.sum(gates, -1, keepdims=True), 1e-9)
    flat_e = eidx.swapaxes(0, 1).reshape(-1)                   # (k*T,)
    onehot = jax.nn.one_hot(flat_e, e_total, dtype=jnp.int32)  # (k*T, E)
    pos_flat = jnp.cumsum(onehot, axis=0) - 1
    pos_flat = jnp.take_along_axis(pos_flat, flat_e[:, None], 1)[:, 0]
    pos = pos_flat.reshape(k, t).swapaxes(0, 1)                # (T, k)
    keep = pos < capacity
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(eidx[:, 0], e_total, dtype=jnp.float32), 0)
    aux = n_real * jnp.sum(me * ce)
    return gates, eidx, pos, keep, aux


def _expert_ffn_quantized(xe, wg, wi, wo, counts):
    """Quantized-expert FFN: one expert at a time, router-gated.

    The old path dequantized all three (E, D, F) expert tensors eagerly on
    every call — full bf16 materialization even for experts the router
    never selected. Here ``lax.map`` streams one expert's weights at a
    time (dequantize / packed-dispatch just that slice) and ``lax.cond``
    on the router count skips the matmuls entirely for experts with no
    routed tokens — at decode (1 token, k of E experts active) most
    experts take the zero branch.
    """
    from ..core.quantize import PackedQTensor, QTensor
    from ..kernels.msb_matmul.ops import packed_matmul

    def mm(xc, w):
        if isinstance(w, PackedQTensor):
            return packed_matmul(xc, w)
        if isinstance(w, QTensor):
            w = w.dequantize()
        return jnp.einsum("cd,df->cf", xc, w.astype(xc.dtype))

    def one(args):
        xc, g_, i_, o_, cnt = args

        def compute(xc):
            g = mm(xc, g_)
            u = mm(xc, i_)
            h = jax.nn.silu(g.astype(jnp.float32)).astype(xc.dtype) * u
            return mm(h, o_)

        return jax.lax.cond(cnt > 0, compute, jnp.zeros_like, xc)

    return jax.lax.map(one, (xe, wg, wi, wo, counts))


def _expert_ffn(xe, wg, wi, wo, chunk=2048):
    """xe: (E_loc, C, D); weights (E_loc, D, F) / (E_loc, F, D).

    Chunked over capacity (remat'd) so the (E_loc, C, F) hidden activations
    never materialize for the full capacity at once.
    """
    e, c, d = xe.shape

    @jax.checkpoint
    def one(xc):
        g = jnp.einsum("ecd,edf->ecf", xc, wg.astype(xc.dtype))
        u = jnp.einsum("ecd,edf->ecf", xc, wi.astype(xc.dtype))
        h = jax.nn.silu(g.astype(jnp.float32)).astype(xc.dtype) * u
        return jnp.einsum("ecf,efd->ecd", h, wo.astype(xc.dtype))

    ck = min(chunk, c)
    while c % ck:
        ck -= 1
    if ck == c:
        return one(xe)
    xr = jnp.moveaxis(xe.reshape(e, c // ck, ck, d), 1, 0)
    ys = jax.lax.map(one, xr)
    return jnp.moveaxis(ys, 0, 1).reshape(e, c, d)


def _dispatch(x2d, gates, eidx, pos, keep, e_total, capacity):
    """Scatter tokens into the (E*C+1, D) dispatch buffer (last row = drop)."""
    t, d = x2d.shape
    k = eidx.shape[1]
    slot = jnp.where(keep, eidx * capacity + pos, e_total * capacity)
    flat_slot = slot.reshape(-1)
    xrep = jnp.broadcast_to(x2d[:, None], (t, k, d)).reshape(-1, d)
    buf = jnp.zeros((e_total * capacity + 1, d), x2d.dtype)
    return buf.at[flat_slot].set(xrep, mode="drop"), flat_slot


def _combine(ye_flat, flat_slot, gates, keep, t, k, d):
    yflat = jnp.concatenate([ye_flat, jnp.zeros((1, d), ye_flat.dtype)], 0)
    yk = yflat[flat_slot].reshape(t, k, d)
    w = (gates * keep).astype(yk.dtype)
    return jnp.sum(yk * w[..., None], axis=1)


def _moe_tp(p, x, cfg, tp):
    """Expert-sharded MoE under a manual-TP ``shard_map`` (DESIGN.md §10).

    Every rank routes the full (replicated) token stream with the
    replicated router — gates, expert assignment and per-expert positions
    are bit-identical across ranks and to the single-device path — then
    dispatches only the tokens bound for its ``E/tp`` resident experts,
    runs the streamed/router-gated quantized expert FFN on them, and
    combines with gates masked to local experts. The psum over the model
    axis adds disjoint per-rank partial sums (every token-expert term is
    computed on exactly one rank), so the result equals the single-device
    combine up to float-add reordering across ranks.
    """
    b, s, d = x.shape
    k = cfg.n_experts_active
    e_total = cfg.n_experts_padded
    e_loc = e_total // tp.size
    capacity = _capacity(b * s, k, e_total, cfg.capacity_factor)
    x2d = x.reshape(-1, d)
    gates, eidx, pos, keep, aux = _route(x2d, p["router"], e_total,
                                         cfg.n_experts, k, capacity)
    r = jax.lax.axis_index(tp.axis)
    local = (eidx >= r * e_loc) & (eidx < (r + 1) * e_loc)
    keep_l = keep & local
    eidx_l = jnp.where(local, eidx - r * e_loc, 0)
    buf, flat_slot = _dispatch(x2d, gates, eidx_l, pos, keep_l, e_loc,
                               capacity)
    xe = buf[:-1].reshape(e_loc, capacity, d)
    counts = jnp.zeros((e_loc,), jnp.int32).at[eidx_l.reshape(-1)].add(
        keep_l.reshape(-1).astype(jnp.int32))
    ye = _expert_ffn_quantized(xe, p["wg"], p["wi"], p["wo"], counts)
    y = _combine(ye.reshape(-1, d), flat_slot, gates, keep_l, b * s, k, d)
    y = jax.lax.psum(y, tp.axis)
    return y.reshape(b, s, d), aux


def moe_layer(p, x, cfg, parallel=None):
    """x: (B, S, D) -> (B, S, D).

    ``parallel``: None (single device), a ``ParallelContext`` (training-
    style all-to-all expert parallelism under its own shard_map), or a
    ``TPShard`` (serving: already inside shard_map — expert-sharded leaves
    take ``_moe_tp``, anything else falls back to the replicated local
    path, which is exact).
    """
    from ..core.quantize import PackedQTensor, QTensor
    from ..parallel.sharding import TPShard
    if isinstance(parallel, TPShard):
        if parallel.size > 1 and getattr(p.get("wg"), "shard", None) == "e":
            return _moe_tp(p, x, cfg, parallel)
        parallel = None
    quantized = isinstance(p.get("wg"), (QTensor, PackedQTensor))
    b, s, d = x.shape
    k = cfg.n_experts_active
    e_total = cfg.n_experts_padded
    n_real = cfg.n_experts

    ep_ok = (parallel is not None
             and e_total % parallel.tp_size == 0
             and (b * s) % (parallel.dp_size * parallel.tp_size) == 0)
    if not ep_ok:
        capacity = _capacity(b * s, k, e_total, cfg.capacity_factor)
        gates, eidx, pos, keep, aux = _route(
            x.reshape(-1, d), p["router"], e_total, n_real, k, capacity)
        buf, flat_slot = _dispatch(x.reshape(-1, d), gates, eidx, pos, keep,
                                   e_total, capacity)
        xe = buf[:-1].reshape(e_total, capacity, d)
        if quantized:
            counts = jnp.zeros((e_total,), jnp.int32).at[
                eidx.reshape(-1)].add(keep.reshape(-1).astype(jnp.int32))
            ye = _expert_ffn_quantized(xe, p["wg"], p["wi"], p["wo"], counts)
        else:
            ye = _expert_ffn(xe, p["wg"], p["wi"], p["wo"])
        y = _combine(ye.reshape(-1, d), flat_slot, gates, keep, b * s, k, d)
        return y.reshape(b, s, d), aux

    if quantized:    # EP collectives need dense bf16 expert weights
        p = dict(p, wg=p["wg"].dequantize(), wi=p["wi"].dequantize(),
                 wo=p["wo"].dequantize())

    mesh = parallel.mesh
    tp = parallel.tp_size
    tp_axis = parallel.tp_axis
    dp_axes = parallel.dp_axes
    fsdp = parallel.fsdp_axis
    e_loc = e_total // tp
    t_local = (b * s) // (parallel.dp_size * tp)
    capacity = _capacity(t_local, k, e_total, cfg.capacity_factor)
    P = jax.sharding.PartitionSpec

    def inner(xl, rw, wg, wi, wo):
        if fsdp is not None:  # ZeRO-3 per-layer bf16 gather
            wg = jax.lax.all_gather(wg.astype(cfg.dtype), fsdp, axis=1,
                                    tiled=True)
            wi = jax.lax.all_gather(wi.astype(cfg.dtype), fsdp, axis=1,
                                    tiled=True)
            wo = jax.lax.all_gather(wo.astype(cfg.dtype), fsdp, axis=1,
                                    tiled=True)
        x2d = xl.reshape(-1, d)
        gates, eidx, pos, keep, aux = _route(x2d, rw, e_total, n_real, k,
                                             capacity)
        buf, flat_slot = _dispatch(x2d, gates, eidx, pos, keep, e_total,
                                   capacity)
        # (E, C, D) -> a2a -> (E_loc, tp*C, D): resident experts gather their
        # tokens from every source rank
        send = buf[:-1].reshape(e_total, capacity, d)
        recv = jax.lax.all_to_all(send, tp_axis, split_axis=0, concat_axis=1,
                                  tiled=True)
        ye = _expert_ffn(recv, wg, wi, wo)
        back = jax.lax.all_to_all(ye, tp_axis, split_axis=1, concat_axis=0,
                                  tiled=True)
        y = _combine(back.reshape(-1, d), flat_slot, gates, keep,
                     t_local, k, d)
        aux = jax.lax.psum(aux, (tp_axis, *dp_axes)) / parallel.n_devices
        return y.reshape(xl.shape), aux

    wspec = P(tp_axis, fsdp, None)
    y, aux = jax.shard_map(
        inner, mesh=mesh,
        in_specs=(P(dp_axes, tp_axis, None), P(None, None),
                  wspec, wspec, wspec),
        out_specs=(P(dp_axes, tp_axis, None), P()),
    )(x, p["router"], p["wg"], p["wi"], p["wo"])
    return y, aux


def _capacity(tokens, k, e_total, cf):
    cap = int(cf * tokens * k / max(e_total, 1))
    return max(8, -(-cap // 8) * 8)
