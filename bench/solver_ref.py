"""Plain NumPy reference of the block-wise MSB grouping (paper Sec. 3).

A block of 64 weights is split into at most 8 groups of its sorted
magnitudes; each group's codebook entry is the mean magnitude of the group,
and a weight decodes to its sign times its group's entry. The optimal
grouping minimises the block's squared error, and the exact dynamic program
over sorted positions finds it:

    C[i, j] = sum over [i, j) of (v - mean)^2        (0 <= i < j <= 64)
    D_1[j]  = C[0, j];   D_k[j] = min_i D_{k-1}[i] + C[i, j]

computed here in float64. ``solve`` returns codes and codebooks in the
program's storage convention (code = sign * (level + 1)); ``precision``
"bfloat16" rounds every stored quantity of the same program to bfloat16,
which is the lower-precision control.
"""
from __future__ import annotations

import numpy as np

GROUPS = 8


def _round(x, precision):
    if precision == "float64":
        return x
    import ml_dtypes
    return x.astype(ml_dtypes.bfloat16).astype(np.float64)


def _cumsum(x, precision):
    """Prefix sums with a zero in front, each partial sum rounded."""
    out = np.zeros(x.shape[:-1] + (x.shape[-1] + 1,), np.float64)
    for i in range(x.shape[-1]):
        out[..., i + 1] = _round(out[..., i] + x[..., i], precision)
    return out


def solve(blocks, precision="float64"):
    """blocks (nb, n) -> codes int8 (nb, n), codebooks float64 (nb, 8)."""
    w = np.asarray(blocks, np.float64)
    nb, n = w.shape
    mags = np.abs(w)
    order = np.argsort(mags, axis=1, kind="stable")
    v = _round(np.take_along_axis(mags, order, 1), precision)
    s1, s2 = _cumsum(v, precision), _cumsum(v * v, precision)
    idx = np.arange(n + 1)
    m = (idx[None, :] - idx[:, None]).astype(np.float64)
    d1 = s1[:, None, :] - s1[:, :, None]
    d2 = s2[:, None, :] - s2[:, :, None]
    cost = _round(d2 - _round(d1 * d1, precision) / np.maximum(m, 1),
                  precision)
    cost = np.where(m[None] >= 1, cost, np.inf)
    g = min(GROUPS, n)
    best = cost[:, 0, :]
    args = []
    for _ in range(1, g):
        tot = _round(best[:, :, None] + cost, precision)   # (nb, i, j)
        args.append(np.argmin(tot, axis=1))
        best = np.min(tot, axis=1)
    # walk back the split points: bounds[k] for k = g .. 0
    bounds = np.zeros((nb, g + 1), np.int64)
    bounds[:, g] = n
    for k in range(g - 1, 0, -1):
        bounds[:, k] = np.take_along_axis(args[k - 1], bounds[:, k + 1:k + 2],
                                          1)[:, 0]
    level_sorted = np.zeros((nb, n), np.int64)
    for k in range(1, g):
        level_sorted += idx[None, :n] >= bounds[:, k:k + 1]
    cnt = np.diff(bounds, axis=1).astype(np.float64)
    sums = (np.take_along_axis(s1, bounds[:, 1:], 1)
            - np.take_along_axis(s1, bounds[:, :-1], 1))
    scales = _round(np.where(cnt > 0, sums / np.maximum(cnt, 1), 0.0),
                    precision)
    levels = np.empty_like(level_sorted)
    np.put_along_axis(levels, order, level_sorted, 1)
    sign = np.where(w < 0, -1, 1)
    codes = np.where(w == 0, 0, sign * (levels + 1)).astype(np.int8)
    return codes, scales


def decode(codes, scales):
    codes = np.asarray(codes, np.int64)
    lv = np.maximum(np.abs(codes) - 1, 0)
    mag = np.take_along_axis(np.asarray(scales, np.float64), lv, 1)
    return np.where(codes == 0, 0.0, np.sign(codes) * mag)


def sse(blocks, codes, scales):
    """Squared error of each block under the given codes and codebooks."""
    w = np.asarray(blocks, np.float64)
    return np.sum((w - decode(codes, scales)) ** 2, axis=1)


def excess(blocks, codes, scales, chunk=2048):
    """Per block: (squared error of the given answer - optimal squared
    error) / optimal squared error, the optimum from ``solve`` in float64."""
    w = np.asarray(blocks, np.float64)
    out = np.empty(len(w))
    for s in range(0, len(w), chunk):
        sl = slice(s, s + chunk)
        ref = sse(w[sl], *solve(w[sl]))
        got = sse(w[sl], codes[sl], scales[sl])
        out[sl] = (got - ref) / np.maximum(ref, 1e-300)
    return out
