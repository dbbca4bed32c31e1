"""The ``msb_matmul`` Pallas kernel's share of its roofline: the least time
its calls could take on this chip, over their summed device time.

Each call's least time is the larger of its operations over the bf16 peak
and its bytes over the HBM bandwidth, with operations and bytes computed
from the shapes the call was given (``trace.msb_call_cost``): 2 M K N, and
the 4-bit codes, codebooks and activations read once plus the result
written once. The bound that binds is printed."""
from bench import trace as tr
from bench.device import log

UNIT, SOURCE, LAYER, MOVES = ("%", "device_trace",
                              "kernels (kernels/msb_matmul)",
                              "output_tokens_per_s")
KERNEL = "msb_matmul"


def read(ctx):
    if ctx.trace is None:
        return None
    lo, hi = ctx.trace_window
    least, spent, bound_by = 0.0, 0.0, {"compute": 0.0, "memory": 0.0}
    for op in ctx.trace.ops:
        if tr.op_short(op.name) != KERNEL or not lo <= op.start < hi:
            continue
        t, bound = tr.roofline_seconds(*tr.msb_call_cost(op.name), ctx.peaks)
        least += t
        bound_by[bound] += t
        spent += op.dur * 1e-9
    if spent <= 0:
        return None
    log(f"{KERNEL}: least time {least:.6f} s of {spent:.6f} s on the device;"
        f" bound by memory {bound_by['memory']:.6f} s, compute "
        f"{bound_by['compute']:.6f} s")
    return 100.0 * least / spent
