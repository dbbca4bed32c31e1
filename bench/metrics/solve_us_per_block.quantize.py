"""Device time of the grouping solver's programs (``jit__solve_chunked``,
``core/grouping.py`` ``solve_blocks``) over the 64-weight blocks of the
matrices quantized inside the traced window."""
UNIT, SOURCE, LAYER, MOVES = ("us", "device_trace",
                              "quantizer (core/grouping.py)",
                              "quantize_weights_per_s")
MODULES = ("jit__solve_chunked",)


def read(ctx):
    blocks = ctx.counters.get("blocks_in_trace", 0)
    if ctx.trace is None or not blocks:
        return None
    runs = ctx.modules(*MODULES)
    if not runs:
        return None
    return sum(e.dur for e in runs) * 1e-3 / blocks
