"""Share of the traced window in which no operation ran on the device:
1 - (union of the XLA operation intervals) / window."""
UNIT, SOURCE, LAYER, MOVES = ("%", "device_trace", "device (TPU v5e)",
                              "output_tokens_per_s")


def read(ctx):
    if ctx.trace is None or ctx.trace_seconds <= 0:
        return None
    return 100.0 * (1.0 - ctx.busy_seconds() / ctx.trace_seconds)
