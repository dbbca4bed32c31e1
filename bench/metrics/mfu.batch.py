"""The whole step's share of the chip's bf16 peak: model operations of
every token processed (prompt and output; the projections, the
unembedding, and attention at the live context; ``bench/flops.py``) over
whole dispatch periods of the traced stretch, from the end of its first
burst of streamed tokens to the end of its last, over those seconds times
the device kind's peak."""
UNIT, SOURCE, LAYER, MOVES = ("%", "host_clock",
                              "model step (models/model.py)",
                              "output_tokens_per_s")


def read(ctx):
    flops = ctx.counters.get("burst_flops")
    seconds = ctx.counters.get("burst_seconds", 0)
    if ctx.trace is None or not flops or seconds <= 0:
        return None
    return 100.0 * flops / (seconds * ctx.peaks["bf16_flops"])
