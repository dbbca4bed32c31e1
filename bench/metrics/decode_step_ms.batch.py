"""Device time of one decode step of a full batch, as the decode-horizon
dispatches (``jit__paged_horizon_step``) retired it: their mean device time
over the steps each retired, a step being one token for each of the
engine's ``max_batch`` rows. The tokens and dispatches are the engine's
own counts (``stats()`` ``tokens_out`` less the first tokens that prefill
samples, and ``decode_steps``) over the traced stretch, so rows that retire
fewer than ``decode_horizon`` tokens in a dispatch raise it."""
UNIT, SOURCE, LAYER, MOVES = ("ms", "device_trace",
                              "model step (models/model.py)",
                              "output_tokens_per_s")
MODULES = ("jit__paged_horizon_step",)


def read(ctx):
    tokens = ctx.counters.get("trace_decode_tokens", 0)
    dispatches = ctx.counters.get("trace_decode_dispatches", 0)
    if ctx.trace is None or tokens <= 0 or dispatches <= 0:
        return None
    runs = ctx.modules(*MODULES)
    if not runs:
        return None
    steps_per_dispatch = tokens / dispatches / ctx.engine["max_batch"]
    return sum(e.dur for e in runs) / len(runs) / steps_per_dispatch * 1e-6
