"""Mean of ``stats()["running"]``, sampled every 10 ms in the window, over
the engine's ``max_batch``."""
UNIT, SOURCE, LAYER, MOVES = ("%", "program_counter",
                              "scheduler (serve/scheduler.py)",
                              "output_tokens_per_s")


def read(ctx):
    running = ctx.samples.get("running")
    if not running:
        return None
    return 100.0 * sum(running) / len(running) / ctx.engine["max_batch"]
