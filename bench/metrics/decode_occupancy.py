"""Scheduler: tokens the engine decoded in the window over (decode steps it
dispatched in the window x slots), in percent.  Both are the engine's own
counters (``stats["decode_tokens"]``, ``stats["ticks"]``: one tick is one
decode step), read as the window opens and closes."""


def read(ctx):
    w = ctx.window
    steps = w.stats_close["ticks"] - w.stats_open["ticks"]
    if not steps:
        return None
    tokens = w.stats_close["decode_tokens"] - w.stats_open["decode_tokens"]
    return 100.0 * tokens / (steps * ctx.engine["slots"])
