"""Model step: decode steps the engine dispatched in the window with every
active slot greedy, over all the decode steps it dispatched in the window, in
percent.  Both are the engine's own counters (``stats["greedy_ticks"]``,
``stats["ticks"]``), read as the window opens and closes.  The host counts a
step greedy when every slot it dispatched is, so the share is a lower bound
on the steps whose sampler took only the argmax.  A program without the
counter reads None."""


def read(ctx):
    w = ctx.window
    if "greedy_ticks" not in w.stats_close:
        return None
    steps = w.stats_close["ticks"] - w.stats_open["ticks"]
    if not steps:
        return None
    greedy = w.stats_close["greedy_ticks"] - w.stats_open["greedy_ticks"]
    return 100.0 * greedy / steps
