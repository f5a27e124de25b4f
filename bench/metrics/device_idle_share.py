"""Device: the share of the traced window in which no operation ran on the
chip (1 - union of op intervals / window), averaged over the chips used, in
percent."""


def read(ctx):
    if not ctx.window_s:
        return None
    return 100.0 * (1.0 - ctx.busy_s / ctx.window_s)
