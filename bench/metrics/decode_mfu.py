"""Model step: model operations of the tokens decoded in the window (every
projection, attention at each token's context, the head) over the traced
window x chips x the bf16 peak, in percent."""
import work


def read(ctx):
    if not ctx.decoded:
        return None
    ops = sum(work.decode_token_flops(ctx.model, n) for n in ctx.decoded)
    return 100.0 * ops / (ctx.window_s * ctx.chips * ctx.peak["bf16_flops"])
