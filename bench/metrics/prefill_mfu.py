"""Model step: model operations of the prompt tokens prefilled in the window
(projections and causal attention, no head) over the traced window x chips
x the bf16 peak, in percent."""
import work


def read(ctx):
    if not ctx.chunks:
        return None
    ops = sum(work.prefill_token_flops(ctx.model, p)
              for p0, n in ctx.chunks for p in range(p0, p0 + n))
    return 100.0 * ops / (ctx.window_s * ctx.chips * ctx.peak["bf16_flops"])
