"""Kernels: the paged_attention kernel inside the decode program.  Work is
counted from the traffic: for every token decoded in the window, attention
over its live context and that context's keys and values at the pool's
precision.  Time is the kernel's device time in decode programs in the
traced window.  Least time at the chip's peaks over that time, in percent."""
import work


def read(ctx):
    secs = ctx.kernel_seconds("paged_attention", "decode")
    if not secs or not ctx.decoded:
        return None
    e = ctx.engine
    ops = nbytes = 0.0
    for n in ctx.decoded:
        o, b = work.paged_decode(ctx.model, n, e["kv_bits"], e["page_size"])
        ops, nbytes = ops + o, nbytes + b
    return 100.0 * work.roofline_share(ops, nbytes, secs, ctx.peak)
