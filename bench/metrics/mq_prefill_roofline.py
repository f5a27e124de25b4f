"""Kernels: the paged_attention kernel in its multi-query (prefill chunk)
mode, inside the chunked-prefill program.  Work is counted from the prompt
chunks prefilled in the window (causal attention of each real prompt
position, the keys and values up to the chunk's end).  Least time at the
chip's peaks over the kernel's device time, in percent."""
import work


def read(ctx):
    secs = ctx.kernel_seconds("paged_attention", "prefill_chunk")
    if not secs or not ctx.chunks:
        return None
    e = ctx.engine
    ops = nbytes = 0.0
    for p0, n in ctx.chunks:
        o, b = work.mq_prefill(ctx.model, p0, n, e["kv_bits"], e["page_size"])
        ops, nbytes = ops + o, nbytes + b
    return 100.0 * work.roofline_share(ops, nbytes, secs, ctx.peak)
