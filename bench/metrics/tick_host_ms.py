"""Scheduler: host time of one decode tick.  Over the front door's tick
spans (``serve.tick``) that start in the traced window, their length less
the time they spent in the device syncs inside them
(``serve.device_step``), divided by the decode calls made in the window
(``serve.call.decode``), in ms."""
import serve_trace


def read(ctx):
    if not serve_trace.expected():
        return None
    st = serve_trace.of(ctx)
    lo, hi = st.window
    calls = len(serve_trace.named(st.spans, "serve.call.decode", lo, hi))
    return serve_trace.tick_host_ns(st.spans, lo, hi) * 1e-6 / calls
