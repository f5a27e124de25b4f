"""Device: the share of the traced window in which the chip was idle (no
operation ran, as for ``device_idle_share``) while the host was inside a
front-door tick (``serve.tick``): the idle time the serving loop leaves on
the chip by running host work and device work one after the other,
averaged over the chips used, in percent.  The part of it inside the
tick's device syncs (``serve.device_step``) is printed beside it on the
``trace:`` line as ``idle_in_device_step_s``."""
import serve_trace


def read(ctx):
    if not serve_trace.expected():
        return None
    st = serve_trace.of(ctx)
    lo, hi = st.window
    idle = [serve_trace.idle_in(d["ops"], st.spans, "serve.tick", lo, hi)
            for d in st.devs]
    return 100.0 * sum(idle) / len(idle) / (hi - lo)
