"""Scheduler: 90th percentile of the wait from submit to admission (the
engine's lifecycle events), over requests due in the window.  A request never
admitted waits until the run stopped waiting for it."""
import numpy as np


def read(ctx):
    submit, admit = {}, {}
    for ev in ctx.events:
        if ev["event"] == "submit":
            submit.setdefault(ev["rid"], ev["ts"])
        elif ev["event"] == "admit":
            admit.setdefault(ev["rid"], ev["ts"])
    waits = [(admit.get(c.rid, ctx.window.done_at) - submit[c.rid]) * 1e3
             for c in ctx.due_in_window if c.rid in submit]
    return float(np.percentile(waits, 90)) if waits else None
