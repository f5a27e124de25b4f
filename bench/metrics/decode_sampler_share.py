"""Model step: the sampler's share of the decode program's device time.
Over the ops of the traced window that ran in a program the engine compiled
for ``decode`` (named scope ``decode``), the union of the intervals of
those scoped ``sample`` over the union of all of them, averaged over the
chips used, in percent.  Scopes are read from the HLO the profile carries
(``bench/op_scopes.py``); the decode step's time by scope is printed on a
``trace: decode_scopes=`` line."""
import json

import op_scopes
import serve_trace
import trace_reduce as tr


def read(ctx):
    if not serve_trace.expected():
        return None
    st = serve_trace.of(ctx)
    if getattr(st, "programs", None) is None:
        st.programs = op_scopes.Programs(op_scopes.read_protos(st.trace_dir))
    lo, hi = st.window
    shares = []
    for i, d in enumerate(st.devs):
        ops = op_scopes.step_ops(d, st.programs, "decode", lo, hi)
        sample = [(o.start, o.end) for o, n in ops
                  if op_scopes.in_scope(n, "sample")]
        if not sample:
            raise RuntimeError(f"no op of the traced window carries the "
                               f"scopes decode and sample ({len(ops)} ops "
                               f"carry decode)")
        every = tr.union(((o.start, o.end) for o, _ in ops), lo, hi)
        shares.append(serve_trace.length(tr.union(sample, lo, hi))
                      / serve_trace.length(every))
        if i == 0:
            split = op_scopes.self_seconds(ops, lo, hi)
            print(f"trace: decode_scopes={json.dumps(split, sort_keys=True)}"
                  f" decode_busy_s={serve_trace.length(every) * 1e-9!r}",
                  flush=True)
    return 100.0 * sum(shares) / len(shares)
