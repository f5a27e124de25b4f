"""The serving program's own host spans in a traced run.

The engine and its front door open a host span named ``serve.<name>`` at
every boundary of a decode tick (``serve.tick``, ``serve.step``,
``serve.drain``, ``serve.device_step``, ``serve.schedule``,
``serve.dispatch``, ``serve.reap``, ``serve.call.<program>``, ``serve.gc``;
``src/repro/serve/spans.py``), on the profiler's clock.

The per-layer readers ``tick_host_ms``, ``idle_in_host_share`` and
``decode_sampler_share`` get them through ``of(ctx)``: ``ctx.serve_trace``
where the harness gives one, else the profile of the cell traced last under
``.bench_out/trace`` (the run's own, written just before the readers run),
loaded once per run.  A program without ``repro.serve.spans`` writes no
``serve.*`` span, and the readers return None for it; a program with it
whose trace lacks the spans fails the run.
"""
from __future__ import annotations

import bisect
import importlib.util
import json
import os
import types
from typing import Dict, List, Sequence, Tuple

import trace_reduce as tr

# TODO: once run.py may change, load the serve.* spans there as
# ctx.serve_trace, from the run's own trace_dir, and print the idle split
# there (PERF.md section 7); this second load of the profile then goes.
TRACE_ROOT = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), ".bench_out", "trace")
PREFIX = "serve."
# idle time while no serve.* span is open: the event loop's other tasks
# (the streams' consumers, the traffic generator) or no host work at all
OUTSIDE = "outside serve.tick"

Interval = Tuple[float, float]


def expected() -> bool:
    """Whether the program under test writes the serve.* spans and the
    named scopes (it has ``repro.serve.spans``)."""
    return importlib.util.find_spec("repro.serve.spans") is not None


def newest_dir(root: str = TRACE_ROOT) -> str:
    """The cell directory under `root` written last."""
    dirs = [os.path.join(root, d) for d in os.listdir(root)]
    return max(dirs, key=os.path.getmtime)


def load(pd, chips: int) -> types.SimpleNamespace:
    """The serve.* spans, the window (``bench.window``) on the trace's clock
    and the first `chips` chips' op and module events of one profile."""
    return types.SimpleNamespace(
        spans=tr.host_spans(pd, PREFIX),
        window=tr.span(tr.host_spans(pd), "bench.window"),
        devs=tr.device_lines(pd)[:chips])


def require(st) -> None:
    """A window in which the chip ran ops holds the front door's ticks and
    the engine's decode calls, or the spans were renamed or lost."""
    lo, hi = st.window
    if not any(lo <= o.start < hi for d in st.devs for o in d["ops"]):
        return
    for name in ("serve.tick", "serve.call.decode"):
        if not named(st.spans, name, lo, hi):
            raise RuntimeError(f"the traced window holds device ops but no "
                               f"{name!r} span")


def of(ctx) -> types.SimpleNamespace:
    st = getattr(ctx, "serve_trace", None)
    if st is None:
        trace_dir = newest_dir()
        st = load(tr.load(trace_dir), ctx.chips)
        st.trace_dir = trace_dir
        require(st)
        ctx.serve_trace = st
        print(f"trace: {summary(st)}", flush=True)
    return st


def named(spans: Sequence[tr.Event], name: str, lo: float,
          hi: float) -> List[tr.Event]:
    """Spans called `name` that start inside [lo, hi)."""
    return [s for s in spans if s.name == name and lo <= s.start < hi]


# --- intervals -------------------------------------------------------------

def intersect(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Both sorted lists of disjoint intervals; their common part."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def length(ivs: Sequence[Interval]) -> float:
    return sum(e - s for s, e in ivs)


# --- host spans --------------------------------------------------------------

def innermost(spans: Sequence[tr.Event], lo: float,
              hi: float) -> List[Tuple[float, float, str]]:
    """[lo, hi) cut where a span opens or closes, each piece named by the
    innermost span open over it (the latest opened), or OUTSIDE.  A span of
    no length covers nothing (its close would sort before its open)."""
    spans = [s for s in spans if s.end > s.start]
    marks = sorted([(s.end, 0, i) for i, s in enumerate(spans)]
                   + [(s.start, 1, i) for i, s in enumerate(spans)])
    open_, out, t = set(), [], lo

    def name():
        if not open_:
            return OUTSIDE
        k = max(open_, key=lambda i: (spans[i].start, -spans[i].end))
        return spans[k].name
    for x, opens, i in marks:
        x = min(max(x, lo), hi)
        if x > t:
            out.append((t, x, name()))
            t = x
        if opens:
            open_.add(i)
        else:
            open_.discard(i)
    if hi > t:
        out.append((t, hi, name()))
    return out


def idle_by_span(ops: Sequence[tr.Event], spans: Sequence[tr.Event],
                 lo: float, hi: float) -> Dict[str, float]:
    """Device idle seconds in [lo, hi), split by the innermost serve.* span
    open at each idle instant; OUTSIDE where none is open.  The parts add up
    to ``trace_reduce.idle_gaps``' total."""
    pieces = innermost(spans, lo, hi)
    starts = [p[0] for p in pieces]
    out: Dict[str, float] = {}
    for s, e in tr.idle_gaps(list(ops), lo, hi):
        i = bisect.bisect_right(starts, s) - 1
        while i < len(pieces) and pieces[i][0] < e:
            a, b, n = pieces[i]
            d = min(b, e) - max(a, s)
            if d > 0:
                out[n] = out.get(n, 0.0) + d * 1e-9
            i += 1
    return out


def covered(spans: Sequence[tr.Event], name: str, lo: float,
            hi: float) -> List[Interval]:
    """The union of the `name` spans, clipped to [lo, hi]."""
    return tr.union(((s.start, s.end) for s in spans if s.name == name),
                    lo, hi)


def idle_in(ops: Sequence[tr.Event], spans: Sequence[tr.Event], name: str,
            lo: float, hi: float) -> float:
    """Device idle ns in [lo, hi) while a `name` span was open."""
    return length(intersect(tr.idle_gaps(list(ops), lo, hi),
                            covered(spans, name, lo, hi)))


def tick_host_ns(spans: Sequence[tr.Event], lo: float, hi: float) -> float:
    """Sum over the serve.tick spans starting in [lo, hi) of their length
    less that of their serve.device_step children."""
    syncs = sorted((s for s in spans if s.name == "serve.device_step"),
                   key=lambda s: s.start)
    starts = [s.start for s in syncs]
    tot = 0.0
    for t in named(spans, "serve.tick", lo, hi):
        i = bisect.bisect_left(starts, t.start)
        waited = 0.0
        while i < len(syncs) and syncs[i].start < t.end:
            waited += syncs[i].end - syncs[i].start
            i += 1
        tot += t.end - t.start - waited
    return tot


# --- the printed line ------------------------------------------------------

def summary(st, n: int = 5) -> str:
    """Idle seconds of the first chip by innermost span, their total, the
    part inside a tick and the part inside its syncs, and the `n` longest
    gaps, each split the same way."""
    lo, hi = st.window
    ops = st.devs[0]["ops"]
    split = idle_by_span(ops, st.spans, lo, hi)
    gaps = sorted(tr.idle_gaps(ops, lo, hi), key=lambda g: g[0] - g[1])[:n]
    longest = [[(e - s) * 1e-9, idle_by_span(ops, st.spans, s, e)]
               for s, e in gaps]
    tick, sync = (idle_in(ops, st.spans, name, lo, hi) * 1e-9
                  for name in ("serve.tick", "serve.device_step"))
    return (f"idle_by_span={json.dumps(split, sort_keys=True)} "
            f"idle_s={sum(split.values())!r} idle_in_tick_s={tick!r} "
            f"idle_in_device_step_s={sync!r} "
            f"longest_gaps={json.dumps(longest)}")
