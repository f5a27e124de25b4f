"""From a profiler trace to device busy time, kernel time per program, and
the host spans under each idle gap.

The JAX profiler writes ``<dir>/plugins/profile/<run>/<host>.xplane.pb``;
``jax.profiler.ProfileData`` reads it.  A TPU chip is a plane named
``/device:TPU:<n>``; its ``XLA Ops`` line holds one event per operation that
ran (a Pallas kernel is an operation named after its ``pallas_call`` name)
and its ``XLA Modules`` line one event per program execution.  The host's
planes hold the benchmark's own ``TraceAnnotation`` spans on the same clock.

A module event is named ``jit_<function>(<program id>)``, one name per
compiled executable (seen on a v5e, JAX 0.9).  The engine jits every step
through one wrapper function, so its programs differ only in the id; the
harness therefore replays the engine's warm-up under a span of its own and
maps each engine program it sees there, in dispatch order, to the step it
belongs to (``program_map``).  Eager helpers (key folds, converts) run as
modules of other names and are skipped.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"

Interval = Tuple[float, float]


def load(trace_dir: str):
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return ProfileData.from_file(paths[-1])


class Event:
    __slots__ = ("name", "start", "end", "stats")

    def __init__(self, name, start, end, stats):
        self.name, self.start, self.end, self.stats = name, start, end, stats


def _events(line) -> List[Event]:
    out = []
    for e in line.events:
        s = float(e.start_ns)
        out.append(Event(e.name, s, s + float(e.duration_ns),
                         dict(e.stats)))
    return out


def device_lines(pd) -> List[Dict[str, List[Event]]]:
    """Per TPU plane: {"ops": [...], "modules": [...]} in start order."""
    out = []
    for plane in pd.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        lines = {line.name: line for line in plane.lines}
        missing = [n for n in (OPS_LINE, MODULES_LINE) if n not in lines]
        if missing:
            raise ValueError(f"{plane.name} has no line {missing}; its lines "
                             f"are {sorted(lines)}")
        d = {"ops": _events(lines[OPS_LINE]),
             "modules": _events(lines[MODULES_LINE])}
        for v in d.values():
            v.sort(key=lambda e: e.start)
        out.append(d)
    return out


def host_spans(pd, prefix: str = "bench.") -> List[Event]:
    """The benchmark's own TraceAnnotation spans, from every host plane."""
    out = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            out.extend(e for e in _events(line) if e.name.startswith(prefix))
    return sorted(out, key=lambda e: e.start)


def span(spans: List[Event], name: str) -> Interval:
    hits = [e for e in spans if e.name == name]
    if not hits:
        raise ValueError(f"no span {name!r} in the trace")
    return hits[-1].start, hits[-1].end


def union(intervals: Iterable[Interval], lo: float,
          hi: float) -> List[Interval]:
    """Merged intervals clipped to [lo, hi]."""
    merged: List[Interval] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1] = (merged[-1][0], max(merged[-1][1], e))
        else:
            merged.append((s, e))
    return merged


def busy_ns(ops: List[Event], lo: float, hi: float) -> float:
    return sum(e - s for s, e in union(((o.start, o.end) for o in ops), lo,
                                       hi))


def idle_gaps(ops: List[Event], lo: float, hi: float) -> List[Interval]:
    busy = union(((o.start, o.end) for o in ops), lo, hi)
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def op_name(op: Event) -> str:
    """An op event is named by its HLO instruction's text,
    ``%paged_attention.11 = f32[...] custom-call(...)``: its short name is
    the instruction's, ``paged_attention.11``."""
    return op.name.split(" = ", 1)[0].lstrip("%")


def program_map(modules: List[Event], replay: Interval, order: List[str],
                family: str) -> Dict[str, str]:
    """Module name -> engine step, from the modules of `family` (the jitted
    function's name, ``jit_<name>(<program id>)``) that ran during the
    replayed warm-up, which dispatches the steps in `order`, each once.  A
    module that ran more than once there cannot be told apart and is left
    out; other families are eager helpers."""
    ran = [m for m in modules if replay[0] <= m.start < replay[1]
           and m.name.startswith(family + "(")]
    names = [m.name for m in ran]
    seen = [m for m in ran if names.count(m.name) == 1]
    if len(seen) != len(order):
        raise ValueError(f"replay ran {len(seen)} distinct programs, "
                         f"expected {len(order)}")
    return {m.name: step for m, step in zip(seen, order)}


def op_program(ops: List[Event], modules: List[Event]) -> List[Optional[str]]:
    """For each op, the name of the module execution enclosing its start."""
    out, j = [], 0
    for o in ops:
        while j < len(modules) and modules[j].end < o.start:
            j += 1
        m = modules[j] if j < len(modules) else None
        out.append(m.name if m is not None and m.start <= o.start else None)
    return out


def is_kernel(op: Event, kernel: str) -> bool:
    """A Pallas kernel's op is the custom call named after its
    ``pallas_call`` name (``paged_attention.11``)."""
    name = op_name(op)
    return name == kernel or name.startswith(kernel + ".")


def kernel_seconds(dev: Dict[str, List[Event]], kernel: str,
                   programs: Dict[str, str], step: str, lo: float,
                   hi: float) -> float:
    """Device seconds of `kernel` ops inside executions of engine `step`
    within [lo, hi]."""
    owner = op_program(dev["ops"], dev["modules"])
    tot = 0.0
    for o, m in zip(dev["ops"], owner):
        if (lo <= o.start < hi and m is not None and programs.get(m) == step
                and is_kernel(o, kernel)):
            tot += o.end - o.start
    return tot * 1e-9


def top_ops(dev: Dict[str, List[Event]], lo: float, hi: float,
            n: int = 10) -> List[list]:
    tot: Dict[str, float] = {}
    for o in dev["ops"]:
        if lo <= o.start < hi:
            k = op_name(o)
            tot[k] = tot.get(k, 0.0) + (o.end - o.start) * 1e-9
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def top_gaps(dev: Dict[str, List[Event]], spans: List[Event], lo: float,
             hi: float, n: int = 10) -> List[list]:
    """The longest idle gaps, each named by the innermost benchmark span
    open at its midpoint (what the host was doing)."""
    out = []
    for s, e in sorted(idle_gaps(dev["ops"], lo, hi),
                       key=lambda g: g[0] - g[1])[:n]:
        mid = (s + e) / 2
        inside = [x for x in spans if x.start <= mid < x.end
                  and x.name != "bench.window"]
        name = (min(inside, key=lambda x: x.end - x.start).name
                if inside else "host: outside the engine's calls")
        out.append([name, (e - s) * 1e-9])
    return out
