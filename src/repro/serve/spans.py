"""Host spans of the serving loop, on the profiler's clock.

Every boundary of a decode tick is a ``jax.profiler.TraceAnnotation``
named ``serve.<name>``: with a profiler running the spans land on the same
clock as the device's ops, so an idle gap on the chip can be put down to
what the host was doing; with none running a span costs one TraceMe check.
Nesting (docs/observability.md, "Host spans and scopes"):

    serve.tick                  one iteration of the front door's tick loop
      serve.step                ServeEngine.step
        serve.drain             the drain the step triggers
          serve.device_step     one np.asarray sync of a pending tick
        serve.schedule          preemption, admission, prefill chunks
        serve.dispatch          bucket slice + the decode call
      serve.drain               the front door's drain(keep=1)
      serve.reap
    serve.call.<program>        every call of an engine program
    serve.gc                    a collection of Python's oldest generation

The tick phases of ``serve_tick_phase_seconds`` are these same spans:
with telemetry on, `Spans` observes a phase's self time (its duration less
the phases nested inside it) under its name.
"""
from __future__ import annotations

import contextlib
import gc
import time
from typing import Dict, Iterator, Optional

import jax

__all__ = ["Spans", "install_gc_span", "remove_gc_span"]


class Spans:
    """``spans(name)`` opens ``serve.<name>``; a name in `phases` (phase ->
    histogram child, from telemetry) is also timed into it."""

    def __init__(self, phases: Optional[Dict[str, object]] = None):
        self._phases = phases or {}
        self._nested = []     # per open timed phase: its timed children's s

    def __call__(self, name: str):
        hist = self._phases.get(name)
        if hist is None:
            return jax.profiler.TraceAnnotation("serve." + name)
        return self._timed(name, hist)

    @contextlib.contextmanager
    def _timed(self, name: str, hist) -> Iterator[None]:
        with jax.profiler.TraceAnnotation("serve." + name):
            self._nested.append(0.0)
            t0 = time.perf_counter()
            try:
                yield
            finally:
                dt = time.perf_counter() - t0
                inner = self._nested.pop()
                if self._nested:
                    self._nested[-1] += dt
                hist.observe(dt - inner)


_gc_users = 0
_gc_open: Optional[jax.profiler.TraceAnnotation] = None


def _on_gc(phase: str, info: dict) -> None:
    global _gc_open
    if info.get("generation") != 2:
        return
    if phase == "start":
        _gc_open = jax.profiler.TraceAnnotation("serve.gc")
        _gc_open.__enter__()
    elif _gc_open is not None:
        _gc_open.__exit__(None, None, None)
        _gc_open = None


def install_gc_span() -> None:
    """Span every full collection while a front door runs (counted, so two
    doors in one process share one callback)."""
    global _gc_users
    if _gc_users == 0:
        gc.callbacks.append(_on_gc)
    _gc_users += 1


def remove_gc_span() -> None:
    global _gc_users
    _gc_users -= 1
    if _gc_users == 0:
        gc.callbacks.remove(_on_gc)
