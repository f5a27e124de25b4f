"""The measured window: seeded traffic through FrontDoor, timed at the client.

One asyncio loop holds the front door's tick task, an open-loop generator
that submits each arrival at its due time whatever the server is doing, and
one consumer per request that stamps every token as it reaches the client.
Every request is timed from its due time, so a stalled generator or a slow
tick shows up as latency.  The pre-roll runs the same traffic before the
window opens; after the window closes nothing more is sent, and the run
waits (at most ``LATE_WAIT_S``) for the first token of every request that
was due inside the window: an answer that comes late is late, not missing.
"""
from __future__ import annotations

import asyncio
import dataclasses
import time
from typing import Callable, List, Optional

import numpy as np

LATE_WAIT_S = 60.0


@dataclasses.dataclass
class Client:
    due: float                 # perf_counter time the request was due
    prompt: np.ndarray
    max_new_tokens: int
    sent: float = 0.0
    rid: int = -1
    times: List[float] = dataclasses.field(default_factory=list)
    tokens: List[int] = dataclasses.field(default_factory=list)
    finish: Optional[str] = None


@dataclasses.dataclass
class Window:
    start: float               # perf_counter at the window's opening
    end: float
    clients: List[Client]
    compiles_in_window: int
    done_at: float             # when the late wait ended
    # the engine's counters (engine.stats) at the window's opening and close
    stats_open: dict = dataclasses.field(default_factory=dict)
    stats_close: dict = dataclasses.field(default_factory=dict)


async def _sleep_until(t: float) -> None:
    delay = t - time.perf_counter()
    if delay > 0:
        await asyncio.sleep(delay)


async def _drive(engine, arrivals, preroll_s: float, seconds: float,
                 on_open: Callable[[], None],
                 on_close: Callable[[], None]) -> Window:
    from repro.serve.frontdoor import FrontDoor
    from repro.serve.sampling import SamplingParams

    door = FrontDoor(engine)
    door.start()
    t0 = time.perf_counter()
    w0, w1 = t0 + preroll_s, t0 + preroll_s + seconds
    clients: List[Client] = []
    consumers: List[asyncio.Task] = []

    async def consume(c: Client, stream) -> None:
        async for tok in stream:
            c.times.append(time.perf_counter())
            c.tokens.append(tok)
        c.finish = stream.finish_reason

    async def generate() -> None:
        for a in arrivals:
            due = t0 + a.due_s
            if due >= w1:
                return
            await _sleep_until(due)
            c = Client(due=due, prompt=a.prompt,
                       max_new_tokens=a.max_new_tokens)
            stream = await door.submit(a.prompt, a.max_new_tokens,
                                       SamplingParams())
            c.sent, c.rid = time.perf_counter(), stream.rid
            clients.append(c)
            consumers.append(asyncio.create_task(consume(c, stream)))

    gen = asyncio.create_task(generate())
    try:
        await _sleep_until(w0)
        compiles0 = engine.compile_count()
        stats_open = dict(engine.stats)
        on_open()
        await _sleep_until(w1)
        on_close()
        stats_close = dict(engine.stats)
        compiles = engine.compile_count() - compiles0
        await gen
        due_in = [c for c in clients if c.due >= w0]
        limit = time.perf_counter() + LATE_WAIT_S
        while (any(not c.times and c.finish is None for c in due_in)
               and time.perf_counter() < limit):
            await asyncio.sleep(0.05)
        done_at = time.perf_counter()
    finally:
        gen.cancel()
        for t in consumers:
            t.cancel()
        await asyncio.gather(gen, *consumers, return_exceptions=True)
        await door.stop()
    return Window(w0, w1, clients, compiles, done_at, stats_open,
                  stats_close)


def run(engine, arrivals, preroll_s: float, seconds: float,
        on_open: Callable[[], None] = lambda: None,
        on_close: Callable[[], None] = lambda: None) -> Window:
    return asyncio.run(_drive(engine, arrivals, preroll_s, seconds, on_open,
                              on_close))


# --- end-to-end readings ---------------------------------------------------

def _pct(values, q) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


def due_in_window(w: Window) -> List[Client]:
    return [c for c in w.clients if w.start <= c.due < w.end]


def ttft_ms(w: Window) -> List[float]:
    """Due time to first token of every request due in the window; one that
    never answered counts as answering when the late wait ended."""
    return [((c.times[0] if c.times else w.done_at) - c.due) * 1e3
            for c in due_in_window(w)]


def itl_ms(w: Window) -> List[float]:
    """Every gap between two tokens of one request, both inside the window."""
    out: List[float] = []
    for c in w.clients:
        t = np.asarray(c.times)
        t = t[(t >= w.start) & (t < w.end)]
        out.extend((np.diff(t) * 1e3).tolist())
    return out


def tokens_in_window(w: Window) -> int:
    return sum(int(np.count_nonzero((np.asarray(c.times) >= w.start)
                                    & (np.asarray(c.times) < w.end)))
               for c in w.clients)


def end_to_end(w: Window) -> dict:
    secs = w.end - w.start
    ttft, itl = ttft_ms(w), itl_ms(w)
    return {
        "output_tok_s": tokens_in_window(w) / secs,
        "ttft_p90_ms": _pct(ttft, 90) if ttft else None,
        "itl_p95_ms": _pct(itl, 95) if itl else None,
    }
