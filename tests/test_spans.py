"""Host spans and device scopes of the serving loop (serve/spans.py).

The contracts under test: a front door served under the profiler writes
every ``serve.*`` span with the documented nesting, one ``serve.tick`` per
loop iteration and no span per token; the engine's programs carry their
named scopes in the lowered program's metadata; full collections are
spanned only while a front door runs; and the tick-phase histogram is fed
by the same spans, each phase observed at its self time."""
import asyncio
import gc
import glob
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs.archs import get_config
from repro.models import lm
from repro.serve import spans as spans_lib
from repro.serve.engine import EngineConfig, ServeEngine
from repro.serve.frontdoor import FrontDoor


@pytest.fixture(scope="module")
def small_lm():
    cfg = get_config("llama3.2-3b", smoke=True)
    params, _ = lm.init_lm(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    return cfg, params


def _engine(small_lm, **kw):
    cfg, params = small_lm
    return ServeEngine(cfg, params, EngineConfig(
        slots=4, max_seq=64, page_size=8, eos_id=-1, **kw))


async def _serve(engine, prompts, max_new_tokens):
    door = FrontDoor(engine)
    door.start()
    streams = [await door.submit(p, max_new_tokens=max_new_tokens)
               for p in prompts]
    outs = [await s.drain() for s in streams]
    await door.stop()
    return outs


def _host_spans(trace_dir):
    """(name, start, end) of every serve.* event in the profile, by start."""
    from jax.profiler import ProfileData
    path = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("serve."):
                    out.append((e.name, float(e.start_ns),
                                float(e.start_ns + e.duration_ns)))
    return sorted(out, key=lambda s: s[1])


def _parent(spans, child):
    """The innermost span enclosing `child` (None at the top)."""
    name, s, e = child
    around = [p for p in spans if p is not child and p[1] <= s and e <= p[2]
              and (p[1], p[2]) != (s, e)]
    return min(around, key=lambda p: p[2] - p[1]) if around else None


def test_front_door_spans_nest_once_per_tick(small_lm, tmp_path, rng):
    cfg, _ = small_lm
    engine = _engine(small_lm)
    engine.warmup()
    prompts = [rng.integers(2, cfg.vocab_size, size=n) for n in (5, 9, 40)]
    steps = []
    step = engine.step
    engine.step = lambda: steps.append(1) or step()
    with jax.profiler.trace(str(tmp_path)):
        outs = asyncio.run(_serve(engine, prompts, max_new_tokens=12))
    engine.close()
    tokens = sum(len(o) for o in outs)
    assert tokens == 36

    spans = _host_spans(tmp_path)
    count = {}
    for name, _, _ in spans:
        count[name] = count.get(name, 0) + 1
    # one tick per loop iteration, each with its step and reap; the decode
    # program is called once per tick that advanced a slot
    assert count["serve.tick"] == count["serve.step"] == len(steps)
    assert count["serve.reap"] == len(steps)
    assert count["serve.dispatch"] == count["serve.call.decode"]
    assert count["serve.call.prefill_chunk"] >= 3     # 40 tokens: 2 chunks
    assert count["serve.device_step"] == count["serve.call.decode"]
    # no span per token: every name is per tick (or per chunk)
    assert tokens > len(steps)
    assert max(n for k, n in count.items()
               if k != "serve.call.prefill_chunk") <= len(steps)

    allowed = {
        "serve.step": {"serve.tick"},
        "serve.reap": {"serve.tick"},
        # the door's last drain, after its loop, lies outside any tick
        "serve.drain": {"serve.tick", "serve.step", None},
        "serve.schedule": {"serve.step"},
        "serve.dispatch": {"serve.step"},
        "serve.device_step": {"serve.drain"},
        "serve.call.decode": {"serve.dispatch"},
        "serve.call.prefill_chunk": {"serve.schedule"},
    }
    for sp in spans:
        parent = _parent(spans, sp)
        if sp[0] == "serve.tick":
            assert parent is None
        elif sp[0] in allowed:
            assert (parent and parent[0]) in allowed[sp[0]], sp
    # every serve.* name the loop wrote is one of the documented ones
    assert set(count) <= set(allowed) | {"serve.tick", "serve.gc"}


def test_decode_program_carries_its_scopes(small_lm):
    engine = _engine(small_lm)
    bt = engine.block_table[:, :engine.decode_buckets[0]]
    text = engine._decode._jit.lower(
        engine.params, engine.caches, engine._state, bt, engine._sp_packed,
        engine._key).as_text(debug_info=True)
    engine.close()
    # each op's location is named by its scope path, "jit(counted)/decode/.."
    names = set()
    for path in re.findall(r'loc\("([^"]*)"', text):
        names.update(path.split("/"))
    assert {"decode", "sample", "attention", "mlp", "head"} <= names


def test_collections_are_spanned_only_while_a_door_runs(small_lm):
    engine = _engine(small_lm)
    assert spans_lib._on_gc not in gc.callbacks

    async def main():
        door = FrontDoor(engine)
        door.start()
        await asyncio.sleep(0)
        inside = spans_lib._on_gc in gc.callbacks
        gc.collect()
        await door.stop()
        return inside
    assert asyncio.run(main())
    engine.close()
    assert spans_lib._on_gc not in gc.callbacks


class _Hist:
    def __init__(self):
        self.seen = []

    def observe(self, v):
        self.seen.append(v)


def test_a_phase_observes_its_self_time():
    hists = {"drain": _Hist(), "device_step": _Hist()}
    span = spans_lib.Spans(hists)
    with span("drain"):
        with span("device_step"):
            s = sum(range(200000))
        with span("tick"):                 # untimed: not subtracted
            pass
    assert s > 0
    (drain,), (sync,) = hists["drain"].seen, hists["device_step"].seen
    assert drain >= 0 and sync > 0
    # the drain's own time excludes the sync nested inside it
    assert drain < sync


def test_telemetry_off_spans_without_observing(small_lm):
    engine = _engine(small_lm, telemetry=False)
    assert isinstance(engine.span("schedule"), jax.profiler.TraceAnnotation)
    engine.close()
