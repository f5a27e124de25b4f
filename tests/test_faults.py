"""Fault containment: the deterministic injection harness (serve/faults),
graceful degradation (deadlines, numeric quarantine, tick watchdog), the
invariant audit, health states on the front door and /healthz, and
exception-safe shutdown.

The contracts under test: a fault costs exactly its target request — a
structured retire reason, never a hang, never an unhandled exception, with
co-batched streams bit-identical to a fault-free run; ``engine.audit()``
reclaims injected pin/block leaks and reports exact cross-check mismatches;
the watchdog degrades on a slow step and auto-recovers; a DEGRADED engine
refuses new front-door submits (EngineUnhealthy, 503 on /healthz) while
in-flight streams keep draining; and ``close()`` is idempotent and
exception-safe. Every scenario is schedule-deterministic — FaultPlan
triggers on request id / tick / occurrence count, never wall clock."""
import asyncio
import itertools
import json
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.archs import get_config
from repro.models import lm
from repro.serve import faults as fl
from repro.serve.engine import (DEGRADED, DRAINING, HEALTHY, EngineConfig,
                                Request, ServeEngine)
from repro.serve.frontdoor import EngineUnhealthy, FrontDoor


@pytest.fixture(scope="module")
def small_lm():
    cfg = get_config("llama3.2-3b", smoke=True)
    params, _ = lm.init_lm(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    return cfg, params


def make_requests(cfg, n, max_new=4, seed=0, **kw):
    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    prompt=rng.integers(2, cfg.vocab_size,
                                        size=int(rng.integers(3, 12))),
                    max_new_tokens=max_new, **kw)
            for i in range(n)]


def finish_reasons(engine):
    return {rs.rid: rs.finish_reason for rs in engine.scheduler.finished}


def streams(engine):
    return {rs.rid: tuple(rs.out_tokens)
            for rs in engine.scheduler.finished}


# ---------------------------------------------------------------------------
# FaultPlan: schedule-deterministic triggering (pure host-side)
# ---------------------------------------------------------------------------

def test_fault_spec_selectors_and_consumption():
    plan = fl.FaultPlan()
    plan.arm("chunk_error", rid=3)
    assert plan.fire("chunk_error", rid=1, tick=0) is None
    assert plan.fire("nan_logits", rid=3, tick=0) is None   # wrong site
    spec = plan.fire("chunk_error", rid=3, tick=0)
    assert spec is not None and spec.fired == 1
    # once=True (default): consumed after the first fire
    assert plan.fire("chunk_error", rid=3, tick=1) is None
    assert plan.injected == {"chunk_error": 1}
    assert plan.log == [("chunk_error", 3, 0)]


def test_fault_spec_nth_skips_matches():
    plan = fl.FaultPlan()
    plan.arm("nan_logits", nth=2)
    assert plan.fire("nan_logits", rid=0, tick=0) is None
    assert plan.fire("nan_logits", rid=0, tick=1) is None
    assert plan.fire("nan_logits", rid=0, tick=2) is not None


def test_fault_spec_tick_selector_and_repeat():
    plan = fl.FaultPlan()
    plan.arm("slow_step", tick=5, once=False, delay_s=0.1)
    assert plan.fire("slow_step", tick=4) is None
    assert plan.fire("slow_step", tick=5) is not None
    assert plan.fire("slow_step", tick=5) is not None       # non-once
    assert plan.injected["slow_step"] == 2


def test_fault_none_context_is_wildcard():
    """A site with no request in scope (step_error fires before admission)
    passes rid=None — a targeted spec still fires there and its rid
    survives as payload on the fault, not as a failed selector."""
    plan = fl.FaultPlan()
    spec = plan.arm("step_error", rid=7)
    assert plan.fire("step_error", rid=None, tick=0) is spec


def test_fault_unknown_site_rejected():
    with pytest.raises(ValueError, match="unknown fault site"):
        fl.FaultPlan().arm("power_loss")


def test_seeded_plan_is_reproducible():
    a = fl.FaultPlan.seeded(42, rids=(0, 1, 2))
    b = fl.FaultPlan.seeded(42, rids=(0, 1, 2))
    assert ([(s.site, s.rid) for s in a.pending()]
            == [(s.site, s.rid) for s in b.pending()])
    c = fl.FaultPlan.seeded(43, rids=(0, 1, 2))
    assert ([(s.site, s.rid) for s in a.pending()]
            != [(s.site, s.rid) for s in c.pending()])


# ---------------------------------------------------------------------------
# Deadlines: wall-clock budget enforced at tick boundaries
# ---------------------------------------------------------------------------

def test_deadline_expires_while_waiting(small_lm):
    """An expired deadline retires a still-queued request with reason
    "deadline" — the slot-less retire path (same accounting as a queued
    cancel)."""
    cfg, params = small_lm
    engine = ServeEngine(cfg, params,
                         EngineConfig(slots=1, max_seq=64, page_size=8))
    blocker = Request(rid=0, prompt=np.array([5, 6, 7], np.int32),
                      max_new_tokens=8)
    doomed = Request(rid=1, prompt=np.array([8, 9, 10], np.int32),
                     max_new_tokens=8, deadline_ms=0.001)
    engine.submit(blocker)
    engine.submit(doomed)                  # queued behind the only slot
    done = engine.run([], max_ticks=100)
    fin = finish_reasons(engine)
    assert fin[1] == "deadline"
    assert fin[0] == "max_tokens"          # the blocker is untouched
    assert doomed.out_tokens == []
    assert {r.rid for r in done} == {0, 1}
    assert engine.allocator.free_blocks == engine.allocator.num_blocks - 1


def test_deadline_expires_mid_decode(small_lm):
    """A decoding request past its budget retires at the next tick
    boundary, keeping the tokens it already generated and freeing its
    blocks like cancel()."""
    cfg, params = small_lm
    engine = ServeEngine(cfg, params,
                         EngineConfig(slots=1, max_seq=128, page_size=8))
    req = Request(rid=0, prompt=np.array([5, 6, 7], np.int32),
                  max_new_tokens=96, deadline_ms=150.0)
    engine.submit(req)
    import time
    t0 = time.perf_counter()
    while not finish_reasons(engine) and time.perf_counter() - t0 < 30:
        engine.step()
        engine.poll()
    assert finish_reasons(engine)[0] == "deadline"
    assert 0 < len(req.out_tokens) < 96
    assert engine.allocator.free_blocks == engine.allocator.num_blocks - 1


def test_deadline_must_be_positive(small_lm):
    cfg, params = small_lm
    engine = ServeEngine(cfg, params, EngineConfig(slots=1, max_seq=64))
    with pytest.raises(ValueError, match="deadline_ms"):
        engine.submit(Request(rid=0, prompt=np.array([5], np.int32),
                              max_new_tokens=2, deadline_ms=0.0))


# ---------------------------------------------------------------------------
# Numeric quarantine: NaN/Inf logits cost one slot, not the batch
# ---------------------------------------------------------------------------

def test_injected_nan_quarantines_only_target(small_lm):
    """An injected nan_logits fault retires exactly its target with
    "numeric_error"; every co-batched stream is bit-identical to the
    fault-free run on the same workload."""
    cfg, params = small_lm
    out = {}
    for label in ("clean", "fault"):
        plan = None
        if label == "fault":
            plan = fl.FaultPlan()
            plan.arm("nan_logits", rid=1)
        engine = ServeEngine(cfg, params,
                             EngineConfig(slots=2, max_seq=64, page_size=8,
                                          faults=plan))
        engine.run(make_requests(cfg, 4, max_new=6))
        out[label] = (finish_reasons(engine), streams(engine))
    fin, toks = out["fault"]
    assert fin[1] == "numeric_error"
    clean_fin, clean_toks = out["clean"]
    for rid in (0, 2, 3):
        assert fin[rid] == clean_fin[rid]
        assert toks[rid] == clean_toks[rid]


def test_real_nan_in_pool_quarantines_slot(small_lm):
    """Not just the injected flag: genuinely NaN-poisoned KV storage makes
    the device-side finite check trip and the poisoned slot quarantine,
    while the co-batched slot keeps decoding bit-exactly."""
    cfg, params = small_lm
    ref = ServeEngine(cfg, params,
                      EngineConfig(slots=2, max_seq=64, page_size=8))
    ref_reqs = make_requests(cfg, 2, max_new=8, seed=5)
    ref.run(ref_reqs)
    ref_toks = streams(ref)

    engine = ServeEngine(cfg, params,
                         EngineConfig(slots=2, max_seq=64, page_size=8))
    reqs = make_requests(cfg, 2, max_new=8, seed=5)
    for r in reqs:
        engine.submit(r)
    # a couple of ticks so both requests are decoding
    for _ in range(3):
        engine.step()
    engine.drain()
    victim = engine.slot_req[0]
    assert victim is not None and victim.blocks
    blk = victim.blocks[0]
    engine.caches = jax.tree.map(
        lambda buf: (buf.at[:, blk].set(jnp.nan)
                     if jnp.issubdtype(buf.dtype, jnp.floating) else buf),
        engine.caches)
    done = engine.run([], max_ticks=200)
    fin = finish_reasons(engine)
    assert fin[victim.rid] == "numeric_error"
    other = ({0, 1} - {victim.rid}).pop()
    assert fin[other] in ("eos", "max_tokens")
    assert streams(engine)[other] == ref_toks[other]
    assert len(done) == 2
    # quarantine scrubbed + freed the poisoned request's blocks
    assert engine.allocator.free_blocks == engine.allocator.num_blocks - 1
    assert engine.audit()["leaked_after"] == 0


def test_quarantine_scrubs_poisoned_blocks_before_reuse(small_lm):
    """Blocks a quarantined request wrote are zeroed before returning to
    the allocator — a later request reusing the pool slot must never read
    residual NaN."""
    cfg, params = small_lm
    plan = fl.FaultPlan()
    plan.arm("nan_logits", rid=0)
    engine = ServeEngine(cfg, params,
                         EngineConfig(slots=1, max_seq=64, page_size=8,
                                      faults=plan))
    engine.run(make_requests(cfg, 1, max_new=4))
    assert finish_reasons(engine)[0] == "numeric_error"
    for leaf in jax.tree.leaves(engine.caches):
        if jnp.issubdtype(leaf.dtype, jnp.floating):
            assert bool(jnp.all(jnp.isfinite(leaf)))
    # and the pool still serves: a fresh request decodes normally
    nxt = make_requests(cfg, 1, max_new=4, seed=9)[0]
    nxt.rid = 5
    engine.run([nxt])
    assert finish_reasons(engine)[5] in ("eos", "max_tokens")
    assert all(np.isfinite(t) for t in nxt.out_tokens)


# ---------------------------------------------------------------------------
# Tick watchdog: slow-step degradation + auto-recovery
# ---------------------------------------------------------------------------

def test_watchdog_degrades_and_recovers(small_lm):
    """Driven directly with synthetic step times: a breach past
    watchdog_ticks x rolling p99 degrades; `watchdog_recovery` consecutive
    in-threshold steps recover; breaching samples never inflate the
    window."""
    cfg, params = small_lm
    engine = ServeEngine(cfg, params,
                         EngineConfig(slots=1, max_seq=64,
                                      watchdog_ticks=4.0,
                                      watchdog_floor_s=0.0,
                                      watchdog_recovery=3))
    for _ in range(engine._watchdog_arm):
        engine._watchdog(0.01)
    assert engine.health == HEALTHY
    engine._watchdog(10.0)
    assert engine.health == DEGRADED
    assert engine.health_reason == "watchdog"
    # breaching sample stayed out of the window: the threshold is unmoved
    assert max(engine._tick_window) <= 0.01
    for _ in range(2):
        engine._watchdog(0.01)
    assert engine.health == DEGRADED        # streak not yet complete
    engine._watchdog(0.01)
    assert engine.health == HEALTHY
    # a second breach re-degrades (recovery armed the trap again)
    engine._watchdog(10.0)
    assert engine.health == DEGRADED


def test_watchdog_breach_resets_recovery_streak(small_lm):
    cfg, params = small_lm
    engine = ServeEngine(cfg, params,
                         EngineConfig(slots=1, max_seq=64,
                                      watchdog_ticks=4.0,
                                      watchdog_floor_s=0.0,
                                      watchdog_recovery=3))
    for _ in range(engine._watchdog_arm):
        engine._watchdog(0.01)
    engine._watchdog(10.0)
    engine._watchdog(0.01)
    engine._watchdog(0.01)
    engine._watchdog(10.0)                  # breach mid-streak
    engine._watchdog(0.01)
    engine._watchdog(0.01)
    assert engine.health == DEGRADED        # streak restarted
    engine._watchdog(0.01)
    assert engine.health == HEALTHY


def test_watchdog_disabled_with_none(small_lm):
    cfg, params = small_lm
    engine = ServeEngine(cfg, params,
                         EngineConfig(slots=1, max_seq=64,
                                      watchdog_ticks=None))
    for _ in range(engine._watchdog_arm + 1):
        engine._watchdog(100.0)
    assert engine.health == HEALTHY


# ---------------------------------------------------------------------------
# audit(): refcount / pin / span cross-check reclaims injected leaks
# ---------------------------------------------------------------------------

def _shared_prefix_run(cfg, params, plan):
    """Publish a 3-block prefix, then retire a second request that holds
    pins + cached-block refs on it — the workload where a leaky retire
    path actually leaks."""
    engine = ServeEngine(cfg, params,
                         EngineConfig(slots=2, max_seq=64, page_size=8,
                                      prefill_chunk=8, prefix_cache=True,
                                      faults=plan))
    shared = np.arange(2, 26, dtype=np.int32)
    engine.run([Request(rid=0, prompt=shared, max_new_tokens=4)])
    engine.run([Request(rid=1, prompt=shared.copy(), max_new_tokens=4)])
    return engine


def test_audit_reclaims_radix_pin_leak(small_lm):
    cfg, params = small_lm
    plan = fl.FaultPlan()
    plan.arm("radix_pin_leak", rid=1)
    engine = _shared_prefix_run(cfg, params, plan)
    assert plan.injected.get("radix_pin_leak") == 1
    leaked_pins = sum(n.pins for n in engine.radix.nodes())
    assert leaked_pins > 0                  # the leak is real before audit
    rep = engine.audit()
    assert rep["reclaimed_pins"] == leaked_pins
    assert rep["reclaimed_refs"] > 0        # cached-block refs leaked too
    assert rep["leaked_after"] == 0
    assert sum(n.pins for n in engine.radix.nodes()) == 0
    rep2 = engine.audit()                   # audit converges
    assert rep2["reclaimed_pins"] == 0 and rep2["reclaimed_refs"] == 0
    # the cache still works: pins reclaimed, prefix still matched
    engine.run([Request(rid=2, prompt=np.arange(2, 26, dtype=np.int32),
                        max_new_tokens=4)])
    assert finish_reasons(engine)[2] in ("eos", "max_tokens")


def test_audit_reclaims_block_leak(small_lm):
    cfg, params = small_lm
    plan = fl.FaultPlan()
    plan.arm("block_leak", rid=1)
    engine = _shared_prefix_run(cfg, params, plan)
    assert plan.injected.get("block_leak") == 1
    free_before = engine.allocator.free_blocks
    rep = engine.audit()
    assert rep["reclaimed_refs"] > 0
    assert rep["leaked_after"] == 0
    assert engine.allocator.free_blocks > free_before
    assert engine.audit()["reclaimed_refs"] == 0


def test_audit_clean_engine_reclaims_nothing(small_lm):
    cfg, params = small_lm
    engine = _shared_prefix_run(cfg, params, None)
    rep = engine.audit()
    assert rep["reclaimed_refs"] == 0
    assert rep["reclaimed_pins"] == 0
    assert rep["mismatches"] == []
    assert rep["leaked_after"] == 0


def test_audit_mid_flight_is_safe(small_lm):
    """audit() against live slots (mid-decode) must account slot-owned
    refs and pins as owed — reclaiming nothing and disturbing nothing."""
    cfg, params = small_lm
    engine = ServeEngine(cfg, params,
                         EngineConfig(slots=2, max_seq=64, page_size=8,
                                      prefix_cache=True))
    reqs = make_requests(cfg, 2, max_new=16)
    for r in reqs:
        engine.submit(r)
    for _ in range(4):
        engine.step()
    rep = engine.audit()
    assert rep["reclaimed_refs"] == 0 and rep["reclaimed_pins"] == 0
    assert rep["mismatches"] == []
    done = engine.run([], max_ticks=200)
    assert len(done) == 2
    assert all(len(r.out_tokens) == 16 for r in reqs)


# ---------------------------------------------------------------------------
# Containment: exceptions in chunk/step/sink cost one request
# ---------------------------------------------------------------------------

def test_chunk_error_contained_to_target(small_lm):
    cfg, params = small_lm
    plan = fl.FaultPlan()
    plan.arm("chunk_error", rid=1)
    engine = ServeEngine(cfg, params,
                         EngineConfig(slots=2, max_seq=64, page_size=8,
                                      faults=plan))
    done = engine.run(make_requests(cfg, 4, max_new=4))
    fin = finish_reasons(engine)
    assert fin[1] == "internal_error"
    assert all(fin[r] in ("eos", "max_tokens") for r in (0, 2, 3))
    assert len(done) == 4
    # the contained exception stays visible: metrics and the finish event
    err = "InjectedFault: injected fault site=chunk_error rid=1 tick="
    [rec] = engine.metrics()["errors"]
    assert rec["rid"] == 1 and rec["error"].startswith(err)
    [ev] = [e for e in engine.trace.events()
            if e["event"] == "finish" and e["rid"] == 1]
    assert ev["error"] == rec["error"]
    assert engine.allocator.free_blocks == engine.allocator.num_blocks - 1


def test_serving_failures_flags_internal_error_and_degraded(small_lm):
    """The launcher's exit status: a request retired by containment, or an
    engine left degraded, fails the run (launch/serve.serving_failures)."""
    from repro.launch.serve import serve_requests, serving_failures
    cfg, params = small_lm
    clean = ServeEngine(cfg, params,
                        EngineConfig(slots=2, max_seq=64, page_size=8))
    done = serve_requests(clean, make_requests(cfg, 3, max_new=4))
    assert len(done) == 3 and serving_failures(clean) == []
    clean.mark_degraded("operator")
    assert serving_failures(clean) == ["engine degraded: operator"]

    plan = fl.FaultPlan()
    plan.arm("chunk_error", rid=1)
    engine = ServeEngine(cfg, params,
                         EngineConfig(slots=2, max_seq=64, page_size=8,
                                      faults=plan))
    assert len(serve_requests(engine, make_requests(cfg, 3, max_new=4))) == 3
    [failure] = serving_failures(engine)
    assert "1 request(s) retired as internal_error (rids [1])" in failure
    assert "InjectedFault: injected fault site=chunk_error" in failure


def test_step_error_contained_and_run_completes(small_lm):
    """A step-level fault retires its payload request and the driver loop
    keeps going — the contained tick counts as progress, not as a dead
    queue."""
    cfg, params = small_lm
    plan = fl.FaultPlan()
    plan.arm("step_error", rid=1)
    engine = ServeEngine(cfg, params,
                         EngineConfig(slots=2, max_seq=64, page_size=8,
                                      faults=plan))
    done = engine.run(make_requests(cfg, 3, max_new=4))
    fin = finish_reasons(engine)
    assert fin[1] == "internal_error"
    assert len(done) == 3
    assert engine.health == HEALTHY         # targeted fault: no degrade


def test_untargeted_step_error_degrades(small_lm):
    cfg, params = small_lm
    plan = fl.FaultPlan()
    plan.arm("step_error")                  # no rid: nothing to retire
    engine = ServeEngine(cfg, params,
                         EngineConfig(slots=1, max_seq=64, page_size=8,
                                      faults=plan))
    engine.submit(make_requests(cfg, 1, max_new=2)[0])
    engine.step()
    assert engine.health == DEGRADED
    assert engine.health_reason == "injected:step_error"
    # recovery is explicit for non-watchdog reasons
    engine.mark_healthy()
    assert engine.health == HEALTHY
    done = engine.run([], max_ticks=100)
    assert len(done) == 1


# ---------------------------------------------------------------------------
# Health machine + /healthz + front-door refusal
# ---------------------------------------------------------------------------

def test_health_transitions_and_trace_events(small_lm):
    cfg, params = small_lm
    engine = ServeEngine(cfg, params, EngineConfig(slots=1, max_seq=64))
    assert engine.health == HEALTHY
    engine.mark_degraded("test_reason")
    assert engine.health == DEGRADED
    engine.mark_degraded("second")          # no-op: already degraded
    assert engine.health_reason == "test_reason"
    engine.mark_healthy()
    assert engine.health == HEALTHY
    engine.close()
    assert engine.health == DRAINING        # terminal
    engine.mark_healthy()
    assert engine.health == DRAINING
    ev = [e for e in engine.trace.events() if e["event"] == "health"]
    assert [(e["state"], e["rid"]) for e in ev] == [
        (DEGRADED, -1), (HEALTHY, -1), (DRAINING, -1)]


def test_healthz_endpoint_tracks_health(small_lm):
    cfg, params = small_lm
    engine = ServeEngine(cfg, params, EngineConfig(slots=1, max_seq=64))
    server = engine.serve_metrics(0)
    port = server.server_address[1]
    url = f"http://127.0.0.1:{port}/healthz"
    with urllib.request.urlopen(url) as resp:
        assert resp.status == 200
        assert json.load(resp) == {"status": "healthy"}
    engine.mark_degraded("unit_test")
    with pytest.raises(urllib.error.HTTPError) as exc_info:
        urllib.request.urlopen(url)
    assert exc_info.value.code == 503
    assert json.load(exc_info.value) == {"status": "degraded"}
    text = urllib.request.urlopen(
        f"http://127.0.0.1:{port}/metrics").read().decode()
    assert "serve_health 1.0" in text
    engine.close()


def test_frontdoor_refuses_submits_when_degraded_and_recovers(small_lm):
    """End to end: an injected slow step trips the watchdog mid-serve; the
    door refuses new submits (EngineUnhealthy) while the in-flight stream
    keeps draining; in-threshold ticks auto-recover the engine and submits
    flow again."""
    cfg, params = small_lm
    plan = fl.FaultPlan()
    plan.arm("slow_step", delay_s=0.3, nth=18)
    engine = ServeEngine(cfg, params,
                         EngineConfig(slots=2, max_seq=128, page_size=8,
                                      faults=plan, watchdog_ticks=2.0,
                                      watchdog_floor_s=0.0,
                                      watchdog_recovery=4))
    prompt = np.array([5, 6, 7], np.int32)
    saw = {"degraded": False, "refused": False}

    async def serve():
        async with FrontDoor(engine) as door:
            s1 = await door.submit(prompt, max_new_tokens=96)
            while engine.health == HEALTHY and not s1.finish_reason:
                await asyncio.sleep(0.005)
            assert engine.health == DEGRADED, "watchdog never tripped"
            saw["degraded"] = True
            with pytest.raises(EngineUnhealthy) as exc_info:
                await door.submit(prompt, max_new_tokens=4)
            assert exc_info.value.state == DEGRADED
            saw["refused"] = True
            while engine.health == DEGRADED and not s1.finish_reason:
                await asyncio.sleep(0.005)
            assert engine.health == HEALTHY, "watchdog never recovered"
            s2 = await door.submit(prompt, max_new_tokens=4)
            out2 = await s2.drain()
            await s1.cancel()
            await s1.drain()
            return out2

    out2 = asyncio.run(serve())
    assert len(out2) == 4
    assert engine.metrics()["faults_injected"] == {"slow_step": 1}


# ---------------------------------------------------------------------------
# Shutdown: close() is idempotent and exception-safe
# ---------------------------------------------------------------------------

def test_close_twice_and_after_failed_step(small_lm):
    """close() after a step that degraded the engine, then again: both
    no-ops beyond the first, health pinned at DRAINING."""
    cfg, params = small_lm
    plan = fl.FaultPlan()
    plan.arm("step_error")
    engine = ServeEngine(cfg, params,
                         EngineConfig(slots=1, max_seq=64, faults=plan))
    engine.submit(make_requests(cfg, 1, max_new=2)[0])
    engine.step()                          # contained: engine DEGRADED
    assert engine.health == DEGRADED
    engine.close()
    assert engine.health == DRAINING
    engine.close()
    assert engine.health == DRAINING


def test_close_stops_metrics_server_even_when_drain_raises(small_lm,
                                                           monkeypatch):
    import socket
    cfg, params = small_lm
    engine = ServeEngine(cfg, params, EngineConfig(slots=1, max_seq=64))
    server = engine.serve_metrics(0)
    port = server.server_address[1]

    def boom():
        raise RuntimeError("drain exploded")

    monkeypatch.setattr(engine, "_drain", boom)
    with pytest.raises(RuntimeError, match="drain exploded"):
        engine.close()
    # exception-safe: the listener is gone despite the raise
    s = socket.socket()
    s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    s.bind(("127.0.0.1", port))
    s.close()
    monkeypatch.undo()
    engine.close()                          # second close: clean no-op


def test_frontdoor_tick_error_degrades_but_streams_drain(small_lm):
    """An engine exception the tick loop cannot attribute to one request
    degrades the engine (submits refused) but the loop keeps draining —
    the in-flight stream completes instead of hanging its consumer."""
    cfg, params = small_lm
    engine = ServeEngine(cfg, params,
                         EngineConfig(slots=1, max_seq=64, page_size=8))
    real_step = engine.step
    fired = []

    def step_once_broken():
        if not fired:
            fired.append(True)
            raise RuntimeError("transient device error")
        return real_step()

    engine.step = step_once_broken

    async def serve():
        async with FrontDoor(engine) as door:
            s1 = await door.submit(np.array([5, 6, 7], np.int32),
                                   max_new_tokens=4)
            while engine.health == HEALTHY:
                await asyncio.sleep(0.002)
            assert engine.health_reason == "tick_error:RuntimeError"
            assert engine.metrics()["errors"] == [
                {"rid": None, "error": "RuntimeError: transient device error"}]
            with pytest.raises(EngineUnhealthy):
                await door.submit(np.array([5], np.int32),
                                  max_new_tokens=2)
            out = await s1.drain()          # loop survived the bad tick
            assert len(out) == 4
            engine.mark_healthy()
            s2 = await door.submit(np.array([5], np.int32),
                                   max_new_tokens=2)
            assert len(await s2.drain()) == 2

    asyncio.run(serve())


def test_frontdoor_aexit_closes_engine_after_tick_task_death(small_lm):
    """__aexit__ closes the engine (metrics port released) even when the
    tick task died outside its containment (stop() re-raises the task's
    error exactly once); a second stop() is a no-op."""
    import socket
    cfg, params = small_lm
    engine = ServeEngine(cfg, params,
                         EngineConfig(slots=1, max_seq=64, page_size=8))
    server = engine.serve_metrics(0)
    port = server.server_address[1]

    async def serve():
        door = FrontDoor(engine)
        door.start()

        def boom():
            raise RuntimeError("tick task killed")

        # _has_work runs outside the loop's containment: the task dies
        door._has_work = boom
        for _ in range(200):
            await asyncio.sleep(0.005)
            if door._task is not None and door._task.done():
                break
        assert door._task is not None and door._task.done()
        with pytest.raises(RuntimeError, match="tick task killed"):
            await door.__aexit__(None, None, None)
        await door.stop()                   # idempotent after the error

    asyncio.run(serve())
    assert engine.health == DRAINING
    s = socket.socket()
    s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    s.bind(("127.0.0.1", port))
    s.close()


# ---------------------------------------------------------------------------
# Interleaving matrix: cancel x preempt x drain in the same tick
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("order",
                         list(itertools.permutations(
                             ("cancel", "step", "drain"))),
                         ids=lambda o: "-".join(o))
def test_cancel_preempt_drain_interleavings(small_lm, order):
    """Every ordering of {cancel a decoding request, step (which preempts
    under pool pressure), drain} within one tick leaves block, pin, and
    span accounting exact: the run completes, the audit cross-check is
    clean, and the pool returns to fully free."""
    cfg, params = small_lm
    rng = np.random.default_rng(7)
    engine = ServeEngine(cfg, params,
                         EngineConfig(slots=3, max_seq=64, page_size=16,
                                      num_blocks=4, preemption=True,
                                      preempt_after_ticks=1,
                                      prefix_cache=True))
    reqs = [Request(rid=0, prompt=rng.integers(2, cfg.vocab_size, size=8),
                    max_new_tokens=8),
            Request(rid=1, prompt=rng.integers(2, cfg.vocab_size, size=8),
                    max_new_tokens=8),
            Request(rid=2, prompt=rng.integers(2, cfg.vocab_size, size=33),
                    max_new_tokens=4)]      # 3-block head: forces pressure
    for r in reqs:
        engine.submit(r)
    # let the smalls occupy the pool so the big head ages toward preemption
    for _ in range(2):
        engine.step()
    engine.drain()
    assert any(rs is not None for rs in engine.slot_req)
    ops = {"cancel": lambda: engine.cancel(0),
           "step": engine.step,
           "drain": engine.drain}
    for name in order:
        ops[name]()
    rep = engine.audit()
    assert rep["mismatches"] == []
    assert rep["reclaimed_refs"] == 0 and rep["reclaimed_pins"] == 0
    done = engine.run([], max_ticks=400)
    fin = finish_reasons(engine)
    assert set(fin) == {0, 1, 2}
    assert fin[1] in ("eos", "max_tokens")
    assert fin[2] in ("eos", "max_tokens")
    assert fin[0] in ("cancelled", "eos", "max_tokens")
    assert len(done) + (1 if fin[0] == "cancelled" and not any(
        r.rid == 0 for r in done) else 0) >= 3
    rep = engine.audit()
    assert rep["mismatches"] == []
    assert rep["leaked_after"] == 0
    assert sum(n.pins for n in engine.radix.nodes()) == 0
    # every non-cache block is back: free + radix-resident == capacity - null
    resident = len(engine.radix.block_ids())
    assert (engine.allocator.free_blocks + resident
            == engine.allocator.num_blocks - 1)


def test_cancel_and_deadline_same_tick_single_retire(small_lm):
    """A request cancelled in the same tick its deadline expires retires
    exactly once — whichever path runs first wins, the other is a no-op."""
    cfg, params = small_lm
    engine = ServeEngine(cfg, params,
                         EngineConfig(slots=1, max_seq=64, page_size=8))
    req = Request(rid=0, prompt=np.array([5, 6, 7], np.int32),
                  max_new_tokens=8, deadline_ms=0.001)
    engine.submit(req)
    import time
    time.sleep(0.005)
    engine.cancel(0)
    engine.step()                          # deadline sweep runs here
    engine.poll()
    fin = finish_reasons(engine)
    assert fin[0] in ("cancelled", "deadline")
    assert sum(1 for rs in engine.scheduler.finished if rs.rid == 0) == 1
    assert engine.allocator.free_blocks == engine.allocator.num_blocks - 1
