"""Serving telemetry: metrics registry, exporters, lifecycle traces, and the
zero-cost contracts (no new jit traces, bit-identical streams, side-effect-
free snapshots) the observability subsystem must keep."""
import json
import time
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.archs import get_config
from repro.models import lm
from repro.serve import telemetry as tel
from repro.serve import trace as trace_lib
from repro.serve.engine import EngineConfig, Request, ServeEngine


@pytest.fixture(scope="module")
def small_lm():
    cfg = get_config("llama3.2-3b", smoke=True)
    params, _ = lm.init_lm(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    return cfg, params


def make_requests(cfg, n, max_new=4, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    prompt=rng.integers(2, cfg.vocab_size,
                                        size=int(rng.integers(3, 12))),
                    max_new_tokens=max_new) for i in range(n)]


# ---------------------------------------------------------------------------
# Registry primitives
# ---------------------------------------------------------------------------

def test_counter_gauge_basics():
    r = tel.MetricsRegistry()
    c = r.counter("c_total", "help", labels=("kind",))
    h1 = c.labels(kind="a")
    h1.inc()
    h1.inc(2.5)
    c.inc(kind="b")
    g = r.gauge("g")
    g.set(7)
    snap = r.snapshot()
    assert snap["c_total"] == {"kind=a": 3.5, "kind=b": 1.0}
    assert snap["g"] == 7.0


def test_label_handles_are_cached():
    r = tel.MetricsRegistry()
    c = r.counter("c_total", labels=("k",))
    assert c.labels(k="x") is c.labels(k="x")
    with pytest.raises(ValueError):
        c.labels(wrong="x")


def test_reregistration_returns_existing_or_raises():
    r = tel.MetricsRegistry()
    c = r.counter("c_total", labels=("k",))
    assert r.counter("c_total", labels=("k",)) is c
    with pytest.raises(ValueError):
        r.gauge("c_total")                       # type mismatch
    with pytest.raises(ValueError):
        r.counter("c_total", labels=("other",))  # label mismatch
    h = r.histogram("h", buckets=(1.0, 2.0))
    with pytest.raises(ValueError):
        r.histogram("h", buckets=(1.0, 4.0))     # bucket mismatch
    assert r.histogram("h", buckets=(1.0, 2.0)) is h


def test_invalid_names_and_buckets_rejected():
    r = tel.MetricsRegistry()
    with pytest.raises(ValueError):
        r.counter("bad name")
    with pytest.raises(ValueError):
        r.histogram("h", buckets=(2.0, 1.0))     # not ascending
    with pytest.raises(ValueError):
        r.histogram("h2", buckets=())


def test_snapshot_is_side_effect_free():
    r = tel.MetricsRegistry()
    h = r.histogram("h", buckets=(1.0, 2.0)).labels()
    h.observe(0.5)
    first = r.snapshot()
    second = r.snapshot()
    assert first == second
    assert first["h"]["count"] == 1
    # mutating the snapshot must not write through to the registry
    first["h"]["count"] = 99
    assert r.snapshot()["h"]["count"] == 1


# ---------------------------------------------------------------------------
# Percentiles: the one shared implementation
# ---------------------------------------------------------------------------

def test_percentiles_match_numpy():
    rng = np.random.default_rng(3)
    vals = rng.exponential(0.05, size=137).tolist()
    qs = (50, 90, 99)
    got = tel.percentiles(vals, qs)
    want = [float(np.percentile(np.asarray(vals), q)) for q in qs]
    assert got == want


def test_percentiles_empty_returns_none():
    assert tel.percentiles([], (50, 99)) == [None, None]


def test_histogram_quantile_bucket_tolerance():
    """The interpolated estimate must land within the bucket containing the
    exact quantile — the <=2x band the power-of-two ladders guarantee."""
    rng = np.random.default_rng(7)
    vals = rng.exponential(0.05, size=500)
    h = tel.Histogram("h", "", (), tel.DEFAULT_LATENCY_BUCKETS).labels()
    for v in vals:
        h.observe(float(v))
    edges = (0.0,) + tel.DEFAULT_LATENCY_BUCKETS
    for q in (50, 90, 99):
        exact = float(np.percentile(vals, q))
        est = h.quantile(q)
        lo = max(e for e in edges if e <= exact)
        hi = min(e for e in edges if e > exact)
        assert lo <= est <= hi, (q, exact, est, lo, hi)
    assert tel.Histogram("h2", "", (), (1.0,)).labels().quantile(50) is None


# ---------------------------------------------------------------------------
# Golden schema: the exported catalog is a stable contract
# ---------------------------------------------------------------------------

GOLDEN_SCHEMA = {
    "serve_requests_submitted_total": ("counter", ()),
    "serve_requests_admitted_total": ("counter", ()),
    "serve_requests_retired_total": ("counter", ("reason",)),
    "serve_preemptions_total": ("counter", ()),
    "serve_decode_tokens_total": ("counter", ()),
    "serve_prefill_tokens_total": ("counter", ("kind",)),
    "serve_ticks_total": ("counter", ()),
    "serve_greedy_ticks_total": ("counter", ()),
    "serve_jit_traces_total": ("counter", ("fn",)),
    "serve_prefix_cache_hits_total": ("counter", ()),
    "serve_prefix_cache_misses_total": ("counter", ()),
    "serve_prefix_cache_evictions_total": ("counter", ()),
    "serve_audit_runs_total": ("counter", ()),
    "serve_snapshots_total": ("counter", ()),
    "serve_restored_requests_total": ("counter", ()),
    "serve_handoffs_total": ("counter", ()),
    "serve_faults_injected_total": ("counter", ("site",)),
    "serve_slots_active": ("gauge", ()),
    "serve_queue_depth": ("gauge", ()),
    "serve_kv_pool_blocks_total": ("gauge", ()),
    "serve_kv_pool_blocks_free": ("gauge", ()),
    "serve_kv_pool_blocks_live": ("gauge", ()),
    "serve_kv_pool_blocks_shared": ("gauge", ()),
    "serve_kv_pool_blocks_leaked": ("gauge", ()),
    "serve_radix_nodes": ("gauge", ()),
    "serve_mesh_devices": ("gauge", ("axis",)),
    "serve_health": ("gauge", ()),
    "serve_ttft_seconds": ("histogram", ()),
    "serve_tpot_seconds": ("histogram", ()),
    "serve_queue_wait_seconds": ("histogram", ()),
    "serve_tick_phase_seconds": ("histogram", ("phase",)),
}


def test_golden_metric_schema():
    """Every metric ServingMetrics declares, by exact name/kind/labels.
    A rename, retype, or label change MUST update this test (and
    docs/observability.md) in the same commit — dashboards and the CI
    regression gates read these names."""
    r = tel.MetricsRegistry()
    tel.ServingMetrics(r)
    got = {name: (spec["kind"], tuple(spec["labels"]))
           for name, spec in r.schema().items()}
    assert got == GOLDEN_SCHEMA


def test_telemetry_module_imports_no_jax():
    """The host-side-only guarantee, structurally: telemetry/trace never
    import jax, so no publish can ever trace or sync."""
    import ast
    import repro.serve.telemetry as t
    import repro.serve.trace as tr
    for mod in (t, tr):
        tree = ast.parse(open(mod.__file__).read())
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                names = [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom):
                names = [node.module or ""]
            else:
                continue
            for n in names:
                assert not n.startswith("jax"), (mod.__name__, n)


# ---------------------------------------------------------------------------
# Prometheus text + HTTP exporter
# ---------------------------------------------------------------------------

def test_prometheus_text_format():
    r = tel.MetricsRegistry()
    c = r.counter("req_total", "requests", labels=("kind",))
    c.inc(kind='we"ird\n')
    h = r.histogram("lat_seconds", "latency", buckets=(0.1, 1.0)).labels()
    h.observe(0.05)
    h.observe(5.0)
    text = r.to_prometheus_text()
    lines = text.splitlines()
    assert "# TYPE req_total counter" in lines
    assert "# TYPE lat_seconds histogram" in lines
    assert 'req_total{kind="we\\"ird\\n"} 1.0' in lines
    # histogram buckets are cumulative and end at +Inf == _count
    assert 'lat_seconds_bucket{le="0.1"} 1' in lines
    assert 'lat_seconds_bucket{le="1.0"} 1' in lines
    assert 'lat_seconds_bucket{le="+Inf"} 2' in lines
    assert "lat_seconds_count 2" in lines
    assert any(line.startswith("lat_seconds_sum ") for line in lines)
    # every non-comment line is "name{labels} value"
    for line in lines:
        if line.startswith("#") or not line:
            continue
        name_part, _, value = line.rpartition(" ")
        assert name_part and value
        float(value.replace("+Inf", "inf"))


def test_http_metrics_endpoint():
    r = tel.MetricsRegistry()
    r.counter("hits_total").labels().inc(3)
    server = tel.start_metrics_server(r, port=0)
    try:
        port = server.server_address[1]
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics") as resp:
            assert resp.status == 200
            assert "text/plain" in resp.headers["Content-Type"]
            body = resp.read().decode()
        assert "hits_total 3.0" in body
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics.json") as resp:
            assert json.loads(resp.read())["hits_total"] == 3.0
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(f"http://127.0.0.1:{port}/nope")
    finally:
        server.shutdown()


# ---------------------------------------------------------------------------
# Trace recorder
# ---------------------------------------------------------------------------

def test_trace_ring_bound_and_accounting():
    rec = trace_lib.TraceRecorder(capacity=4)
    for rid in range(3):
        rec.record(rid, "submit", prompt_len=4, max_new_tokens=2)
        rec.record(rid, "finish", reason="eos", tokens=2, decode_s=0.1,
                   tpot_s=0.05)
    assert len(rec.events()) == 4          # ring bound
    assert rec.recorded == 6
    assert rec.dropped == 2
    assert rec.open_rids() == set()        # exact despite eviction
    assert rec.validate() == []
    trace_lib.drain_recorders()


def test_trace_slot_recycle_leak_oracle():
    """An admit into a slot whose previous request is still open is a span
    leak — caught with no engine attached (the conftest fixture's fallback
    when the engine was already garbage-collected)."""
    rec = trace_lib.TraceRecorder()
    rec.record(1, "submit", prompt_len=4, max_new_tokens=2)
    rec.record(1, "admit", slot=0, cached_prefix_tokens=0, suffix_tokens=3,
               blocks_reserved=1)
    # rid 1 never finishes; slot 0 is re-admitted
    rec.record(2, "submit", prompt_len=4, max_new_tokens=2)
    rec.record(2, "admit", slot=0, cached_prefix_tokens=0, suffix_tokens=3,
               blocks_reserved=1)
    leaks = rec.check_leaks(live_rids=[2])
    assert any("rid 1" in m for m in leaks)
    assert rec.validate() != []
    trace_lib.drain_recorders()            # don't fail the autouse sweep


def test_trace_rid_reuse_is_not_a_leak():
    rec = trace_lib.TraceRecorder()
    for _ in range(2):                     # same rid, two full spans
        rec.record(7, "submit", prompt_len=4, max_new_tokens=2)
        rec.record(7, "finish", reason="eos", tokens=1, decode_s=0.0,
                   tpot_s=0.0)
    assert rec.validate() == []
    assert rec.check_leaks(live_rids=[]) == []
    trace_lib.drain_recorders()


def test_event_schema_validation():
    assert trace_lib.validate_event(
        {"ts": 0.0, "rid": 1, "event": "queued", "queue_depth": 2}) is None
    assert trace_lib.validate_event(
        {"ts": 0.0, "rid": 1, "event": "queued"}) is not None   # missing attr
    assert trace_lib.validate_event(
        {"ts": 0.0, "rid": 1, "event": "nope"}) is not None
    assert trace_lib.validate_event({"rid": 1, "event": "queued"}) is not None


# ---------------------------------------------------------------------------
# Engine integration
# ---------------------------------------------------------------------------

def test_request_lifecycle_trace_jsonl(small_lm, tmp_path):
    """A served request leaves a schema-valid JSONL span covering the whole
    lifecycle, in order, with monotonic timestamps."""
    cfg, params = small_lm
    engine = ServeEngine(cfg, params,
                         EngineConfig(slots=2, max_seq=64, page_size=8,
                                      prefix_cache=True))
    done = engine.run(make_requests(cfg, 3))
    assert len(done) == 3
    path = tmp_path / "trace.jsonl"
    n = engine.export_trace(path)
    lines = [json.loads(line) for line in path.read_text().splitlines()]
    assert len(lines) == n
    # line 0 anchors the relative perf_counter timestamps to wall-clock:
    # wall_time_s (epoch seconds) and ts (perf_counter) read back to back
    # at export time, so consumers recover absolute times via
    # wall_time_s - (header.ts - event.ts)
    header, events = lines[0], lines[1:]
    assert header["event"] == "epoch" and header["rid"] == -1
    assert abs(header["wall_time_s"] - time.time()) < 300.0
    assert all(header["ts"] >= e["ts"] for e in events)
    for ev in lines:
        assert trace_lib.validate_event(ev) is None
    for rid in range(3):
        kinds = [e["event"] for e in events if e["rid"] == rid]
        assert kinds[0] == "submit" and kinds[1] == "queued"
        assert "admit" in kinds and "activate" in kinds
        assert "first_token" in kinds and kinds[-1] == "finish"
        assert kinds.index("admit") < kinds.index("activate") \
            < kinds.index("first_token")
    ts = [e["ts"] for e in events]
    assert ts == sorted(ts)
    assert engine.trace.open_rids() == set()


def test_registry_counts_match_engine_stats(small_lm):
    cfg, params = small_lm
    engine = ServeEngine(cfg, params,
                         EngineConfig(slots=2, max_seq=64, page_size=8))
    engine.warmup()
    done = engine.run(make_requests(cfg, 4))
    snap = engine.registry.snapshot()
    assert snap["serve_decode_tokens_total"] == engine.stats["decode_tokens"]
    assert snap["serve_ticks_total"] == engine.stats["ticks"]
    # greedy requests only: every tick took the sampler's argmax path
    assert snap["serve_greedy_ticks_total"] == engine.stats["ticks"]
    assert snap["serve_requests_submitted_total"] == 4
    assert snap["serve_requests_admitted_total"] == 4
    retired = snap["serve_requests_retired_total"]
    assert sum(retired.values()) == len(done) == 4
    # jit trace counters mirror _CountingJit exactly, per fn
    traces = snap["serve_jit_traces_total"]
    for j in engine._jits:
        assert traces.get(f"fn={j.name}", 0.0) == j.compiles
    # tick phases observed on every stepped tick
    phases = snap["serve_tick_phase_seconds"]
    assert phases["phase=schedule"]["count"] >= engine.stats["ticks"]
    assert phases["phase=dispatch"]["count"] == engine.stats["ticks"]
    assert phases["phase=device_step"]["count"] >= 1
    # pool accounting: everything freed at the end, nothing leaked
    assert snap["serve_kv_pool_blocks_leaked"] == 0
    assert snap["serve_kv_pool_blocks_live"] == 0
    assert snap["serve_ttft_seconds"]["count"] == 4
    assert snap["serve_queue_wait_seconds"]["count"] == 4


def test_engine_prometheus_export(small_lm):
    cfg, params = small_lm
    engine = ServeEngine(cfg, params,
                         EngineConfig(slots=2, max_seq=64, page_size=8))
    engine.run(make_requests(cfg, 2))
    text = engine.prometheus_text()
    for name, (kind, _) in GOLDEN_SCHEMA.items():
        assert f"# TYPE {name} {kind}" in text


def test_telemetry_off_noops_and_identical_streams(small_lm):
    """The flag contract: telemetry off produces bit-identical tokens, the
    same warm compile count, zero recompiles either way, and stubs out every
    surface (no registry, null recorder, empty exports)."""
    cfg, params = small_lm
    out, warm = {}, {}
    for on in (True, False):
        engine = ServeEngine(cfg, params,
                             EngineConfig(slots=2, max_seq=64, page_size=8,
                                          prefix_cache=True, telemetry=on))
        warm[on] = engine.warmup()
        reqs = make_requests(cfg, 5, seed=2)
        engine.run(reqs)
        assert engine.compile_count() == warm[on]    # zero recompiles
        out[on] = {r.rid: tuple(r.out_tokens) for r in reqs}
    assert out[True] == out[False]
    assert warm[True] == warm[False]
    assert engine.registry is None                   # the off engine
    assert isinstance(engine.trace, trace_lib.NullTraceRecorder)
    assert engine.prometheus_text() == ""
    assert engine.export_trace("/dev/null") == 0
    assert engine.metrics()["telemetry"] is False


def test_metrics_snapshot_semantics(small_lm):
    """engine.metrics() is side-effect-free and stable between ticks — two
    consecutive calls return equal dicts and mutate nothing."""
    cfg, params = small_lm
    engine = ServeEngine(cfg, params,
                         EngineConfig(slots=2, max_seq=64, page_size=8,
                                      prefix_cache=True))
    engine.run(make_requests(cfg, 3))
    m1 = engine.metrics()
    m2 = engine.metrics()
    assert m1 == m2
    m1["ticks"] = -1                      # caller mutation must not leak in
    assert engine.metrics()["ticks"] == m2["ticks"]
    # the stable keys launchers/benches/tests read (docs/observability.md)
    for key in ("backend", "telemetry", "submitted", "admitted", "retired",
                "max_queue_depth", "mean_queue_ticks", "mean_ttft_s",
                "p50_ttft_s", "p90_ttft_s", "p99_ttft_s", "p50_tpot_s",
                "p99_tpot_s", "p50_queue_wait_s", "p99_queue_wait_s",
                "ticks", "decode_tokens", "prefill_tokens",
                "cached_prefix_tokens", "prefix_hit_rate", "evictions",
                "compiles", "compiles_by_fn", "free_blocks", "total_blocks",
                "prefix_cache_nodes"):
        assert key in m2, key


def test_scheduler_histogram_percentiles(small_lm):
    """The O(1) histogram estimates in scheduler.metrics() bracket the exact
    shared-helper percentiles within one bucket."""
    cfg, params = small_lm
    engine = ServeEngine(cfg, params,
                         EngineConfig(slots=2, max_seq=64, page_size=8))
    engine.run(make_requests(cfg, 6, seed=5))
    m = engine.scheduler.metrics()
    exact = engine.scheduler.ttft_percentiles((50, 90, 99))
    edges = (0.0,) + tel.DEFAULT_LATENCY_BUCKETS
    for est, ex in zip((m["p50_ttft_s"], m["p90_ttft_s"], m["p99_ttft_s"]),
                       exact):
        lo = max(e for e in edges if e <= ex)
        hi = min(e for e in edges if e > ex)
        assert lo <= est <= hi, (est, ex)
    assert m["p50_tpot_s"] is not None
    assert m["p50_queue_wait_s"] is not None


def test_mesh_devices_gauge_unsharded(small_lm):
    cfg, params = small_lm
    engine = ServeEngine(cfg, params,
                         EngineConfig(slots=2, max_seq=64, page_size=8))
    snap = engine.registry.snapshot()
    assert snap["serve_mesh_devices"] == {"axis=data": 1.0, "axis=model": 1.0}
