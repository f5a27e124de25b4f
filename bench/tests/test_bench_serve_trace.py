"""The program's own spans in a trace (``serve_trace.py``), the scopes of
its ops (``op_scopes.py``) and the three readers built on them, on
hand-made events, and the host spans and compiled scopes of a CPU run read
back from its profile."""
import os
import re
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
import cell  # noqa: E402
import op_scopes  # noqa: E402
import serve_trace as st  # noqa: E402
import trace_reduce as tr  # noqa: E402


def ev(name, start, end, **stats):
    return tr.Event(name, float(start), float(end), stats)


def op(start, end):
    return ev(f"%fusion.{start} = f32[8] fusion(%a)", start, end)


# Two ticks in a window [0, 100).  Tick 1: a full drain waits for the chip
# (20-30), which finishes at 25 and is idle through the rest of the sync,
# then the host schedules (31-34) and dispatches (34-37) while the chip
# sits idle (to 36).  Tick 2 runs with no sync.  A collection runs
# outside any tick (70-80), and the chip is idle after 90.
SPANS = [
    ev("serve.tick", 10, 40), ev("serve.step", 12, 38),
    ev("serve.drain", 18, 31), ev("serve.device_step", 20, 30),
    ev("serve.schedule", 31, 34), ev("serve.call.prefill_chunk", 32, 33),
    ev("serve.dispatch", 34, 37), ev("serve.call.decode", 35, 36),
    ev("serve.reap", 38, 39),
    ev("serve.tick", 50, 60), ev("serve.step", 51, 59),
    ev("serve.dispatch", 52, 55), ev("serve.call.decode", 53, 54),
    ev("serve.gc", 70, 80),
]
OPS = [op(0, 25), op(36, 46), op(46, 50), op(50, 70), op(80, 90)]


def ctx(spans=SPANS, ops=OPS, window=(0.0, 100.0)):
    trace = types.SimpleNamespace(
        spans=spans, window=window, devs=[{"ops": ops, "modules": []}])
    return types.SimpleNamespace(serve_trace=trace, chips=1)


def test_interval_algebra():
    a = [(0, 10), (20, 30)]
    b = [(5, 25), (28, 40)]
    assert st.intersect(a, b) == [(5, 10), (20, 25), (28, 30)]
    assert st.intersect(a, []) == []
    assert st.length(a) == 20


def test_innermost_span_names_every_piece():
    pieces = st.innermost(SPANS, 0, 100)
    assert pieces[0] == (0, 10, st.OUTSIDE)
    at = {p[0]: p[2] for p in pieces}
    assert at[20] == "serve.device_step" and at[32] == "serve.call.prefill_chunk"
    assert at[33] == "serve.schedule" and at[70] == "serve.gc"
    assert pieces[-1] == (80, 100, st.OUTSIDE)
    assert st.length([(a, b) for a, b, _ in pieces]) == 100
    # an event of no length (a TPU allocation op) names no piece
    assert st.innermost(SPANS + [ev("serve.empty", 5, 5)], 0, 100) == pieces


def test_idle_split_by_span_adds_up_to_the_idle_gaps():
    split = st.idle_by_span(OPS, SPANS, 0, 100)
    idle = st.length(tr.idle_gaps(OPS, 0, 100)) * 1e-9
    assert sum(split.values()) == pytest.approx(idle)
    assert split == pytest.approx({
        "serve.device_step": 5e-9, "serve.drain": 1e-9, "serve.schedule": 2e-9,
        "serve.call.prefill_chunk": 1e-9, "serve.dispatch": 1e-9,
        "serve.call.decode": 1e-9, "serve.gc": 10e-9,
        "outside serve.tick": 10e-9})


def test_tick_host_ms_leaves_the_syncs_out():
    # (30 - 10) + 10 ns of host work over two decode calls
    assert cell.metric_reader("tick_host_ms")(ctx()) == pytest.approx(
        30 * 1e-6 / 2)
    assert st.tick_host_ns(SPANS, 45, 100) == 10


def test_idle_in_host_share_counts_idle_inside_ticks():
    # idle 25-36 inside tick 1 (5 ns of it in its sync), none in tick 2,
    # and the collection's 10 ns lie outside every tick: 11 ns of 100
    assert cell.metric_reader("idle_in_host_share")(ctx()) == pytest.approx(
        11.0)


def test_a_program_without_serve_spans_reads_nothing(monkeypatch):
    monkeypatch.setattr(st, "expected", lambda: False)
    for name in ("tick_host_ms", "idle_in_host_share",
                 "decode_sampler_share"):
        assert cell.metric_reader(name)(ctx()) is None


def test_a_program_with_spans_fails_a_trace_without_them():
    assert st.expected()
    st.require(ctx().serve_trace)
    bench_only = ctx(spans=[ev("bench.engine.step", 10, 40)]).serve_trace
    with pytest.raises(RuntimeError, match="serve.tick"):
        st.require(bench_only)
    no_decode = [s for s in SPANS if s.name != "serve.call.decode"]
    with pytest.raises(RuntimeError, match="serve.call.decode"):
        st.require(ctx(spans=no_decode).serve_trace)
    # a window in which the chip ran nothing asks for no span
    st.require(ctx(spans=[], ops=[]).serve_trace)


def test_the_summary_line_names_the_longest_gaps():
    line = st.summary(ctx().serve_trace, n=2)
    assert line.startswith("idle_by_span={")
    assert "idle_s=3.1e-08" in line
    got = dict(re.findall(r"(idle_in_\w+_s)=(\S+)", line))
    assert float(got["idle_in_tick_s"]) == pytest.approx(11e-9)
    assert float(got["idle_in_device_step_s"]) == pytest.approx(5e-9)
    assert '"outside serve.tick": 1e-08' in line


# One chip running a decode program (module 1) over [0, 50) and a prefill
# chunk (module 2) over [60, 80).  Decode: the layer scan's while op
# (0-30) with an attention op (2-12) and an MLP op (14-28) nested in it,
# the head (30-36), the sampler's sort (36-46) and its softmax (46-48).
DECODE = {"while.8": "jit(counted)/decode/while",
          "fusion.1": "jit(counted)/decode/while/body/attention/dot_general",
          "fusion.2": "jit(counted)/decode/while/body/mlp/dot_general",
          "fusion.3": "jit(counted)/decode/head/dot_general",
          "sort.5": "jit(counted)/decode/sample/jit(sort)/sort",
          "divide_negate_fusion": "jit(counted)/decode/sample/div"}
PREFILL = {"sort.5": "jit(counted)/prefill_chunk/sample/sort",
           "while.4": "jit(counted)/prefill_chunk/while"}


def named_op(name, start, end):
    return ev(f"%{name} = f32[8] op(%a)", start, end)


DEV = {"modules": [ev("jit_counted(1)", 0, 50), ev("jit_counted(2)", 60, 80)],
       "ops": [named_op("while.8", 0, 30), named_op("custom-call.13", 1, 1),
               named_op("fusion.1", 2, 12),
               named_op("fusion.2", 14, 28), named_op("fusion.3", 30, 36),
               named_op("sort.5", 36, 46),
               named_op("divide_negate_fusion", 46, 48),
               named_op("while.4", 60, 70), named_op("sort.5", 70, 80)]}


def scoped(programs, dev=DEV):
    c = ctx(spans=SPANS, ops=dev["ops"])
    c.serve_trace.devs = [dev]
    c.serve_trace.programs = op_scopes.Programs(programs, parse=dict)
    return c


def test_an_ops_scope_is_its_instructions_op_name():
    progs = op_scopes.Programs({"1": DECODE, "2": PREFILL}, parse=dict)
    assert progs["jit_counted(1)"][1] == "decode"
    assert progs["jit_counted(2)"][1] == "prefill_chunk"
    assert op_scopes.program_id("jit_counted(9017850881652161552)") == (
        "9017850881652161552")
    ops = op_scopes.step_ops(DEV, progs, "decode", 0, 100)
    assert [tr.op_name(o) for o, _ in ops] == [
        "while.8", "custom-call.13", "fusion.1", "fusion.2", "fusion.3", "sort.5",
        "divide_negate_fusion"]
    assert op_scopes.self_seconds(ops, 0, 100) == pytest.approx({
        "other": 6e-9, "attention": 10e-9, "mlp": 14e-9, "head": 6e-9,
        "sample": 12e-9})


def test_decode_sampler_share_reads_the_sample_scope():
    # sample 36-48 of decode's 0-48; the prefill's sort does not count
    read = cell.metric_reader("decode_sampler_share")
    assert read(scoped({"1": DECODE, "2": PREFILL})) == pytest.approx(
        100.0 * 12 / 48)


def test_decode_sampler_share_fails_without_its_scopes():
    read = cell.metric_reader("decode_sampler_share")
    unscoped = {k: v.replace("/sample", "") for k, v in DECODE.items()}
    with pytest.raises(RuntimeError, match="decode and sample"):
        read(scoped({"1": unscoped, "2": PREFILL}))
    with pytest.raises(RuntimeError, match="no HLO for jit_counted"):
        read(scoped({"2": PREFILL}))


@pytest.fixture
def _forget_recorders():
    yield
    from repro.serve import trace
    trace.drain_recorders()


def test_host_spans_of_a_cpu_run_from_its_profile(tmp_path,
                                                  _forget_recorders):
    """A FrontDoor run on the CPU under the profiler, read back as the
    readers read a chip's profile: its ticks, syncs and decode calls."""
    import jax
    import jax.numpy as jnp

    import traffic
    import window
    from repro.configs.archs import get_config
    from repro.models import lm
    from repro.serve.engine import EngineConfig, ServeEngine

    cfg = get_config("llama3.2-3b", smoke=True)
    params, _ = lm.init_lm(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    eng = ServeEngine(cfg, params, EngineConfig(slots=2, max_seq=64,
                                                page_size=8, eos_id=-1))
    eng.warmup()
    mix = {"arrival": "poisson", "rate_per_s": 20.0, "preroll_s": 0.1,
           "prompt": {"dist": "uniform", "min": 4, "max": 12},
           "output": {"dist": "uniform", "min": 4, "max": 8}, "greedy": True}
    arrivals = traffic.schedule(mix, 7, 0.5, cfg.vocab_size)
    span = []
    with jax.profiler.trace(str(tmp_path)):
        w = window.run(eng, arrivals, mix["preroll_s"], 0.5,
                       lambda: span.append(jax.profiler.TraceAnnotation(
                           "bench.window").__enter__()),
                       lambda: span[0].__exit__(None, None, None))
    eng.close()
    pd = tr.load(str(tmp_path))
    spans = tr.host_spans(pd, st.PREFIX)
    lo, hi = tr.span(tr.host_spans(pd), "bench.window")
    ticks = st.named(spans, "serve.tick", lo, hi)
    calls = st.named(spans, "serve.call.decode", lo, hi)
    assert w.stats_close["ticks"] > w.stats_open["ticks"]
    assert ticks and calls
    host = st.tick_host_ns(spans, lo, hi)
    assert 0 < host <= sum(t.end - t.start for t in ticks)
    # the profile carries each engine program's HLO, and the decode
    # program's ops carry the model step's scopes
    protos = op_scopes.read_protos(str(tmp_path))
    progs = op_scopes.Programs(protos)
    steps = {progs[f"m({pid})"][1] for pid in protos}
    assert {"decode", "prefill_chunk"} <= steps
    decode = [progs[f"m({pid})"][0] for pid in protos
              if progs[f"m({pid})"][1] == "decode"]
    labels = {op_scopes.label(n) for n in decode[0].values()}
    assert {"sample", "head", "attention", "mlp"} <= labels
