"""A whole run on the CPU at toy widths, without the look for a chip: the
window through FrontDoor, the metrics, and the comparison that decides
`correct` — which must come out false when the timed path is broken."""
import json
import os
import sys

import jax
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
import reference  # noqa: E402
import run  # noqa: E402

SEED = 2**31 + 5
MIX = {"arrival": "poisson", "rate_per_s": 3.0, "preroll_s": 0.5,
       "prompt": {"dist": "lognormal", "median": 24, "sigma": 0.5,
                  "min": 8, "max": 60},
       "output": {"dist": "uniform", "min": 4, "max": 16}, "greedy": True}
SPECS = {"end_to_end": [{"name": n, "unit": u} for n, u in (
    ("output_tok_s", "tokens/s"), ("ttft_p90_ms", "ms"),
    ("itl_p95_ms", "ms"), ("setup_s", "s"))], "per_layer": []}


def conf():
    c = json.load(open(os.path.join(HERE, "data", "smoke-glm.json")))
    # float32 on the CPU: the engine and the reference agree to rounding
    c["check"] = {"widest_logit_gap": 1e-4}
    return c


@pytest.fixture(autouse=True)
def _fast_reference(monkeypatch):
    monkeypatch.setattr(reference, "ROW_BLOCK", 64)
    yield
    from repro.serve import trace
    trace.drain_recorders()


def cpu_run():
    return run.run_cell("smoke", SEED, 2.0, False, require_chip=False,
                        conf=conf(), mix=MIX, specs=SPECS)


def test_a_sound_run_is_correct():
    r = cpu_run()
    assert r["correct"] is True
    assert r["attempted"] > 0 and r["failed"] == 0
    assert set(r["metrics"]) == {"output_tok_s", "ttft_p90_ms",
                                 "itl_p95_ms", "setup_s"}
    assert list(r)[-1] == "checks"
    c = r["checks"]["widest_logit_gap"]
    assert c["value"] <= c["limit"]


def test_a_token_altered_where_it_is_produced_is_caught(monkeypatch):
    from repro.serve import sampling
    sample = sampling.sample

    def altered(logits, sp, key):
        return (sample(logits, sp, key) + 1) % logits.shape[-1]
    monkeypatch.setattr(sampling, "sample", altered)
    r = cpu_run()
    assert r["correct"] is False
    assert r["checks"]["widest_logit_gap"]["value"] > 0.5


def test_the_control_in_the_programs_place_is_not_correct():
    # longer outputs: enough served positions for bfloat16 to flip a token
    mix = dict(MIX, output={"dist": "uniform", "min": 24, "max": 48})
    r = run.run_cell("smoke", SEED, 2.0, False, control=True,
                     require_chip=False, conf=conf(), mix=mix, specs=SPECS)
    assert r["correct"] is False
    c = r["checks"]["widest_logit_gap"]
    assert c["value"] > c["limit"]


def test_no_chip_exits_3_and_prints_no_result(capsys):
    name = run.cell.benchmark()["workloads"][0]["name"]
    try:
        rc = run.main(["--workload", name, "--seed", str(SEED),
                       "--seconds", "1", "--trace", "0"])
    finally:
        jax.config.update("jax_compilation_cache_dir", None)
    out = capsys.readouterr()
    assert rc == 3
    assert "{" not in out.out
    assert "no TPU" in out.err


def test_program_counter_readers_on_a_cpu_window():
    """decode_occupancy and queue_wait_p90_ms read the engine's counters and
    lifecycle events, which a CPU run has too."""
    import types
    import cell
    import traffic
    import weights
    import window
    from repro.serve.engine import ServeEngine
    c = conf()
    cfg = cell.model_config(c)
    eng = ServeEngine(cfg, weights.make(cfg, SEED), cell.engine_config(c))
    eng.warmup()
    w = window.run(eng, traffic.schedule(MIX, SEED, 2.0, cfg.vocab_size),
                   MIX["preroll_s"], 2.0)
    ctx = types.SimpleNamespace(engine=c["engine"], window=w,
                                events=eng.trace.events(),
                                due_in_window=window.due_in_window(w))
    eng.close()
    occ = cell.metric_reader("decode_occupancy")(ctx)
    assert w.stats_close["ticks"] > w.stats_open["ticks"]
    assert 0 < occ <= 100
    wait = cell.metric_reader("queue_wait_p90_ms")(ctx)
    assert wait is not None and wait >= 0
