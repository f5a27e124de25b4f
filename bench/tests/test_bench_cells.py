"""BENCHMARK.json and the files it names: every configuration, traffic mix
and per-layer metric is found by its name and loads, so a PR that adds one
is covered without editing this test."""
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
import cell  # noqa: E402
import traffic  # noqa: E402
import window  # noqa: E402

BENCH = cell.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
CONFIGS = [c["name"] for c in BENCH["configs"]]
MIXES = sorted({w["traffic"] for w in BENCH["workloads"]})
PER_LAYER = [m["name"] for m in BENCH["per_layer"]]
# every mix and reader the harness can find, named in BENCHMARK.json or kept
# for a later cell
ALL_MIXES = sorted(set(MIXES) | {p.stem for p in traffic.TRAFFIC_DIR.glob(
    "*.json")})
ALL_READERS = sorted(set(PER_LAYER) | {p.stem for p in cell.METRIC_DIR.glob(
    "*.py")})


def test_contract_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert 1 <= BENCH["run_seconds"] <= 51
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    names = (CONFIGS + [w["name"] for w in BENCH["workloads"]]
             + list(e2e) + PER_LAYER)
    assert len(names) == len(set(names))
    for n in names + MIXES:
        assert NAME.match(n), n
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for w in BENCH["workloads"]:
        assert w["config"] in CONFIGS and w["chips"] in (1, 4)
        assert 0 < len(w["why"]) <= 200
        reports = {m["name"] for m in BENCH["end_to_end"]
                   if w["name"] in m.get("workloads", [w["name"]])}
        assert "setup_s" in reports and len(reports) >= 2
        assert any(w["name"] in m.get("workloads", [w["name"]])
                   for m in BENCH["per_layer"])
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        for wl in m.get("workloads", []):
            moved = next(x for x in BENCH["end_to_end"]
                         if x["name"] == m["moves"])
            assert wl in moved.get("workloads", [wl]), (m["name"], wl)


@pytest.mark.parametrize("name", CONFIGS)
def test_configuration_builds(name):
    from repro.models import lm
    entry = next(c for c in BENCH["configs"] if c["name"] == name)
    assert entry["file"] == f"bench/configs/{name}.json"
    conf = cell.load_config(name)
    assert conf["name"] == name and conf["source"] == entry["source"]
    for key in entry["reduced"]:
        assert key in conf and key in conf["published"], key
    for k in ("assumed", "deployment", "departures", "check"):
        assert conf[k]
    cfg = cell.model_config(conf)
    shapes = jax.eval_shape(
        lambda: lm.init_lm(cfg, jax.random.PRNGKey(0), jnp.float32)[0])
    assert shapes["head"].shape == (cfg.d_model, cfg.vocab_size)
    ecfg = cell.engine_config(conf)
    assert ecfg.eos_id < 0 and ecfg.slots >= 1


@pytest.mark.parametrize("name", ALL_MIXES)
def test_traffic_loads(name):
    mix = traffic.load_mix(name)
    assert traffic.schedule(mix, 1, BENCH["run_seconds"], 100)


@pytest.mark.parametrize("name", ALL_READERS)
def test_metric_reader_found(name):
    assert callable(cell.metric_reader(name))


def test_end_to_end_metrics_computed():
    w = window.Window(0.0, 1.0, [], 0, 1.0)
    have = set(window.end_to_end(w)) | {"setup_s"}
    assert {m["name"] for m in BENCH["end_to_end"]} <= have


def test_workload_lookup_and_missing_names():
    w = BENCH["workloads"][0]
    assert cell.workload(w["name"]) == w
    with pytest.raises(KeyError):
        cell.workload("no-such-cell")
    with pytest.raises(FileNotFoundError):
        cell.load_config("no-such-config")
    with pytest.raises(FileNotFoundError):
        traffic.load_mix("no-such-mix")
    with pytest.raises(FileNotFoundError):
        cell.metric_reader("no_such_metric")
    assert json.loads(json.dumps(BENCH)) == BENCH
