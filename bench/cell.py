"""A cell as BENCHMARK.json names it: its configuration file and traffic mix.

Everything that belongs to one configuration, one mix or one per-layer metric
lives in a file of its own, found here by the name BENCHMARK.json gives it:
``configs/<config>.json``, ``traffic/<mix>.json``, ``metrics/<metric>.py``.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
CONFIG_DIR = BENCH_DIR / "configs"
METRIC_DIR = BENCH_DIR / "metrics"


def benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def workload(name: str) -> dict:
    for w in benchmark()["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def load_config(name: str) -> dict:
    path = CONFIG_DIR / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no configuration {name!r} ({path})")
    return json.loads(path.read_text())


def model_config(conf: dict):
    """The program's ModelConfig for a configuration file's ``model`` group
    (dense GQA/MHA decoders)."""
    from repro.models.config import ModelConfig, dense_groups
    m = dict(conf["model"])
    layers = m.pop("num_layers")
    return ModelConfig(name=conf["name"], groups=dense_groups(layers), **m)


def engine_config(conf: dict):
    """The EngineConfig fields a deployment sets; scheduler knobs stay at the
    engine's defaults, so a change to a default is measured."""
    from repro.serve.engine import EngineConfig
    e = conf["engine"]
    return EngineConfig(
        slots=e["slots"], max_seq=e["max_seq"], page_size=e["page_size"],
        weight_bits=e["weight_bits"], kv_bits=e["kv_bits"],
        # random weights: output length is set by the traffic, never by an
        # end-of-sequence token the weights happen to emit
        eos_id=-1,
        # the lifecycle ring holds every event of a run (queue wait and the
        # prefill work are read from it)
        trace_capacity=1 << 20)


def metric_reader(name: str):
    """``metrics/<name>.py``'s ``read(ctx)``: a number, or None when the run
    holds nothing for it to read."""
    path = METRIC_DIR / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no reader for metric {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
