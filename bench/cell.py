"""A cell as BENCHMARK.json names it: its configuration file and traffic mix.

Everything that belongs to one configuration, one mix or one per-layer metric
lives in a file of its own, found here by the name BENCHMARK.json gives it:
``configs/<config>.json``, ``traffic/<mix>.json``, ``metrics/<metric>.py``.

A configuration file states its model in its ``model`` group, whose keys are
the program's ``ModelConfig`` fields plus two of the harness's own:

  num_layers  the depth; it has to equal the layers that ``layers`` states
  layers      optional: the stack, as a list of groups
              ``{"period": [<LayerSpec fields>, ...], "repeat": n}``, each
              period run ``repeat`` times in order; without it every layer
              is one dense attention layer (``dense_groups``)

A key whose ``ModelConfig`` field holds a dataclass (``moe``, ``mla``,
``ssm``, ``grau``, ...) is given as an object of that dataclass's fields,
and a layer entry as an object of ``LayerSpec``'s; each is built from the
field's own type, so a field the program adds needs no edit here.  Lists
become tuples (the config is frozen and hashed).  ``bench/work.py`` counts
the work from the same statement.

An optional top-level ``reference`` names the plain reference forward: a
path under ``bench/`` without ``.py`` (default ``reference``, the dense
GQA/MHA one).  Its module exposes ``Reference(conf, cfg, seed,
dtype=jnp.float32)`` with ``.logits(seqs)`` (see ``check.readings``).
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import typing
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


def _build(tp, value):
    """`value` from JSON as type `tp` wants it: an object becomes the
    dataclass that `tp` names (or holds, as Optional[...] does), field by
    field; a list becomes a tuple."""
    if isinstance(value, dict):
        cls = next((t for t in (tp, *typing.get_args(tp))
                    if dataclasses.is_dataclass(t)), None)
        if cls is None:
            raise TypeError(f"{value!r} given where {tp} is no dataclass")
        hints = typing.get_type_hints(cls)
        # a key that is no field of `cls` is refused by its constructor
        return cls(**{k: _build(hints.get(k), v) for k, v in value.items()})
    if isinstance(value, list):
        return tuple(value)
    return value


def model_config(conf: dict):
    """The program's ModelConfig for a configuration file's ``model`` group:
    the groups from ``layers`` (or ``dense_groups``), every other key built
    as its field's type."""
    from repro.models.config import ModelConfig, dense_groups
    from repro.nn.blocks import LayerSpec
    m = dict(conf["model"])
    n = m.pop("num_layers")
    layers = m.pop("layers", None)
    if layers is None:
        groups = dense_groups(n)
    else:
        groups = tuple((tuple(_build(LayerSpec, s) for s in g["period"]),
                        g["repeat"]) for g in layers)
        stated = sum(len(period) * r for period, r in groups)
        if stated != n:
            shape = " + ".join(f"{len(p)} x {r}" for p, r in groups)
            raise ValueError(f"{conf['name']}: num_layers is {n} but layers "
                             f"states {stated} ({shape})")
    return _build(ModelConfig, dict(m, name=conf["name"], groups=groups))


def reference_module(conf: dict):
    """The module of the configuration's plain reference forward
    (``reference``, a path under bench/ without ``.py``), imported by name
    from bench/, so `import reference` and this give the same module."""
    return importlib.import_module(
        conf.get("reference", "reference").replace("/", "."))


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
