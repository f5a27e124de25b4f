"""Dense configurations read as before the harness took stated stacks: the
same ModelConfig, the same seeded weights to the bit, and the same work
counts to the bit.  `data/dense-unchanged.json` holds the weights' digests
and the counts as the dense-only harness gave them."""
import hashlib
import json
import os
import sys

import jax
import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
import cell  # noqa: E402
import weights  # noqa: E402
import work  # noqa: E402

BEFORE = json.load(open(os.path.join(HERE, "data", "dense-unchanged.json")))
FILES = {"smoke-glm": os.path.join(HERE, "data", "smoke-glm.json"),
         "smoke-mistral": os.path.join(HERE, "data", "smoke-mistral.json"),
         "glm4-9b-s4-f32": str(cell.CONFIG_DIR / "glm4-9b-s4-f32.json"),
         "mistral-7b-s8-w4kv4": str(cell.CONFIG_DIR
                                    / "mistral-7b-s8-w4kv4.json")}


def load(name):
    return json.load(open(FILES[name]))


@pytest.mark.parametrize("name", sorted(FILES))
def test_the_model_config_is_the_dense_one(name):
    from repro.models.config import ModelConfig, dense_groups
    conf = load(name)
    m = dict(conf["model"])
    n = m.pop("num_layers")
    assert cell.model_config(conf) == ModelConfig(
        name=conf["name"], groups=dense_groups(n), **m)
    assert cell.reference_module(conf).__file__ == str(
        cell.BENCH_DIR / "reference.py")


@pytest.mark.parametrize("name", sorted(BEFORE["weights_sha256"]))
def test_the_weights_are_the_same_bits(name):
    tree = weights.make(cell.model_config(load(name)), BEFORE["seed"])
    h = hashlib.sha256()
    for kp, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        h.update(jax.tree_util.keystr(kp).encode())
        h.update(np.asarray(leaf).tobytes())
    assert h.hexdigest() == BEFORE["weights_sha256"][name]


@pytest.mark.parametrize("name", sorted(FILES))
def test_the_work_counts_are_the_same_floats(name):
    conf = load(name)
    m, e = conf["model"], conf["engine"]
    for key, want in BEFORE["work"][name].items():
        fn, *args = key.split("/")
        args = [int(a) for a in args]
        if fn in ("paged_decode", "mq_prefill"):
            got = list(getattr(work, fn)(m, *args, e["kv_bits"],
                                         e["page_size"]))
        else:
            got = getattr(work, fn)(m, *args)
        assert got == want, key
