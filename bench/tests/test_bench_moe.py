"""A stack the configuration file states: dense and mixture-of-experts
layers, with a reference module of its own (`smoke-moe.json`, toy widths,
on the CPU).  The harness builds, weighs, counts and checks it with no edit:
the window through FrontDoor, and the comparison that decides `correct`,
which must come out false when the timed path is broken."""
import dataclasses
import json
import os
import sys

import jax
import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
import cell  # noqa: E402
import run  # noqa: E402
import weights  # noqa: E402
import work  # noqa: E402

SEED = 2**31 + 23
MIX = {"arrival": "poisson", "rate_per_s": 3.0, "preroll_s": 0.5,
       "prompt": {"dist": "lognormal", "median": 24, "sigma": 0.5,
                  "min": 8, "max": 60},
       "output": {"dist": "uniform", "min": 4, "max": 16}, "greedy": True}
SPECS = {"end_to_end": [{"name": "output_tok_s", "unit": "tokens/s"},
                        {"name": "setup_s", "unit": "s"}], "per_layer": []}


def conf():
    return json.load(open(os.path.join(HERE, "data", "smoke-moe.json")))


@pytest.fixture(autouse=True)
def _drain():
    yield
    from repro.serve import trace
    trace.drain_recorders()


def cpu_run():
    return run.run_cell("smoke-moe", SEED, 2.0, False, require_chip=False,
                        conf=conf(), mix=MIX, specs=SPECS)


def test_the_stack_is_built_from_the_file():
    from repro.nn.blocks import LayerSpec
    from repro.nn.moe import MoEConfig
    cfg = cell.model_config(conf())
    assert cfg.groups == (((LayerSpec("attn", "dense"),
                            LayerSpec("attn", "moe")), 2),)
    assert cfg.moe == MoEConfig(num_experts=8, top_k=2, d_ff=32,
                                num_shared=1)
    assert cfg.num_layers == 4
    assert cell.reference_module(conf()).__file__ == os.path.join(
        HERE, "moe_reference.py")


@pytest.mark.parametrize("change,error", [
    ({"num_layers": 6}, "num_layers is 6 but layers states 4"),
    ({"moe": {"num_experts": 8, "top_k": 2, "d_ff": 32, "no_such": 1}},
     "unexpected keyword argument 'no_such'"),
])
def test_a_misstated_stack_is_refused(change, error):
    c = conf()
    c["model"].update(change)
    with pytest.raises((ValueError, TypeError), match=error):
        cell.model_config(c)


def test_an_expert_is_scaled_by_its_contraction_axis():
    cfg = cell.model_config(conf())
    shapes = weights.tree_shapes(cfg)["group0"]["l1"]["moe"]
    # stacked over the group's repeats, then over the 8 experts
    assert shapes["w_gate"].shape == (2, 8, 64, 32)
    assert weights._fan_in("w_gate", (8, 64, 32), cfg) == 64
    assert weights._fan_in("w_down", (8, 32, 64), cfg) == 32
    assert weights._fan_in("router", (64, 8), cfg) == 64
    assert weights._fan_in("ws_down", (32, 64), cfg) == 32


def test_one_period_drawn_alone_is_the_slice_of_the_tree():
    cfg = cell.model_config(conf())
    tree = weights.make(cfg, SEED)["group0"]
    one = weights.layer_fn(cfg, 0)(weights.root_key(SEED), 1)
    assert set(one) == {"l0", "l1"}
    for (kp, a), b in zip(jax.tree_util.tree_flatten_with_path(one)[0],
                          jax.tree_util.tree_leaves(tree)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b)[1],
                                      err_msg=jax.tree_util.keystr(kp))


def test_a_sound_run_is_correct():
    r = cpu_run()
    assert r["correct"] is True
    assert r["attempted"] > 0 and r["failed"] == 0
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


def test_experts_left_out_are_caught(monkeypatch):
    """The program's expert layer runs one expert a token where the file
    states two."""
    from repro.nn import blocks
    apply_moe = blocks.apply_moe

    def top1(params, x, cfg, act, **k):
        return apply_moe(params, x, dataclasses.replace(cfg, top_k=1), act,
                         **k)
    monkeypatch.setattr(blocks, "apply_moe", top1)
    r = cpu_run()
    assert r["correct"] is False
    assert r["checks"]["widest_logit_gap"]["value"] > 0.05


# d 64, 4 query and 2 KV heads of 16, d_ff 128, vocabulary 512; attention
# 64 * (4 + 2 * 2) * 16 + 4 * 16 * 64 = 12288 weights a layer, a dense MLP
# 3 * 64 * 128 = 24576, an expert layer 2 routed + 1 shared experts of
# 3 * 64 * 32 and the 64 x 8 router = 18944
MATMUL = 2 * (12288 + 24576) + 2 * (12288 + 18944)


def test_expert_work_is_counted_by_hand():
    m = conf()["model"]
    assert work.decode_token_flops(m, 10) == \
        2 * (MATMUL + 64 * 512) + 4 * 4 * 16 * 10 * 4
    assert work.prefill_token_flops(m, 9) == 2 * MATMUL + 4 * 4 * 16 * 10 * 4


def test_window_work_is_counted_by_hand():
    """The two dense layers attend to a window of 8 positions."""
    m = dict(conf()["model"], layers=[{"period": [
        {"kind": "attn", "mlp": "dense", "window": 8},
        {"kind": "attn", "mlp": "moe"}], "repeat": 2}])
    per_key = 4 * 4 * 16                       # operations, one layer
    assert work.attention_flops(m, 20) == per_key * (8 + 20 + 8 + 20)
    assert work.attention_flops(m, 5) == per_key * 5 * 4
    # float32 keys and values: 2 KV heads x 16 x 4 bytes, twice
    assert work.kv_bytes(m, 20, None, 16) == 256 * (8 + 20 + 8 + 20)
    # int4 with one exponent byte per page of 16 and KV head
    assert work.kv_bytes(m, 20, 4, 16) == \
        2 * (2 * 2 * (8 * 8 + 1)) + 2 * (2 * 2 * (20 * 8 + 2))
    ops, nbytes = work.paged_decode(m, 20, None, 16)
    assert ops == per_key * 56
    assert nbytes == 256 * 56 + 2 * 4 * 4 * 16 * 4
    # positions 16..23: a windowed layer reads positions 9..23 once
    ops, nbytes = work.mq_prefill(m, 16, 8, None, 16)
    assert ops == per_key * sum(2 * 8 + 2 * (p + 1) for p in range(16, 24))
    assert nbytes == 256 * (15 + 24 + 15 + 24) + 2 * 4 * 8 * 4 * 16 * 4
