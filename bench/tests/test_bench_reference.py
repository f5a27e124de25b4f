"""The plain reference against the serving path, on the CPU at toy widths.

Both families the benchmark serves: glm4-like (16 query heads per KV head
scaled down, QKV bias, float32) and mistral-like (int4 weights, int4 paged
KV).  The engine prefills in chunks and decodes through the paged pool; the
reference runs one plain forward over prompt + served tokens.  On the CPU
both compute in float32, so every served greedy token is the reference's
first choice (gap 0 up to float32 rounding)."""
import json
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
import cell  # noqa: E402
import check  # noqa: E402
import reference  # noqa: E402
import weights  # noqa: E402

SEED = 2**31 + 11
# float32 on the CPU: the two forwards differ only in summation order
F32_GAP = 1e-4


def smoke(name, **engine):
    conf = json.load(open(os.path.join(HERE, "data", f"{name}.json")))
    conf["engine"].update(engine)
    return conf


@pytest.fixture(autouse=True)
def _fast_reference(monkeypatch):
    monkeypatch.setattr(reference, "ROW_BLOCK", 64)
    yield
    from repro.serve import trace
    trace.drain_recorders()


def serve(conf, n=6, seed=SEED):
    from repro.serve.engine import Request, ServeEngine
    cfg = cell.model_config(conf)
    eng = ServeEngine(cfg, weights.make(cfg, seed), cell.engine_config(conf))
    rng = np.random.default_rng(seed)
    reqs = [Request(rid=i, prompt=rng.integers(
                0, cfg.vocab_size, int(rng.integers(5, 100)), np.int32),
                    max_new_tokens=int(rng.integers(8, 40)))
            for i in range(n)]
    done = eng.run(reqs)
    eng.close()
    assert len(done) == n
    return cfg, [(r.prompt, list(r.out_tokens)) for r in done]


def read(conf, cfg, finished, control=False):
    ref = reference.Reference(conf, cfg, SEED)
    ctl = reference.Reference(conf, cfg, SEED, jnp.bfloat16) \
        if control else None
    return check.readings(ref, check.pick(finished, SEED), ctl)


# one slot per request: every KV page is written from empty (see
# test_recycled_kv_pages_start_from_empty for pages that are reused)
@pytest.mark.parametrize("name", ["smoke-glm", "smoke-mistral"])
def test_served_tokens_are_the_references_first_choice(name):
    conf = smoke(name, slots=6)
    cfg, finished = serve(conf)
    r, ctl = read(conf, cfg, finished, control=True)
    assert r["served_tokens"] >= 100
    assert r["widest_logit_gap"] <= F32_GAP
    # the control (the reference in bfloat16) ranks other tokens first
    assert ctl["served_tokens"] == r["served_tokens"]
    assert ctl["widest_logit_gap"] > 10 * F32_GAP
    assert ctl["off_greedy_share"] > 0


def test_an_altered_token_is_far_below_the_best():
    conf = smoke("smoke-glm", slots=6)
    cfg, finished = serve(conf)
    prompt, toks = finished[0]
    toks = list(toks)
    toks[3] = (toks[3] + 1) % cfg.vocab_size
    r, _ = read(conf, cfg, [(prompt, toks)] + finished[1:])
    assert r["widest_logit_gap"] > 0.5


@pytest.mark.xfail(strict=True, reason=(
    "program fault: a freed int4 KV page keeps its exponent plane "
    "(PERF.md, Open questions, row 0); strict, so the test fails once the "
    "engine resets it and the mark has to go"))
def test_recycled_kv_pages_start_from_empty():
    """Two slots for six requests: later requests decode into pages that
    earlier ones left behind.  The pool's exponent plane is never reset
    when a page is freed, so the first generated token written into a
    recycled page is stored at the previous owner's exponent, not its own;
    the served tokens then depend on which page a request was given.  This
    fails until the engine resets a page's exponents when it allocates
    it."""
    conf = smoke("smoke-mistral", slots=2)
    cfg, finished = serve(conf)
    assert read(conf, cfg, finished)[0]["widest_logit_gap"] <= F32_GAP
