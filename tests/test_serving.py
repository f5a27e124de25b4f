"""Continuous-batching serving subsystem: paged KV cache, scheduler,
static-shape sampling, prefill_step, and the no-recompile invariant."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.archs import get_config
from repro.models import lm
from repro.serve import kv_cache as kvc
from repro.serve import sampling as samp_lib
from repro.serve.engine import EngineConfig, Request, ServeEngine
from repro.serve.sampling import SamplingParams


@pytest.fixture(scope="module")
def small_lm():
    cfg = get_config("llama3.2-3b", smoke=True)
    params, _ = lm.init_lm(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    return cfg, params


def make_requests(cfg, n, max_new=4, seed=0, sampling=SamplingParams()):
    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    prompt=rng.integers(2, cfg.vocab_size,
                                        size=int(rng.integers(3, 12))),
                    max_new_tokens=max_new, sampling=sampling)
            for i in range(n)]


# ---------------------------------------------------------------------------
# Paged vs dense equivalence
# ---------------------------------------------------------------------------

def test_paged_matches_dense(small_lm):
    """Greedy decode through the paged engine must be numerically identical
    to the dense-cache engine (same params, same requests)."""
    cfg, params = small_lm
    out = {}
    for paged in (True, False):
        engine = ServeEngine(cfg, params,
                             EngineConfig(slots=2, max_seq=64, page_size=8,
                                          paged=paged))
        assert engine.paged is paged
        reqs = make_requests(cfg, 5)
        engine.run(reqs)
        out[paged] = {r.rid: r.out_tokens for r in reqs}
    assert out[True] == out[False]


def test_paged_matches_full_forward(small_lm):
    """Engine output (bucketed prefill + paged decode) matches a greedy
    continuation computed by re-running the full causal forward."""
    cfg, params = small_lm
    prompt = np.array([5, 6, 7, 8, 9], np.int32)
    toks = [int(t) for t in prompt]
    for _ in range(3):
        logits, _, _ = lm.apply_lm(params, cfg, jnp.asarray(toks)[None],
                                   mode="train")
        toks.append(int(jnp.argmax(logits[0, -1])))
    engine = ServeEngine(cfg, params, EngineConfig(slots=2, max_seq=64))
    req = Request(rid=0, prompt=prompt, max_new_tokens=3)
    engine.run([req])
    assert req.out_tokens == toks[len(prompt):]


def test_prefill_step_matches_train_forward(small_lm):
    """Bucket-padded prefill_step returns the full forward's last-position
    logits and caches whose length masks the padding."""
    cfg, params = small_lm
    prompt = jnp.asarray([[7, 3, 9, 11, 2]], dtype=jnp.int32)
    full_logits, _, _ = lm.apply_lm(params, cfg, prompt, mode="train")

    bucket = 16
    padded = jnp.zeros((1, bucket), jnp.int32).at[:, :5].set(prompt)
    caches = lm.init_caches(cfg, 1, bucket, dtype=jnp.float32)
    last, filled = lm.prefill_step(params, cfg, padded, caches,
                                   true_length=jnp.array([5], jnp.int32))
    np.testing.assert_allclose(np.asarray(last[0]),
                               np.asarray(full_logits[0, -1]),
                               rtol=1e-4, atol=1e-4)
    lengths = [c.length for group in filled for c in group]
    assert all(int(length.max()) == 5 for length in lengths)


# ---------------------------------------------------------------------------
# Slot lifecycle: retirement and reuse
# ---------------------------------------------------------------------------

def test_retire_on_max_tokens_and_slot_reuse(small_lm):
    cfg, params = small_lm
    engine = ServeEngine(cfg, params, EngineConfig(slots=2, max_seq=64))
    reqs = make_requests(cfg, 5, max_new=4)
    done = engine.run(reqs)
    assert len(done) == 5                      # 5 requests through 2 slots
    assert all(len(r.out_tokens) >= 1 for r in reqs)
    assert all(rs.finish_reason == "max_tokens"
               for rs in engine.scheduler.finished
               if len(rs.out_tokens) == 4)
    # all blocks returned to the pool after retirement
    assert engine.allocator.free_blocks == engine.allocator.num_blocks - 1
    assert all(r is None for r in engine.slot_req)


def test_retire_on_eos(small_lm):
    """Set eos_id to the token the model actually emits first: the request
    must retire immediately with reason 'eos' and free its slot."""
    cfg, params = small_lm
    prompt = np.array([5, 6, 7, 8, 9], np.int32)
    probe = ServeEngine(cfg, params, EngineConfig(slots=1, max_seq=64))
    r = Request(rid=0, prompt=prompt, max_new_tokens=4)
    probe.run([r])
    first = r.out_tokens[0]

    engine = ServeEngine(cfg, params,
                         EngineConfig(slots=1, max_seq=64, eos_id=first))
    reqs = [Request(rid=0, prompt=prompt, max_new_tokens=4),
            Request(rid=1, prompt=prompt, max_new_tokens=4)]
    engine.run(reqs)
    assert reqs[0].out_tokens == [first]
    assert engine.scheduler.finished[0].finish_reason == "eos"
    # the freed slot served the queued request too
    assert reqs[1].out_tokens == [first]


def test_completion_order(small_lm):
    """run() returns requests in completion order: a short request admitted
    later can finish before a long one admitted earlier."""
    cfg, params = small_lm
    engine = ServeEngine(cfg, params, EngineConfig(
        slots=2, max_seq=64, policy="prefill"))
    prompt = np.array([4, 5, 6], np.int32)
    reqs = [Request(rid=0, prompt=prompt, max_new_tokens=10),
            Request(rid=1, prompt=prompt, max_new_tokens=2)]
    done = engine.run(reqs)
    assert [r.rid for r in done] == [1, 0]
    assert [len(r.out_tokens) for r in reqs] == [10, 2]


# ---------------------------------------------------------------------------
# Sampling
# ---------------------------------------------------------------------------

def test_sampler_greedy_is_argmax():
    logits = np.array([[0.1, 2.0, -1.0, 0.5], [3.0, -2.0, 0.0, 1.0]],
                      np.float32)
    sp = samp_lib.pack([SamplingParams(), SamplingParams(temperature=0.7,
                                                         top_k=1)])
    out = samp_lib.sample(jnp.asarray(logits), sp, jax.random.PRNGKey(0))
    # slot 0 greedy, slot 1 top_k=1 — both must equal argmax
    assert list(np.asarray(out)) == [1, 0]


def test_sampler_top_p_masks_tail():
    """With one dominant token and a tight nucleus, only it can be drawn."""
    logits = np.full((1, 16), -5.0, np.float32)
    logits[0, 3] = 10.0
    sp = samp_lib.pack([SamplingParams(temperature=1.0, top_p=0.5)])
    for seed in range(8):
        out = samp_lib.sample(jnp.asarray(logits), sp,
                              jax.random.PRNGKey(seed))
        assert int(out[0]) == 3


def test_sampler_top_p_zero_keeps_top_token():
    """top_p=0 must degenerate to the top token, not an empty nucleus."""
    logits = np.full((1, 16), -5.0, np.float32)
    logits[0, 3] = 10.0
    sp = samp_lib.pack([SamplingParams(temperature=1.0, top_p=0.0)])
    for seed in range(8):
        out = samp_lib.sample(jnp.asarray(logits), sp,
                              jax.random.PRNGKey(seed))
        assert int(out[0]) == 3


def test_sampler_determinism_fixed_key(small_lm):
    """Identical seed => identical sampled generations, end to end."""
    cfg, params = small_lm
    sp = SamplingParams(temperature=0.9, top_k=50, top_p=0.9)

    def run_once():
        engine = ServeEngine(cfg, params,
                             EngineConfig(slots=2, max_seq=64, seed=123))
        reqs = make_requests(cfg, 4, max_new=5, sampling=sp)
        engine.run(reqs)
        return {r.rid: r.out_tokens for r in reqs}

    a, b = run_once(), run_once()
    assert a == b
    assert any(len(set(toks)) > 1 for toks in a.values())


def _sampler_batch(kind):
    """Four slots of random logits; slot 1 samples top-p unless the batch is
    all greedy, and is inactive in the batch that says so."""
    logits = np.random.default_rng(0).normal(size=(4, 64)).astype(np.float32)
    top_p = SamplingParams(temperature=0.8, top_p=0.9)
    sp = samp_lib.pack([SamplingParams(),
                        SamplingParams() if kind == "all_greedy" else top_p,
                        SamplingParams(), SamplingParams()])
    active = np.array([True, kind != "inactive_sampler", True, True])
    keys = samp_lib.SlotKeys(jax.random.PRNGKey(3),
                             jnp.arange(4, dtype=jnp.int32),
                             jnp.full((4,), 5, jnp.int32))
    return logits, sp, active, keys


@pytest.mark.parametrize("kind", ["all_greedy", "mixed", "inactive_sampler"])
def test_sampler_takes_the_path_the_batch_needs(kind):
    """An all-greedy batch, or one whose only sampling slot is not decoding,
    gets the argmax; a batch with an active top-p slot gets the full path's
    tokens in every slot, with the keys the engine folds per slot."""
    logits, sp, active, keys = _sampler_batch(kind)
    out = np.asarray(jax.jit(samp_lib.sample)(
        logits, samp_lib.for_active(sp, active), keys))
    argmax = logits.argmax(axis=-1)
    if kind == "mixed":
        folded = jax.vmap(lambda s, c: jax.random.fold_in(
            jax.random.fold_in(keys.key, s), c))(keys.seed, keys.step)
        full = np.asarray(jax.jit(samp_lib._draw)(logits, sp, folded))
        np.testing.assert_array_equal(out, full)
        assert out[1] != argmax[1]      # slot 1 drew, it did not argmax
        np.testing.assert_array_equal(out[[0, 2, 3]], argmax[[0, 2, 3]])
    else:
        np.testing.assert_array_equal(out[active], argmax[active])


def test_sampler_compiles_one_cond_in_its_scope():
    """One compiled program holds both paths: a single conditional, and its
    branches' ops (the argmax's reduce, the full path's sort) carry the
    ``sample`` scope the decode-step readers look for."""
    import re
    logits, sp, _, keys = _sampler_batch("mixed")
    jaxpr = jax.make_jaxpr(samp_lib.sample)(logits, sp, keys).jaxpr
    assert [e.primitive.name for e in jaxpr.eqns].count("cond") == 1
    hlo = jax.jit(samp_lib.sample).lower(logits, sp, keys).compile().as_text()
    conds = [ln for ln in hlo.splitlines() if " conditional(" in ln]
    assert len(conds) == 1 and 'op_name="jit(sample)/sample/cond"' in conds[0]
    paths = [n for n in re.findall(r'op_name="([^"]*)"', hlo) if "/" in n]
    assert paths and all("sample" in n.split("/") for n in paths)
    # lax.cond's branch 1 is the argmax (a reduce), branch 0 the full path
    branch = "jit(sample)/sample/cond/branch_{}_fun/"
    assert any(n.startswith(branch.format(1)) and n.endswith("/reduce")
               for n in paths)
    assert any(n.startswith(branch.format(0)) and n.endswith("/sort")
               for n in paths)


@pytest.mark.parametrize("paged", [True, False])
def test_greedy_ticks_count_the_argmax_path(small_lm, paged):
    """stats["greedy_ticks"] counts every tick of a greedy-only run, none of
    the ticks a sampled request decodes in, and the sampled neighbour does
    not change the greedy streams' tokens."""
    cfg, params = small_lm
    prompts = [np.array([5, 6, 7, 8], np.int32), np.array([9, 4, 3], np.int32)]

    def serve(with_sampled):
        engine = ServeEngine(cfg, params, EngineConfig(
            slots=3, max_seq=64, page_size=8, paged=paged))
        reqs = [Request(rid=i, prompt=p, max_new_tokens=10)
                for i, p in enumerate(prompts)]
        if with_sampled:
            reqs.append(Request(rid=2, prompt=np.array([11, 12], np.int32),
                                max_new_tokens=4,
                                sampling=SamplingParams(temperature=0.9,
                                                        top_p=0.9)))
        engine.run(reqs)
        return engine.stats, {r.rid: r.out_tokens for r in reqs[:2]}

    alone, greedy_alone = serve(False)
    assert alone["greedy_ticks"] == alone["ticks"] > 0
    mixed, greedy_mixed = serve(True)
    assert 0 < mixed["greedy_ticks"] <= mixed["ticks"] - 4
    assert greedy_mixed == greedy_alone


def test_engine_isolation(small_lm):
    """A request's output must not depend on its co-batched neighbours."""
    cfg, params = small_lm
    prompt = np.array([5, 6, 7, 8], np.int64)

    def serve_with(neigh_seed):
        engine = ServeEngine(cfg, params, EngineConfig(slots=2, max_seq=64))
        rng = np.random.default_rng(neigh_seed)
        reqs = [Request(rid=0, prompt=prompt, max_new_tokens=4),
                Request(rid=1, prompt=rng.integers(2, cfg.vocab_size, size=6),
                        max_new_tokens=4)]
        engine.run(reqs)
        return reqs[0].out_tokens

    assert serve_with(1) == serve_with(2)


# ---------------------------------------------------------------------------
# Scheduler
# ---------------------------------------------------------------------------

def test_scheduler_fairness_queue_longer_than_slots(small_lm):
    """FCFS with 7 requests through 2 slots: everyone is served, admission
    follows arrival order, and queue metrics record the contention."""
    cfg, params = small_lm
    engine = ServeEngine(cfg, params, EngineConfig(slots=2, max_seq=64))
    reqs = make_requests(cfg, 7, max_new=3)
    done = engine.run(reqs)
    assert len(done) == 7
    admit_ticks = {rs.rid: rs.admit_tick for rs in engine.scheduler.finished}
    order = sorted(admit_ticks, key=lambda rid: (admit_ticks[rid], rid))
    assert order == list(range(7))            # arrival order preserved
    m = engine.metrics()
    assert m["max_queue_depth"] >= 5
    assert m["mean_queue_ticks"] > 0
    assert m["retired"] == 7


def test_prefill_policy_saturates_slots(small_lm):
    """policy='prefill' admits into every free slot in one tick; 'fcfs'
    (max 1 prefill/tick) staggers admissions."""
    cfg, params = small_lm

    def admit_ticks(policy):
        engine = ServeEngine(cfg, params,
                             EngineConfig(slots=3, max_seq=64, policy=policy))
        reqs = make_requests(cfg, 3, max_new=2)
        engine.run(reqs)
        return sorted(rs.admit_tick for rs in engine.scheduler.finished)

    assert admit_ticks("prefill") == [0, 0, 0]
    assert admit_ticks("fcfs") == [0, 1, 2]


def test_paged_admission_blocks_gate(small_lm):
    """A request that cannot reserve blocks waits; it is admitted once a
    retirement frees the pool (admission control, not preemption)."""
    cfg, params = small_lm
    # pool: 2 slots' worth of one 32-token request each, minus slack
    engine = ServeEngine(cfg, params, EngineConfig(
        slots=2, max_seq=64, page_size=8, num_blocks=9, policy="prefill"))
    prompt = np.array([3, 4, 5], np.int32)
    reqs = [Request(rid=i, prompt=prompt, max_new_tokens=29) for i in range(3)]
    done = engine.run(reqs)
    assert len(done) == 3
    ticks = {rs.rid: rs.admit_tick for rs in engine.scheduler.finished}
    assert ticks[0] == 0 and ticks[1] == 0    # 4 blocks each, 8 available
    assert ticks[2] > 0                       # waited for a retirement


# ---------------------------------------------------------------------------
# Static-shape / no-recompile invariant
# ---------------------------------------------------------------------------

def test_no_recompile_after_warmup(small_lm):
    cfg, params = small_lm
    engine = ServeEngine(cfg, params, EngineConfig(slots=2, max_seq=64))
    # warmup() traces every decode bucket and prefill bucket up front
    warm_compiles = engine.warmup()
    assert warm_compiles >= 2                 # decode + >=1 prefill bucket
    # organic traffic reaching the same buckets adds nothing
    warm = [Request(rid=100 + i, prompt=np.arange(2, 2 + n),
                    max_new_tokens=2)
            for i, n in enumerate([3, 9, 17, 33])]
    engine.run(warm)
    assert engine.compile_count() == warm_compiles

    reqs = make_requests(cfg, 8, max_new=5, seed=3)
    engine.run(reqs)
    assert engine.compile_count() == warm_compiles
    assert all(len(r.out_tokens) >= 1 for r in reqs)


def test_counting_jit_counts_shape_identical_retrace():
    """A retrace whose input shapes/dtypes are unchanged (weak-type flip)
    must still be counted — the old cache-size/shape-hash probes missed it."""
    from repro.serve.engine import _CountingJit
    cj = _CountingJit(lambda x: x * 2, "probe")
    cj(jnp.float32(1.0))           # strong f32 scalar
    cj(1.0)                        # weak-typed python float: same shape/dtype
    cj(1.0)                        # cached — no new trace
    assert cj.compiles == 2


def test_donation_does_not_add_signatures(small_lm):
    """Donated caches/slot state flow through thousands of decode ticks; the
    trace counter must stay flat after warmup under both backends."""
    cfg, params = small_lm
    for paged in (True, False):
        engine = ServeEngine(cfg, params,
                             EngineConfig(slots=2, max_seq=64, page_size=8,
                                          paged=paged))
        warm = engine.warmup()
        engine.run(make_requests(cfg, 6, max_new=5, seed=11))
        engine.run(make_requests(cfg, 6, max_new=3, seed=12))
        assert engine.compile_count() == warm, f"paged={paged}"


def test_decode_bucket_ladder():
    assert kvc.decode_block_buckets(1) == (1,)
    assert kvc.decode_block_buckets(8) == (1, 2, 4, 8)
    assert kvc.decode_block_buckets(12) == (1, 2, 4, 8, 12)
    for n in (1, 3, 7, 32):
        ladder = kvc.decode_block_buckets(n)
        assert ladder[-1] == n and ladder[0] == 1
        assert list(ladder) == sorted(set(ladder))


def test_decode_buckets_cover_traffic(small_lm):
    """Short and long requests mixed: every tick's bucket must cover the
    longest live context, and the generated tokens must equal the
    full-table (pre-bucketing) configuration's output."""
    cfg, params = small_lm
    full = ServeEngine(cfg, params, EngineConfig(
        slots=2, max_seq=64, page_size=8, decode_buckets=(8,)))
    auto = ServeEngine(cfg, params, EngineConfig(
        slots=2, max_seq=64, page_size=8))
    assert auto.decode_buckets == (1, 2, 4, 8)
    outs = []
    for engine in (full, auto):
        reqs = [Request(rid=i, prompt=np.arange(2, 2 + n),
                        max_new_tokens=m)
                for i, (n, m) in enumerate([(3, 2), (40, 8), (5, 12)])]
        engine.run(reqs)
        outs.append({r.rid: r.out_tokens for r in reqs})
    assert outs[0] == outs[1]


def test_poll_batched_drain_matches_per_tick_poll(small_lm):
    """Running many ticks without polling (host sync deferred) must deliver
    exactly the tokens a poll-every-tick driver sees."""
    cfg, params = small_lm
    reqs_a = make_requests(cfg, 3, max_new=6, seed=21)
    reqs_b = make_requests(cfg, 3, max_new=6, seed=21)

    per_tick = ServeEngine(cfg, params, EngineConfig(slots=3, max_seq=64))
    done_a = per_tick.run(reqs_a)                    # polls every tick

    deferred = ServeEngine(cfg, params, EngineConfig(slots=3, max_seq=64))
    for r in reqs_b:
        deferred.submit(r)
    for _ in range(4):
        deferred.step()                              # no poll: ticks buffer
    done_b = list(deferred.poll())
    while (deferred.scheduler.waiting
           or any(s is not None for s in deferred.slot_req)):
        deferred.step()
        done_b.extend(deferred.poll())
    assert {r.rid: r.out_tokens for r in reqs_a} == \
           {r.rid: r.out_tokens for r in reqs_b}
    assert len(done_a) == len(done_b) == 3


# ---------------------------------------------------------------------------
# kv_cache unit behaviour
# ---------------------------------------------------------------------------

def test_block_allocator_recycles():
    alloc = kvc.BlockAllocator(8)             # 7 usable, block 0 reserved
    a = alloc.alloc(4)
    assert a is not None and kvc.NULL_BLOCK not in a
    assert not alloc.can_alloc(4)
    assert alloc.alloc(4) is None
    alloc.free(a)
    assert alloc.can_alloc(7)
    # regression: double-frees and never-allocated ids used to be appended
    # to the free list silently, corrupting it (tests/test_prefix_cache.py
    # covers the full guard + refcount matrix)
    with pytest.raises(ValueError):
        alloc.free(a)


def test_bucket_ladder():
    buckets = kvc.default_buckets(100, multiple=8)
    assert all(b % 8 == 0 for b in buckets)
    assert kvc.bucket_for(1, buckets) == buckets[0]
    assert kvc.bucket_for(100, buckets) == buckets[-1]
    with pytest.raises(ValueError):
        kvc.bucket_for(10_000, buckets)


def test_bad_prefill_buckets_rejected_at_init(small_lm):
    """Buckets that can't cover every admissible prompt (or aren't page
    multiples) must fail at construction, not mid-admission after blocks
    were committed."""
    cfg, params = small_lm
    with pytest.raises(ValueError, match="bucket"):
        ServeEngine(cfg, params, EngineConfig(slots=1, max_seq=256,
                                              prefill_buckets=(16, 32)))
    with pytest.raises(ValueError, match="page_size"):
        ServeEngine(cfg, params, EngineConfig(slots=1, max_seq=64,
                                              page_size=16,
                                              prefill_buckets=(10, 64)))


def test_paged_unsupported_archs_fall_back():
    for arch in ("mamba2-1.3b", "deepseek-v3-671b", "whisper-medium"):
        cfg = get_config(arch, smoke=True)
        assert not kvc.paged_supported(cfg)
        with pytest.raises(ValueError):
            params = {}
            ServeEngine(cfg, params, EngineConfig(slots=1, max_seq=32,
                                                  paged=True))
