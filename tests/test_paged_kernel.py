"""Paged-attention decode kernel: differential tests vs the dense paged_view
oracle (randomized/fragmented block tables, ragged lengths, GRAU epilogue
bit-exactness) and the decode-cost scaling law (live tokens, not pool size)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.archs import get_config
from repro.core.build import build_grau
from repro.core.folding import fold
from repro.kernels.paged_attention import (decode_grid, paged_attention,
                                           paged_prefill_attention)
from repro.kernels.ref import paged_attention_ref, paged_prefill_ref
from repro.models import lm
from repro.nn import attention as attn_lib
from repro.nn.common import build_lm_grau
from repro.serve import kv_cache as kvc
from repro.serve.engine import EngineConfig, Request, ServeEngine

BS = 8  # block size under test


def make_table(rng, lengths, nblocks, num_blocks, *, poison=None, pools=None):
    """Fragmented allocation: live blocks drawn in shuffled (non-contiguous)
    order, unowned pool blocks optionally poisoned so any dead-entry read
    shows up as a gross mismatch against the length-masked oracle."""
    free = list(range(1, num_blocks))
    rng.shuffle(free)
    owned = set()
    table = np.zeros((len(lengths), nblocks), np.int32)
    for s, n in enumerate(lengths):
        for j in range(max(1, -(-int(n) // BS))):
            table[s, j] = free.pop()
            owned.add(table[s, j])
    if poison is not None:
        k_pool, v_pool = pools
        dead = np.array([b for b in range(num_blocks) if b not in owned])
        k_pool = k_pool.at[dead].set(poison)
        v_pool = v_pool.at[dead].set(poison)
        return table, (k_pool, v_pool)
    return table, pools


def rand_case(rng, *, slots, h, kvh, d, nblocks, num_blocks, lengths,
              poison=None):
    q = jnp.asarray(rng.normal(size=(slots, h, d)), jnp.float32)
    k_pool = jnp.asarray(rng.normal(size=(num_blocks, kvh, BS, d)),
                         jnp.float32)
    v_pool = jnp.asarray(rng.normal(size=(num_blocks, kvh, BS, d)),
                         jnp.float32)
    table, pools = make_table(rng, lengths, nblocks, num_blocks,
                              poison=poison, pools=(k_pool, v_pool))
    if pools is not None:
        k_pool, v_pool = pools
    return (q, k_pool, v_pool, jnp.asarray(table),
            jnp.asarray(np.asarray(lengths, np.int32)))


@pytest.mark.parametrize("h,kvh", [(4, 4), (8, 2), (6, 3)])
def test_kernel_matches_oracle_randomized(h, kvh, rng):
    lengths = [5, 24, 1, 17]
    q, kp, vp, bt, ln = rand_case(rng, slots=4, h=h, kvh=kvh, d=32,
                                  nblocks=4, num_blocks=24, lengths=lengths)
    got = paged_attention(q, kp, vp, bt, ln)
    want = paged_attention_ref(q, kp, vp, bt, ln)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-5, atol=3e-5)


def test_kernel_ragged_lengths_and_idle_slots(rng):
    """Idle slots (length 0) must produce finite garbage without touching
    live outputs; ragged lengths mask partial blocks exactly."""
    lengths = [0, 9, 32, 3]
    q, kp, vp, bt, ln = rand_case(rng, slots=4, h=4, kvh=2, d=16,
                                  nblocks=4, num_blocks=20, lengths=lengths)
    got = np.asarray(paged_attention(q, kp, vp, bt, ln))
    want = np.asarray(paged_attention_ref(q, kp, vp, bt, ln))
    live = np.asarray(lengths) > 0
    np.testing.assert_allclose(got[live], want[live], rtol=3e-5, atol=3e-5)
    assert np.all(np.isfinite(got))          # idle-slot output is inert


def test_kernel_ignores_dead_table_entries(rng):
    """Pool blocks not owned by any live prefix are poisoned with huge
    values: if the kernel's dead-step skip or position mask ever read them,
    the softmax would be dominated and the diff gross."""
    lengths = [7, 30, 12, 2]
    q, kp, vp, bt, ln = rand_case(rng, slots=4, h=4, kvh=2, d=16,
                                  nblocks=4, num_blocks=32, lengths=lengths,
                                  poison=1e4)
    got = paged_attention(q, kp, vp, bt, ln)
    want = paged_attention_ref(q, kp, vp, bt, ln)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-5, atol=3e-5)


def test_kernel_free_then_reuse_blocks(rng):
    """vLLM-style churn: blocks freed by a retired slot get reassigned to a
    new slot; only the *current* table decides what each slot attends."""
    alloc = kvc.BlockAllocator(16)
    a = alloc.alloc(3)               # slot A: 3 blocks
    b = alloc.alloc(2)               # slot B: 2 blocks
    alloc.free(a)                    # A retires
    c = alloc.alloc(4)               # slot C reuses A's blocks (+1 fresh)
    assert set(a) & set(c)           # reuse actually happened
    lengths = np.array([2 * BS, 4 * BS - 3], np.int32)
    table = np.zeros((2, 4), np.int32)
    table[0, :2] = b
    table[1, :4] = c
    q = jnp.asarray(rng.normal(size=(2, 4, 16)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(16, 2, BS, 16)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(16, 2, BS, 16)), jnp.float32)
    got = paged_attention(q, kp, vp, jnp.asarray(table), jnp.asarray(lengths))
    want = paged_attention_ref(q, kp, vp, jnp.asarray(table),
                               jnp.asarray(lengths))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-5, atol=3e-5)


def test_kernel_grau_epilogue_bit_exact(rng):
    """Quantized mode: the fused GRAU epilogue must equal the oracle's
    quantization of the dense-view attention output bit for bit."""
    folded = fold("silu", s_in=2**-10, s_out=2**-4, out_bits=8)
    spec = build_grau(folded, mac_range=(-30000, 30000), segments=6,
                      num_exponents=8, mode="apot", bias_mode="lsq").spec
    lengths = [5, 24, 1, 17]
    q, kp, vp, bt, ln = rand_case(rng, slots=4, h=4, kvh=2, d=32,
                                  nblocks=4, num_blocks=24, lengths=lengths)
    got = paged_attention(q, kp, vp, bt, ln, spec=spec, s_in=2**-10)
    want = paged_attention_ref(q, kp, vp, bt, ln, spec=spec, s_in=2**-10)
    assert got.dtype == want.dtype == jnp.int8
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_kernel_grau_epilogue_unsigned_bus(rng):
    """Unsigned output modes emit uint8 (the [0,255] clamp must not wrap)."""
    folded = fold("relu", s_in=2**-10, s_out=2**-5, out_bits=8,
                  out_signed=False)
    spec = build_grau(folded, mac_range=(-30000, 30000), segments=6,
                      num_exponents=8, mode="apot", bias_mode="lsq").spec
    lengths = [9, 3]
    q, kp, vp, bt, ln = rand_case(rng, slots=2, h=4, kvh=2, d=16,
                                  nblocks=2, num_blocks=8, lengths=lengths)
    got = paged_attention(q, kp, vp, bt, ln, spec=spec, s_in=2**-10)
    want = paged_attention_ref(q, kp, vp, bt, ln, spec=spec, s_in=2**-10)
    assert got.dtype == want.dtype == jnp.uint8
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_paged_decode_attention_wrapper_kernel_vs_gather(rng):
    """The model-facing dispatch (nn/attention.paged_decode_attention) must
    agree across impls, including through a bucket-sliced table."""
    lengths = [6, 20, 11, 2]
    q, kp, vp, bt, ln = rand_case(rng, slots=4, h=4, kvh=2, d=16,
                                  nblocks=6, num_blocks=32, lengths=lengths)
    cache = attn_lib.PagedKVCache(k=kp, v=vp)
    st = attn_lib.PagedState(bt[:, :3], ln - 1)   # bucket covers max length
    q4 = q[:, None]
    got = attn_lib.paged_decode_attention(q4, cache, st, impl="kernel")
    want = attn_lib.paged_decode_attention(q4, cache, st, impl="gather")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-5, atol=3e-5)
    with pytest.raises(ValueError):
        attn_lib.paged_decode_attention(q4, cache, st, impl="nope")


def test_paged_view_max_blocks_is_a_prefix_gather(rng):
    kp = jnp.asarray(rng.normal(size=(12, 2, BS, 8)), jnp.float32)
    cache = attn_lib.PagedKVCache(k=kp, v=kp)
    bt = jnp.asarray(rng.integers(0, 12, size=(3, 4)).astype(np.int32))
    st = attn_lib.PagedState(bt, jnp.zeros(3, jnp.int32))
    full_k, _ = attn_lib.paged_view(cache, st)
    cut_k, _ = attn_lib.paged_view(cache, st, max_blocks=2)
    np.testing.assert_array_equal(np.asarray(cut_k),
                                  np.asarray(full_k)[:, :2 * BS])


# ---------------------------------------------------------------------------
# Multi-query (chunked-prefill) kernel mode
# ---------------------------------------------------------------------------

def mq_case(rng, *, b, chunk, h, kvh, d, nblocks, num_blocks, starts):
    q = jnp.asarray(rng.normal(size=(b, chunk, h, d)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(num_blocks, kvh, BS, d)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(num_blocks, kvh, BS, d)), jnp.float32)
    bt = jnp.asarray(rng.integers(1, num_blocks,
                                  size=(b, nblocks)).astype(np.int32))
    return q, kp, vp, bt, jnp.asarray(np.asarray(starts, np.int32))


@pytest.mark.parametrize("h,kvh", [(4, 4), (8, 2), (6, 3)])
def test_mq_kernel_matches_oracle(h, kvh, rng):
    """Chunked-prefill mode: per-row causal masking over prefix + chunk must
    match the dense-gather oracle at every (head, group) layout."""
    q, kp, vp, bt, st = mq_case(rng, b=3, chunk=16, h=h, kvh=kvh, d=32,
                                nblocks=6, num_blocks=40,
                                starts=[0, 8, 24])      # block-aligned p0
    got = paged_prefill_attention(q, kp, vp, bt, st)
    want = paged_prefill_ref(q, kp, vp, bt, st)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-5, atol=3e-5)


def test_mq_kernel_first_chunk_and_deep_prefix(rng):
    """start=0 (no prefix: pure causal chunk) and a start deep enough that
    dead grid steps follow the live blocks — both must match the oracle."""
    q, kp, vp, bt, st = mq_case(rng, b=2, chunk=8, h=4, kvh=2, d=16,
                                nblocks=8, num_blocks=32, starts=[0, 48])
    got = paged_prefill_attention(q, kp, vp, bt, st)
    want = paged_prefill_ref(q, kp, vp, bt, st)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-5, atol=3e-5)


def test_mq_kernel_grau_epilogue_bit_exact(rng):
    """The fused GRAU epilogue in prefill mode equals the oracle bit for
    bit — the chunk path must quantize exactly like the decode path."""
    folded = fold("silu", s_in=2**-10, s_out=2**-4, out_bits=8)
    spec = build_grau(folded, mac_range=(-30000, 30000), segments=6,
                      num_exponents=8, mode="apot", bias_mode="lsq").spec
    q, kp, vp, bt, st = mq_case(rng, b=2, chunk=16, h=4, kvh=2, d=32,
                                nblocks=5, num_blocks=24, starts=[8, 16])
    got = paged_prefill_attention(q, kp, vp, bt, st, spec=spec, s_in=2**-10)
    want = paged_prefill_ref(q, kp, vp, bt, st, spec=spec, s_in=2**-10)
    assert got.dtype == want.dtype == jnp.int8
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_mq_kernel_grau_unsigned_bus(rng):
    folded = fold("relu", s_in=2**-10, s_out=2**-5, out_bits=8,
                  out_signed=False)
    spec = build_grau(folded, mac_range=(-30000, 30000), segments=6,
                      num_exponents=8, mode="apot", bias_mode="lsq").spec
    q, kp, vp, bt, st = mq_case(rng, b=1, chunk=8, h=4, kvh=2, d=16,
                                nblocks=3, num_blocks=12, starts=[8])
    got = paged_prefill_attention(q, kp, vp, bt, st, spec=spec, s_in=2**-10)
    want = paged_prefill_ref(q, kp, vp, bt, st, spec=spec, s_in=2**-10)
    assert got.dtype == want.dtype == jnp.uint8
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_paged_prefill_wrapper_kernel_vs_gather(rng):
    """The model-facing dispatch (nn/attention.paged_prefill_attention) must
    agree across impls and reject unknown ones."""
    q, kp, vp, bt, st = mq_case(rng, b=2, chunk=16, h=4, kvh=2, d=16,
                                nblocks=6, num_blocks=32, starts=[0, 16])
    cache = attn_lib.PagedKVCache(k=kp, v=vp)
    pst = attn_lib.PagedState(bt, st)
    got = attn_lib.paged_prefill_attention(q, cache, pst, impl="kernel")
    want = attn_lib.paged_prefill_attention(q, cache, pst, impl="gather")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-5, atol=3e-5)
    with pytest.raises(ValueError):
        attn_lib.paged_prefill_attention(q, cache, pst, impl="nope")


def test_engine_kernel_impl_prefix_cache_on_off_bit_identical(tiny_lm):
    """Chunked prefill through the Pallas mq kernel end to end: within the
    kernel impl, turning the radix cache on must not change a single token
    (the bit-exactness invariant holds per impl — cross-impl token equality
    is a tie-breaking question, not a caching one), and the warm trace set
    must cover hits, misses, and suffix chunks."""
    cfg, params = tiny_lm
    rng = np.random.default_rng(9)
    prefix = rng.integers(2, cfg.vocab_size, size=40)
    reqs_proto = [(np.concatenate([prefix,
                                   rng.integers(2, cfg.vocab_size,
                                                size=3 + i)]), 4)
                  for i in range(5)]
    out = {}
    for on in (False, True):
        engine = ServeEngine(cfg, params,
                             EngineConfig(slots=2, max_seq=64, page_size=8,
                                          prefill_chunk=16, prefix_cache=on,
                                          paged_impl="kernel"))
        warm = engine.warmup()
        reqs = [Request(rid=i, prompt=p, max_new_tokens=m)
                for i, (p, m) in enumerate(reqs_proto)]
        engine.run(reqs)
        assert engine.compile_count() == warm
        out[on] = {r.rid: r.out_tokens for r in reqs}
    assert engine.metrics()["cached_prefix_tokens"] > 0
    assert out[True] == out[False]


# ---------------------------------------------------------------------------
# Decode-cost scaling: live tokens, not pool capacity
# ---------------------------------------------------------------------------

def test_decode_grid_scales_with_bucket_not_pool():
    assert decode_grid(4, 2, 2) == (4, 2, 2)
    assert decode_grid(4, 2, 64)[2] == 64
    # no argument of the grid is the pool's block count
    assert decode_grid(4, 2, 2) == decode_grid(4, 2, 2)


@pytest.fixture(scope="module")
def small_engine_factory():
    cfg = get_config("llama3.2-3b", smoke=True)
    params, _ = lm.init_lm(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)

    def make(**kw):
        return cfg, ServeEngine(cfg, params,
                                EngineConfig(slots=2, max_seq=256,
                                             page_size=8, **kw))
    return make


def test_decode_gathered_bytes_scale_with_live_context(small_engine_factory):
    """The paged decode jit's gathered bytes grow ~linearly with the decode
    bucket (live context) and are exactly invariant to the pool's block
    count (blocks_per_slot * block_size worth of capacity)."""
    _, engine = small_engine_factory(paged_impl="gather")
    costs = {b: engine.decode_cost(b) for b in (2, 8, 32)}
    g2, g8, g32 = (costs[b]["gather_bytes"] for b in (2, 8, 32))
    assert g2 > 0
    assert 3.0 < g8 / g2 < 5.0           # ~4x per 4x bucket
    assert 3.0 < g32 / g8 < 5.0
    # attention flops/dot traffic follow the bucket too
    assert costs[32]["dot_bytes"] > costs[2]["dot_bytes"]

    _, big = small_engine_factory(paged_impl="gather", num_blocks=1024)
    big8 = big.decode_cost(8)
    assert big8["gather_bytes"] == costs[8]["gather_bytes"]
    assert big8["dot_bytes"] == costs[8]["dot_bytes"]
    assert big8["flops"] == costs[8]["flops"]


# ---------------------------------------------------------------------------
# Engine-level differential: Pallas kernel vs dense-gather oracle
# ---------------------------------------------------------------------------

def _serve(engine, cfg, n=5, max_new=4):
    reqs = []
    for i in range(n):
        r = np.random.default_rng(100 + i)
        reqs.append(Request(rid=i,
                            prompt=r.integers(2, cfg.vocab_size,
                                              size=int(r.integers(3, 12))),
                            max_new_tokens=max_new))
    engine.run(reqs)
    return {r.rid: r.out_tokens for r in reqs}


@pytest.fixture(scope="module")
def tiny_lm():
    cfg = get_config("llama3.2-3b", smoke=True)
    params, _ = lm.init_lm(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    return cfg, params


def test_engine_kernel_impl_matches_gather(tiny_lm):
    cfg, params = tiny_lm
    out = {}
    for impl in ("gather", "kernel"):
        engine = ServeEngine(cfg, params,
                             EngineConfig(slots=2, max_seq=64, page_size=8,
                                          paged_impl=impl))
        warm = engine.warmup()
        out[impl] = _serve(engine, cfg)
        assert engine.compile_count() == warm   # kernel path is static too
    assert out["kernel"] == out["gather"]


def test_kernel_names_lists_only_compiled_kernels(tiny_lm):
    """kernel_names() reads the Pallas custom calls of the serving programs:
    an interpreted kernel lowers to plain HLO and is never reported (on a
    TPU the same call names paged_attention / matmul_wq), and reading them
    adds no trace to the engine's counted jits."""
    cfg, params = tiny_lm
    engine = ServeEngine(cfg, params,
                         EngineConfig(slots=2, max_seq=64, page_size=8,
                                      paged_impl="kernel"))
    if jax.default_backend() != "tpu":
        assert engine.kernel_names() == {"decode": [],
                                         "prefill_chunk": []}
    else:
        assert "paged_attention" in engine.kernel_names()["decode"]
    assert engine.compile_count() == 0


def test_engine_attn_grau_epilogue_matches_across_impls(tiny_lm):
    """The fused GRAU attention-output epilogue produces identical decodes
    through the kernel and through the gather fallback (quantization makes
    the comparison exact at the token level)."""
    cfg, params = tiny_lm
    g = build_lm_grau("identity", segments=6, num_exponents=8, mode="apot",
                      out_bits=8)
    out = {}
    for impl in ("gather", "kernel"):
        engine = ServeEngine(cfg, params,
                             EngineConfig(slots=2, max_seq=64, page_size=8,
                                          paged_impl=impl, attn_grau=g))
        engine.warmup()
        out[impl] = _serve(engine, cfg)
    assert out["kernel"] == out["gather"]


def test_engine_config_validation(tiny_lm):
    cfg, params = tiny_lm
    with pytest.raises(ValueError, match="paged_impl"):
        ServeEngine(cfg, params, EngineConfig(slots=1, max_seq=32,
                                              paged_impl="warp"))
    with pytest.raises(ValueError, match="decode_buckets"):
        ServeEngine(cfg, params, EngineConfig(slots=1, max_seq=64,
                                              page_size=8,
                                              decode_buckets=(1, 2)))
    with pytest.raises(ValueError, match="paged backend"):
        ServeEngine(cfg, params, EngineConfig(slots=1, max_seq=32,
                                              paged=False,
                                              paged_impl="kernel"))
