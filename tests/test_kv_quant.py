"""Quantized paged KV cache under one PrecisionPolicy.

What must hold at kv_bits < 16 (and is tested here): pool construction
follows the policy per layer with eager packing validation; the decode write
path sets/bumps per-block power-of-two scale exponents deterministically;
the Pallas kernel, the gather fallback, and the jnp oracle read bit-identical
dequantized values (differential tests on fragmented tables, decode and
multi-query prefill modes); copy-on-write block copies carry scale metadata;
the serving engine keeps every existing invariant — kernel==gather token
streams, cache-on/off bit-identity, zero recompiles after warmup — at 8 and
4 bits, composed with the GRAU attention epilogue and under a device mesh;
and the packed pools actually shrink the gathered bytes per decode step.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.archs import get_config
from repro.core.hwcost import kv_cache_cost
from repro.launch.mesh import make_serve_mesh
from repro.models import lm
from repro.nn import attention as attn_lib
from repro.nn.attention import PagedKVCache, PagedState, QuantPagedKVCache
from repro.nn.common import build_lm_grau
from repro.kernels.ref import paged_attention_ref, paged_prefill_ref
from repro.quant import kv as kvq
from repro.quant.policy import PrecisionPolicy, kv_policy
from repro.serve import kv_cache as kvc
from repro.serve.engine import EngineConfig, Request, ServeEngine

BS = 8  # block size under test


@pytest.fixture(scope="module")
def tiny_lm():
    cfg = get_config("llama3.2-3b", smoke=True)
    params, _ = lm.init_lm(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    return cfg, params


def _serve(engine, cfg, *, n=5, max_new=6, seed=0):
    r = np.random.default_rng(seed)
    reqs = [Request(rid=i, prompt=r.integers(2, cfg.vocab_size,
                                             size=int(r.integers(3, 12))),
                    max_new_tokens=max_new) for i in range(n)]
    engine.run(reqs)
    return {q.rid: q.out_tokens for q in reqs}


def _quant_pool(rng, *, nb, kvh, hd, bits):
    hdp = kvq.packed_head_dim(hd, bits)
    return QuantPagedKVCache(
        k=jnp.zeros((nb, kvh, BS, hdp), jnp.int8),
        v=jnp.zeros((nb, kvh, BS, hdp), jnp.int8),
        k_exp=jnp.full((nb, kvh), kvq.EXP_EMPTY, jnp.int8),
        v_exp=jnp.full((nb, kvh), kvq.EXP_EMPTY, jnp.int8), bits=bits)


def _fragmented_case(rng, *, slots, kvh, hd, nblocks, nb, lengths, bits):
    """Pool filled through the real chunk write path over a shuffled
    (fragmented) block table, so every read path sees production layouts."""
    cache = _quant_pool(rng, nb=nb, kvh=kvh, hd=hd, bits=bits)
    free = list(range(1, nb))
    rng.shuffle(free)
    table = np.zeros((slots, nblocks), np.int32)
    for s, n in enumerate(lengths):
        for j in range(max(1, -(-int(n) // BS))):
            table[s, j] = free.pop()
    table = jnp.asarray(table)
    kn = jnp.asarray(rng.normal(size=(slots, nblocks * BS, kvh, hd)),
                     jnp.float32)
    vn = jnp.asarray(rng.normal(size=(slots, nblocks * BS, kvh, hd)),
                     jnp.float32)
    st0 = PagedState(table, jnp.zeros((slots,), jnp.int32))
    cache = attn_lib.paged_prefill_update(cache, kn, vn, st0)
    return cache, table


# ---------------------------------------------------------------------------
# Pool construction + validation (policy -> storage)
# ---------------------------------------------------------------------------

def test_policy_pool_construction(tiny_lm):
    cfg, _ = tiny_lm
    pools = kvc.init_paged_caches(cfg, 9, BS, policy=kv_policy(8))
    leaves = [leaf for grp in pools for leaf in grp]
    assert all(isinstance(c, QuantPagedKVCache) and c.bits == 8
               for c in leaves)
    c = leaves[0]
    assert c.k.dtype == jnp.int8 and c.k.shape[-1] == cfg.head_dim
    assert c.k_exp.shape == c.k.shape[:2] + (cfg.kv_heads_phys,)
    assert int(c.k_exp.min()) == kvq.EXP_EMPTY
    p4 = kvc.init_paged_caches(cfg, 9, BS, policy=kv_policy(4))
    assert all(leaf.k.shape[-1] == cfg.head_dim // 2
               for grp in p4 for leaf in grp)
    # no policy (or an all-16 one) keeps today's float pools
    p16 = kvc.init_paged_caches(cfg, 9, BS, policy=kv_policy(16))
    assert all(isinstance(leaf, PagedKVCache) for grp in p16 for leaf in grp)


def test_mixed_per_layer_policy(tiny_lm):
    cfg, _ = tiny_lm
    pol = PrecisionPolicy(kv_rules=((r"group0\.l0", 8),), kv_default_bits=16)
    pools = kvc.init_paged_caches(cfg, 9, BS, policy=pol)
    assert isinstance(pools[0][0], QuantPagedKVCache)
    flat = [leaf for grp in pools for leaf in grp]
    assert any(isinstance(leaf, PagedKVCache) for leaf in flat[1:]) or \
        len(flat) == 1
    assert kvc.kv_bits_by_layer(cfg, pol)[0][0] == 8


def test_eager_packing_validation(tiny_lm):
    cfg, _ = tiny_lm
    odd = cfg.replace(head_dim=31)
    with pytest.raises(ValueError, match="head_dim=31 is odd"):
        kvc.init_paged_caches(odd, 9, BS, policy=kv_policy(4))
    with pytest.raises(ValueError, match="block_size"):
        kvc.validate_pool_packing(cfg, 0, 8)
    with pytest.raises(ValueError, match="kv_bits"):
        kvc.validate_pool_packing(cfg, BS, 3)
    # dense caches reject quantized-KV policies with a pointer at paged
    with pytest.raises(ValueError, match="paged"):
        lm.init_caches(cfg, 1, 32, policy=kv_policy(8))


def test_engine_precision_validation(tiny_lm):
    cfg, params = tiny_lm
    with pytest.raises(ValueError, match="not both"):
        ServeEngine(cfg, params,
                    EngineConfig(slots=1, max_seq=32, kv_bits=8,
                                 precision=kv_policy(8)))
    with pytest.raises(ValueError, match="paged backend"):
        ServeEngine(cfg, params,
                    EngineConfig(slots=1, max_seq=32, paged=False, kv_bits=8))


# ---------------------------------------------------------------------------
# Write path: exponent set/bump semantics
# ---------------------------------------------------------------------------

def test_decode_write_sets_then_bumps_exponent(rng):
    kvh, hd = 2, 8
    cache = _quant_pool(rng, nb=4, kvh=kvh, hd=hd, bits=8)
    table = jnp.asarray([[1, 2]], jnp.int32)
    small = jnp.asarray(rng.normal(size=(1, 1, kvh, hd)) * 0.01, jnp.float32)
    big = jnp.asarray(rng.normal(size=(1, 1, kvh, hd)) * 100.0, jnp.float32)

    cache = attn_lib.paged_update(cache, small, small,
                                  PagedState(table, jnp.asarray([0])))
    e0 = np.asarray(cache.k_exp[1], np.int32)
    assert (e0 > kvq.EXP_EMPTY).all()      # first write *sets* the scale
    kd, _ = attn_lib.paged_view(cache, PagedState(table, jnp.asarray([0])))
    np.testing.assert_allclose(np.asarray(kd[0, 0]), np.asarray(small[0, 0]),
                               atol=2.0 ** float(e0.max()) * 0.51)

    cache = attn_lib.paged_update(cache, big, big,
                                  PagedState(table, jnp.asarray([1])))
    e1 = np.asarray(cache.k_exp[1], np.int32)
    assert (e1 > e0).all()                 # larger magnitude bumps the scale
    kd, _ = attn_lib.paged_view(cache, PagedState(table, jnp.asarray([1])))
    step = 2.0 ** float(e1.max())
    # position 0 was requantized by shift onto the coarser grid: still
    # within one new-grid step of the original value
    np.testing.assert_allclose(np.asarray(kd[0, 0]), np.asarray(small[0, 0]),
                               atol=step)
    np.testing.assert_allclose(np.asarray(kd[0, 1]), np.asarray(big[0, 0]),
                               atol=step * 0.51)


def test_chunk_padding_does_not_coarsen_block_scale(rng):
    """A chunk's pad rows (positions >= ctx) must not pick the block's scale
    exponent: with PagedState.ctx set, huge garbage K/V past the prompt
    leaves the real tokens' quantization grid untouched."""
    kvh, hd = 2, 8
    real = jnp.asarray(rng.normal(size=(1, BS, kvh, hd)) * 0.05, jnp.float32)
    pad = jnp.full((1, BS, kvh, hd), 1e4, jnp.float32)
    kn = jnp.concatenate([real, pad], axis=1)        # block 1 real, 2 pad
    table = jnp.asarray([[1, 2]], jnp.int32)
    ctx = jnp.asarray([BS], jnp.int32)               # only block 1 is real

    def written(with_ctx):
        cache = _quant_pool(rng, nb=4, kvh=kvh, hd=hd, bits=8)
        st = PagedState(table, jnp.zeros((1,), jnp.int32),
                        ctx if with_ctx else None)
        return attn_lib.paged_prefill_update(cache, kn, kn, st)

    masked, unmasked = written(True), written(False)
    # the fully-real block's exponent is identical either way...
    assert int(masked.k_exp[1, 0]) == int(unmasked.k_exp[1, 0])
    # ...and round-trips the real tokens at their own (fine) grid
    kd, _ = attn_lib.paged_view(masked, PagedState(table, jnp.asarray([BS])))
    step = 2.0 ** float(np.asarray(masked.k_exp[1], np.int32).max())
    np.testing.assert_allclose(np.asarray(kd[0, :BS]), np.asarray(real[0]),
                               atol=step * 0.51)
    # the partial-block scenario: one real row + huge padding in one block
    mixed = jnp.concatenate([real[:, :1], pad[:, 1:]], axis=1)
    cache = _quant_pool(rng, nb=4, kvh=kvh, hd=hd, bits=8)
    st = PagedState(table[:, :1], jnp.zeros((1,), jnp.int32),
                    jnp.asarray([1], jnp.int32))
    cache = attn_lib.paged_prefill_update(cache, mixed, mixed, st)
    kd, _ = attn_lib.paged_view(cache, PagedState(table[:, :1],
                                                  jnp.asarray([0])))
    fine_step = 2.0 ** float(np.asarray(cache.k_exp[1], np.int32).max())
    assert fine_step < 1e-2                 # scale follows the real row
    np.testing.assert_allclose(np.asarray(kd[0, 0]), np.asarray(real[0, 0]),
                               atol=fine_step * 0.51)


def test_copy_pool_block_carries_scale_metadata(rng):
    cache = _quant_pool(rng, nb=4, kvh=2, hd=8, bits=8)
    cache = dataclasses.replace(
        cache,
        k=cache.k.at[1].set(jnp.asarray(
            rng.integers(-127, 128, size=cache.k.shape[1:]), jnp.int8)),
        k_exp=cache.k_exp.at[1].set(5))
    pools = ((dataclasses.replace(
        cache, k=cache.k[None], v=cache.v[None], k_exp=cache.k_exp[None],
        v_exp=cache.v_exp[None]),),)    # stacked (repeats=1) layout
    out = kvc.copy_pool_block(pools, jnp.int32(1), jnp.int32(3))[0][0]
    np.testing.assert_array_equal(np.asarray(out.k[0, 3]),
                                  np.asarray(cache.k[1]))
    assert int(out.k_exp[0, 3, 0]) == 5    # exponent moved with the payload
    assert out.bits == 8


# ---------------------------------------------------------------------------
# Differential: kernel vs gather vs oracle at every kv_bits
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bits", [8, 4])
def test_decode_kernel_matches_gather_and_ref(rng, bits):
    slots, kvh, hd, h = 3, 2, 16, 6
    lengths = np.asarray([20, 9, 24], np.int32)
    cache, table = _fragmented_case(rng, slots=slots, kvh=kvh, hd=hd,
                                    nblocks=3, nb=12, lengths=lengths,
                                    bits=bits)
    q = jnp.asarray(rng.normal(size=(slots, 1, h, hd)), jnp.float32)
    st = PagedState(table, jnp.asarray(lengths - 1))
    got_k = attn_lib.paged_decode_attention(q, cache, st, impl="kernel")
    got_g = attn_lib.paged_decode_attention(q, cache, st, impl="gather")
    want = paged_attention_ref(q[:, 0], cache.k, cache.v, table,
                               jnp.asarray(lengths), k_exp=cache.k_exp,
                               v_exp=cache.v_exp, kv_bits=bits)
    np.testing.assert_allclose(np.asarray(got_k[:, 0]), np.asarray(want),
                               rtol=3e-5, atol=3e-5)
    np.testing.assert_allclose(np.asarray(got_g[:, 0]), np.asarray(want),
                               rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("bits", [8, 4])
def test_prefill_kernel_matches_gather_and_ref(rng, bits):
    slots, kvh, hd, h, C = 3, 2, 16, 6, 8
    cache, table = _fragmented_case(rng, slots=slots, kvh=kvh, hd=hd,
                                    nblocks=3, nb=12,
                                    lengths=[24, 24, 24], bits=bits)
    q = jnp.asarray(rng.normal(size=(slots, C, h, hd)), jnp.float32)
    starts = jnp.asarray([8, 0, 16], jnp.int32)
    pst = PagedState(table, starts)
    got_k = attn_lib.paged_prefill_attention(q, cache, pst, impl="kernel")
    got_g = attn_lib.paged_prefill_attention(q, cache, pst, impl="gather")
    want = paged_prefill_ref(q, cache.k, cache.v, table, starts,
                             k_exp=cache.k_exp, v_exp=cache.v_exp,
                             kv_bits=bits)
    np.testing.assert_allclose(np.asarray(got_k), np.asarray(want),
                               rtol=3e-5, atol=3e-5)
    np.testing.assert_allclose(np.asarray(got_g), np.asarray(want),
                               rtol=3e-5, atol=3e-5)


# ---------------------------------------------------------------------------
# Engine end-to-end at kv_bits < 16
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bits", [8, 4])
def test_engine_kernel_matches_gather_quant(tiny_lm, bits):
    cfg, params = tiny_lm
    out = {}
    for impl in ("gather", "kernel"):
        engine = ServeEngine(cfg, params,
                             EngineConfig(slots=2, max_seq=64, page_size=BS,
                                          paged_impl=impl, kv_bits=bits))
        warm = engine.warmup()
        out[impl] = _serve(engine, cfg)
        assert engine.compile_count() == warm   # quant path is static too
    assert out["kernel"] == out["gather"]


@pytest.mark.parametrize("bits", [8, 4])
def test_engine_cache_on_off_bit_identical_quant(tiny_lm, bits):
    """Prefix reuse stays value-invisible with quantized pools: full-block
    reuse shares payload + exponent (identical writes produced them), and
    partial-block COW is disabled (a donor block's shared exponent would
    leak its suffix into the reused prefix)."""
    cfg, params = tiny_lm
    r = np.random.default_rng(7)
    pre = r.integers(2, cfg.vocab_size, size=40)
    reqs = lambda: [Request(rid=i, prompt=np.concatenate(
        [pre, r2.integers(2, cfg.vocab_size, size=int(r2.integers(2, 9)))]),
        max_new_tokens=4)
        for i, r2 in ((i, np.random.default_rng(100 + i)) for i in range(6))]
    toks = {}
    for on in (False, True):
        engine = ServeEngine(cfg, params,
                             EngineConfig(slots=2, max_seq=128, page_size=BS,
                                          prefill_chunk=16, prefix_cache=on,
                                          kv_bits=bits))
        warm = engine.warmup()
        rs = reqs()
        engine.run(rs)
        assert engine.compile_count() == warm
        toks[on] = {q.rid: q.out_tokens for q in rs}
        if on:
            assert engine.metrics()["prefix_hit_rate"] > 0
    assert toks[True] == toks[False]


def test_engine_quant_grau_epilogue_composes(tiny_lm):
    """KV quantization (storage) composes with the GRAU attention-output
    epilogue (compute): both impls still agree token-for-token."""
    cfg, params = tiny_lm
    g = build_lm_grau("identity", segments=6, num_exponents=8, mode="apot",
                      out_bits=8)
    out = {}
    for impl in ("gather", "kernel"):
        engine = ServeEngine(cfg, params,
                             EngineConfig(slots=2, max_seq=64, page_size=BS,
                                          paged_impl=impl, attn_grau=g,
                                          kv_bits=8))
        engine.warmup()
        out[impl] = _serve(engine, cfg)
    assert out["kernel"] == out["gather"]


def test_engine_quant_under_mesh(tiny_lm):
    """Quantized pools place under a (data, model) mesh — scale planes shard
    alongside payloads — and serve the same tokens as the unsharded engine."""
    cfg, params = tiny_lm
    out = {}
    for mesh in (None, make_serve_mesh(1, 2)):
        engine = ServeEngine(cfg, params,
                             EngineConfig(slots=2, max_seq=64, page_size=BS,
                                          kv_bits=8),
                             mesh=mesh)
        engine.warmup()
        out[mesh is None] = _serve(engine, cfg)
    assert out[True] == out[False]


def test_engine_gather_bytes_shrink(tiny_lm):
    """The acceptance gate, engine-level: int8 pools cut the per-step
    gathered bytes >= 1.8x vs 16-bit pools at the identical decode bucket
    (int4 cuts further), per the trip-count-aware HLO accounting."""
    cfg, params = tiny_lm
    gb = {}
    for bits in (16, 8, 4):
        engine = ServeEngine(cfg, params,
                             EngineConfig(slots=2, max_seq=64, page_size=BS,
                                          kv_bits=bits if bits != 16
                                          else None))
        gb[bits] = engine.decode_cost(
            engine.decode_buckets[-1])["gather_bytes"]
    assert gb[16] / gb[8] >= 1.8
    assert gb[16] / gb[4] > gb[16] / gb[8]


def test_engine_metrics_report_kv_bits(tiny_lm):
    cfg, params = tiny_lm
    engine = ServeEngine(cfg, params,
                         EngineConfig(slots=1, max_seq=32, page_size=BS,
                                      kv_bits=4))
    m = engine.metrics()
    assert m["kv_bits"] == 4 and m["kv_quantized"] is True


# ---------------------------------------------------------------------------
# hwcost: KV memory accounting
# ---------------------------------------------------------------------------

def test_kv_cache_cost_model():
    base = dict(num_layers=4, kv_heads=2, head_dim=32, block_size=16,
                slots=4, max_seq=128)
    r16 = kv_cache_cost(kv_bits=16, **base)
    r8 = kv_cache_cost(kv_bits=8, **base)
    r4 = kv_cache_cost(kv_bits=4, **base)
    assert r16.payload_bytes_per_token_layer == 2 * 2 * 32 * 2   # K+V bf16
    assert r16.scale_bytes_per_token_layer == 0.0
    assert r8.scale_bytes_per_token_layer == 2 * 2 / 16
    # payload halves each step down; scale overhead is amortized tiny
    assert r8.bytes_per_slot < r16.bytes_per_slot / 1.9
    assert r4.bytes_per_slot < r8.bytes_per_slot / 1.9
    assert r4.pool_bytes < r16.pool_bytes / 3.8
    # gather bytes follow live context, not capacity
    short = kv_cache_cost(kv_bits=8, ctx=16, **base)
    assert short.gather_bytes_per_step < r8.gather_bytes_per_step / 7
    with pytest.raises(ValueError, match="kv_bits"):
        kv_cache_cost(kv_bits=5, **base)
