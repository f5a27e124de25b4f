"""Radix-tree prefix caching + chunked prefill.

The invariant under test everywhere: prefix reuse only *skips* work. Because
paged prefill always runs on the absolute chunk grid (same chunk programs,
same chunk-table buckets, regardless of how much prefix was cached),
cache-on and cache-off admissions must produce bit-identical token streams
AND bit-identical pool contents — in float and GRAU modes, under eviction
churn, block free-then-reuse, copy-on-write partial-block divergence, and
the CI device-mesh matrix.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.archs import get_config
from repro.launch.mesh import make_serve_mesh
from repro.models import lm
from repro.models.config import GRAUConfig
from repro.nn.common import build_lm_grau
from repro.serve import kv_cache as kvc
from repro.serve.engine import EngineConfig, Request, ServeEngine
from repro.serve.radix_cache import RadixCache
from repro.serve.sampling import SamplingParams

CFG = get_config("llama3.2-3b", smoke=True)


@pytest.fixture(scope="module")
def params():
    p, _ = lm.init_lm(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)
    return p


def shared_prefix_requests(n, *, prefix_len=70, n_prefixes=2, max_new=4,
                           seed=3, sampling=SamplingParams(), tail=(2, 12)):
    rng = np.random.default_rng(seed)
    prefixes = [rng.integers(2, CFG.vocab_size, size=prefix_len)
                for _ in range(n_prefixes)]
    reqs = []
    for i in range(n):
        pre = prefixes[int(rng.integers(0, n_prefixes))]
        t = rng.integers(2, CFG.vocab_size,
                         size=int(rng.integers(tail[0], tail[1])))
        reqs.append(Request(rid=i, prompt=np.concatenate([pre, t]),
                            max_new_tokens=max_new, sampling=sampling))
    return reqs


def ecfg(prefix_cache, **kw):
    base = dict(slots=2, max_seq=128, page_size=8, prefill_chunk=16,
                prefix_cache=prefix_cache)
    base.update(kw)
    return EngineConfig(**base)


# ---------------------------------------------------------------------------
# BlockAllocator: refcounts and the double-free guard (regression)
# ---------------------------------------------------------------------------

def test_allocator_double_free_raises():
    alloc = kvc.BlockAllocator(8)
    a = alloc.alloc(3)
    alloc.free(a)
    with pytest.raises(ValueError, match="double-free"):
        alloc.free([a[0]])
    # the failed free must not have corrupted the free list
    assert alloc.free_blocks == 7
    assert sorted(alloc.alloc(7)) == list(range(1, 8))


def test_allocator_rejects_bogus_ids():
    alloc = kvc.BlockAllocator(8)
    with pytest.raises(ValueError, match="never-allocated"):
        alloc.free([3])                      # never allocated
    with pytest.raises(ValueError, match="null block"):
        alloc.free([kvc.NULL_BLOCK])
    with pytest.raises(ValueError, match="out-of-range"):
        alloc.free([99])
    with pytest.raises(ValueError, match="out-of-range"):
        alloc.free([-1])
    assert alloc.free_blocks == 7            # nothing leaked into the list


def test_allocator_refcounts_share_and_release():
    alloc = kvc.BlockAllocator(8)
    (b,) = alloc.alloc(1)
    alloc.incref([b])                        # second holder
    alloc.free([b])                          # first drop: still live
    assert alloc.refcount(b) == 1
    assert alloc.free_blocks == 6
    alloc.free([b])                          # last drop: recycled
    assert alloc.refcount(b) == 0
    assert alloc.free_blocks == 7
    with pytest.raises(ValueError):
        alloc.free([b])                      # third drop is a double-free
    with pytest.raises(ValueError, match="unallocated"):
        alloc.incref([b])


# ---------------------------------------------------------------------------
# RadixCache unit behaviour
# ---------------------------------------------------------------------------

def _cache(num_blocks=32, bs=4):
    alloc = kvc.BlockAllocator(num_blocks)
    return RadixCache(alloc, bs), alloc


def test_radix_match_is_block_aligned_and_token_exact():
    cache, alloc = _cache()
    toks = np.arange(100, 112)               # 3 full 4-token blocks
    blocks = alloc.alloc(3)
    cache.insert(toks, blocks)
    m = cache.match(toks)
    assert m.tokens_matched == 12 and m.blocks == blocks
    # a mid-block token flip kills that block and everything after it
    bad = toks.copy()
    bad[5] = 9
    m = cache.match(bad)
    assert m.tokens_matched == 4 and m.blocks == blocks[:1]
    assert cache.match(np.array([1, 2, 3])).tokens_matched == 0


def test_radix_partial_block_cow_probe():
    cache, alloc = _cache()
    toks = np.arange(100, 108)               # 2 full blocks
    blocks = alloc.alloc(2)
    cache.insert(toks, blocks)
    # 1 full block + 2 tokens into the second: COW covers the remainder
    m = cache.match(toks[:6])
    assert m.tokens_matched == 4
    assert m.cow_src == blocks[1] and m.cow_tokens == 2
    # diverging inside the partial block: no COW source
    bad = toks[:6].copy()
    bad[5] = 9
    assert cache.match(bad).cow_src is None


def test_radix_insert_shares_and_refcounts():
    cache, alloc = _cache()
    toks = np.arange(50, 58)
    blocks = alloc.alloc(2)
    cache.insert(toks, blocks)
    assert all(alloc.refcount(b) == 2 for b in blocks)   # owner + cache
    # a second identical insert keeps the existing nodes (no double ref,
    # no new nodes)
    dup = alloc.alloc(2)
    before = cache.num_nodes()
    _, walked = cache.insert(toks, dup)
    assert cache.num_nodes() == before and len(walked) == 2
    assert all(alloc.refcount(b) == 2 for b in blocks)
    assert all(alloc.refcount(b) == 1 for b in dup)


def test_radix_incremental_insert_resumes_from_cursor():
    """Chunk-by-chunk publish: extending from the previous deepest node
    builds the same trie as one root walk, and the pinned cursor chain
    survives eviction pressure."""
    cache, alloc = _cache()
    toks = np.arange(200, 212)               # 3 blocks
    blocks = alloc.alloc(3)
    tail, w1 = cache.insert(toks[:4], blocks[:1])
    tail, w2 = cache.insert(toks[4:], blocks[1:], node=tail)
    assert len(w1) + len(w2) == 3
    cache.pin(w1 + w2)
    m = cache.match(toks)
    assert m.tokens_matched == 12 and m.blocks == blocks
    alloc.free(blocks)                       # cache-only now, but pinned
    assert cache.evictable_blocks() == 0
    cache.evict(99)
    assert cache.match(toks).tokens_matched == 12
    cache.unpin(w1 + w2)
    cache.evict(99)
    assert cache.match(toks).tokens_matched == 0


def test_radix_lru_eviction_skips_pinned():
    cache, alloc = _cache(num_blocks=8, bs=4)
    a = alloc.alloc(2)
    cache.insert(np.arange(0, 8), a)
    b = alloc.alloc(2)
    cache.insert(np.arange(100, 108), b)
    alloc.free(a)
    alloc.free(b)                            # both chains now cache-only
    assert alloc.free_blocks == 3
    m = cache.match(np.arange(100, 108))     # pin the fresher chain
    cache.pin(m.nodes)
    assert cache.evictable_blocks() == 2     # only the unpinned chain
    cache.evict(7)                           # ask for more than evictable
    assert alloc.free_blocks == 5            # chain `a` gone, `b` survives
    assert cache.match(np.arange(0, 8)).tokens_matched == 0
    assert cache.match(np.arange(100, 108)).tokens_matched == 8
    cache.unpin(m.nodes)
    cache.evict(7)
    assert alloc.free_blocks == 7
    assert cache.evictions == 4


def test_radix_deep_chain_walks_do_not_recurse():
    """Long-context prompts build trie chains thousands of nodes deep;
    every traversal must be iterative (a recursive walk dies at Python's
    default recursion limit around depth 1000)."""
    alloc = kvc.BlockAllocator(2002)
    cache = RadixCache(alloc, 1)               # 1-token blocks: depth = len
    toks = np.arange(2000) % 7
    blocks = alloc.alloc(2000)
    tail, walked = cache.insert(toks, blocks)
    assert len(walked) == 2000
    alloc.free(blocks)                         # cache-only chain
    assert cache.evictable_blocks() == 2000
    assert cache.num_nodes() == 2000
    assert cache.match(toks).tokens_matched == 2000
    assert cache.evict(2001) == 2000           # leaf-first teardown
    assert alloc.free_blocks == 2001


def test_prefill_chunk_auto_adapts_to_page_size(params):
    """The default chunk must work for any valid page size, not just the
    small ones: page_size=64 engines used to be constructible and must
    stay so without the caller touching prefill_chunk."""
    eng = ServeEngine(CFG, params, EngineConfig(slots=1, max_seq=256,
                                                page_size=64))
    assert eng.prefill_chunk == 64
    eng = ServeEngine(CFG, params, EngineConfig(slots=1, max_seq=256,
                                                page_size=16))
    assert eng.prefill_chunk == 32


def test_chunk_grid_coverage_and_warmup_widths(params):
    """The absolute chunk grid underwrites the bit-exactness story: every
    reachable (ctx, cached) pair must decompose into grid chunks whose
    table widths all sit in the engine's warmed set — so organic traffic
    (hits, misses, partial reuse) can never reach an untraced width."""
    for max_seq, page, chunk in [(64, 8, 16), (128, 8, 32), (256, 16, 32)]:
        eng = ServeEngine(CFG, params, EngineConfig(
            slots=1, max_seq=max_seq, page_size=page, prefill_chunk=chunk))
        widths = set()
        for ctx in range(1, max_seq):
            for cached in range(0, ctx + 1, chunk):
                for p0 in kvc.chunk_starts(cached, ctx, chunk):
                    assert p0 % chunk == 0                  # on the grid
                    widths.add(kvc.chunk_table_width(
                        p0, chunk, page, eng.chunk_buckets))
        assert widths == set(eng.chunk_widths)   # warmup covers exactly these
    with pytest.raises(ValueError, match="grid"):
        kvc.chunk_starts(8, 64, 16)              # off-grid cached prefix


# ---------------------------------------------------------------------------
# Engine: cache-on == cache-off, bit for bit
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sampling", [
    SamplingParams(),
    SamplingParams(temperature=0.8, top_k=40, top_p=0.9),
], ids=["greedy", "sampled"])
def test_cache_on_off_streams_bit_identical(params, sampling):
    """Randomized shared-prefix workload: enabling the radix cache must not
    change a single token, greedy or sampled."""
    out = {}
    for on in (False, True):
        eng = ServeEngine(CFG, params, ecfg(on, seed=5))
        reqs = shared_prefix_requests(8, sampling=sampling)
        eng.run(reqs)
        out[on] = {r.rid: tuple(r.out_tokens) for r in reqs}
    assert out[True] == out[False]


def _slot_prefix_views(engine, slot, ctx):
    """Dense (reps, ctx, kvh, hd) views of one slot's prompt-context KV,
    gathered bitwise from the pool through the live table."""
    row = engine.block_table[slot, :engine.blocks_per_slot]
    views = []
    for leaf in jax.tree.leaves(engine.caches):
        arr = np.asarray(leaf)                       # (reps, nb, kvh, bs, hd)
        reps, _, kvh, bs, hd = arr.shape
        dense = (arr[:, row].transpose(0, 1, 3, 2, 4)
                 .reshape(reps, -1, kvh, hd))
        views.append(dense[:, :ctx])
    return views


def test_cache_on_off_pool_contents_bit_identical(params):
    """Freeze both engines right after every admission finished prefilling
    (decode still running) and compare each slot's prompt-context KV region
    gathered from the pool: reused blocks must hold byte-for-byte what the
    cache-off engine recomputed."""
    engines, reqs = {}, {}
    for on in (False, True):
        eng = ServeEngine(CFG, params, ecfg(on))
        rq = shared_prefix_requests(2, max_new=40, seed=11, n_prefixes=1)
        for r in rq:
            eng.submit(r)
        for _ in range(30):
            eng.step()
        eng._drain()
        # every slot must be past prefill and still decoding
        assert all(rs is not None and rs.prefill_pos >= rs.prefill_ctx
                   for rs in eng.slot_req)
        engines[on], reqs[on] = eng, rq
    for slot in range(2):
        rs_on = engines[True].slot_req[slot]
        rs_off = engines[False].slot_req[slot]
        assert rs_on.rid == rs_off.rid           # same FCFS slot assignment
        ctx = rs_on.prefill_ctx
        v_on = _slot_prefix_views(engines[True], slot, ctx)
        v_off = _slot_prefix_views(engines[False], slot, ctx)
        for a, b in zip(v_on, v_off):
            np.testing.assert_array_equal(a, b)
    # the second admission must actually have reused the first one's prefix
    assert engines[True].slot_req[1].cached_prefix_tokens > 0


def test_identical_resubmit_skips_prefill(params):
    """Second identical prompt: the whole chunk-grid-aligned context comes
    from the cache and the token stream matches the first run exactly."""
    eng = ServeEngine(CFG, params, ecfg(True, slots=1))
    p = np.random.default_rng(0).integers(2, CFG.vocab_size, size=66)
    r1 = Request(rid=0, prompt=p, max_new_tokens=4)
    eng.run([r1])
    r2 = Request(rid=1, prompt=p, max_new_tokens=4)
    eng.run([r2])
    rs2 = eng.scheduler.finished[-1]
    # ctx=65 -> 64 grid-aligned tokens cached, one suffix chunk computed
    assert rs2.cached_prefix_tokens == 64
    assert rs2.computed_prefill_tokens == 1
    assert r1.out_tokens == r2.out_tokens


def test_cow_partial_block_divergence(params):
    """A shorter prompt sharing a donor's partial block reuses it
    copy-on-write: zero suffix prefill, bit-exact tokens, and the donor's
    cached block survives the borrower's decode writes."""
    eng = ServeEngine(CFG, params, ecfg(True, slots=1))
    donor = np.random.default_rng(1).integers(2, CFG.vocab_size, size=53)
    r1 = Request(rid=0, prompt=donor, max_new_tokens=4)
    eng.run([r1])
    r2 = Request(rid=1, prompt=donor[:44], max_new_tokens=4)  # ctx=43: 5
    eng.run([r2])                       # full blocks + 3 tokens of block 6
    rs2 = eng.scheduler.finished[-1]
    assert rs2.cached_prefix_tokens == 43
    assert rs2.computed_prefill_tokens == 0
    # cache-off oracle for the borrower
    off = ServeEngine(CFG, params, ecfg(False, slots=1))
    r1b = Request(rid=0, prompt=donor, max_new_tokens=4)
    off.run([r1b])
    r2b = Request(rid=1, prompt=donor[:44], max_new_tokens=4)
    off.run([r2b])
    assert r2.out_tokens == r2b.out_tokens
    # the donor's prefix must be uncorrupted by the borrower's decode
    r3 = Request(rid=2, prompt=donor, max_new_tokens=4)
    eng.run([r3])
    assert r3.out_tokens == r1.out_tokens


def test_eviction_churn_and_free_then_reuse(params):
    """Tiny pool + rotating prefixes: admissions must evict cold prefixes,
    recycle their blocks, and still match the cache-off streams exactly."""
    out, evictions = {}, 0
    for on in (False, True):
        eng = ServeEngine(CFG, params, ecfg(on, max_seq=64, num_blocks=17))
        warm = eng.warmup()
        rng = np.random.default_rng(2)
        prefixes = [rng.integers(2, CFG.vocab_size, size=30)
                    for _ in range(4)]
        reqs = [Request(rid=i,
                        prompt=np.concatenate(
                            [prefixes[i % 4],
                             rng.integers(2, CFG.vocab_size, size=3)]),
                        max_new_tokens=3)
                for i in range(12)]
        done = eng.run(reqs)
        assert len(done) == 12
        assert eng.compile_count() == warm
        out[on] = {r.rid: tuple(r.out_tokens) for r in reqs}
        if on:
            evictions = eng.metrics()["evictions"]
            # nothing leaked: cache refs + free list account for every block
            assert (eng.allocator.free_blocks
                    + eng.allocator.live_blocks) == 16
    assert evictions > 0                  # the pool actually churned
    assert out[True] == out[False]


def test_same_tick_overcommit_requeues_instead_of_crashing(params):
    """policy='prefill' picks every admissible request against the *same*
    free+evictable pool before any admission lands; when the later pick no
    longer fits it must be requeued at the head (and served after a
    retirement), never over-allocated."""
    eng = ServeEngine(CFG, params, EngineConfig(
        slots=2, max_seq=64, page_size=8, prefill_chunk=16,
        prefix_cache=True, num_blocks=13, policy="prefill"))
    rng = np.random.default_rng(6)
    # seed the cache with 4 evictable blocks (ctx=32 -> 4 full blocks)
    warm_req = Request(rid=0, prompt=rng.integers(2, CFG.vocab_size,
                                                  size=33),
                       max_new_tokens=3)
    eng.run([warm_req])
    assert eng.allocator.live_blocks == 4       # cache-held, evictable
    # two picks in one tick, each needing 7 of the 12 usable blocks: both
    # pass can_admit (8 free + 4 evictable), only one can actually land
    reqs = [Request(rid=1 + i,
                    prompt=rng.integers(2, CFG.vocab_size, size=50),
                    max_new_tokens=3)
            for i in range(2)]
    done = eng.run(reqs)
    assert len(done) == 2                       # both served, in sequence
    assert all(len(r.out_tokens) == 3 for r in reqs)
    ticks = {rs.rid: rs.admit_tick for rs in eng.scheduler.finished
             if rs.rid > 0}
    assert ticks[1] != ticks[2]                 # the requeued one waited
    # requeued retries must not inflate hit/miss accounting: one committed
    # match per admission (3 admissions with ctx > 0)
    assert eng.radix.hits + eng.radix.misses == 3


def test_chunked_prefill_interleaves_with_decode(params):
    """A long prompt prefills in budgeted chunks across many ticks; a short
    co-batched request must decode to completion in the meantime (TTFT
    protection), and the long request still matches the full forward."""
    eng = ServeEngine(CFG, params, EngineConfig(
        slots=2, max_seq=256, page_size=8, prefill_chunk=16,
        policy="prefill"))
    rng = np.random.default_rng(4)
    long_req = Request(rid=0, prompt=rng.integers(2, CFG.vocab_size,
                                                  size=200),
                       max_new_tokens=2)
    short_req = Request(rid=1, prompt=rng.integers(2, CFG.vocab_size,
                                                   size=5),
                        max_new_tokens=2)
    eng.run([long_req, short_req])
    recs = {rs.rid: rs for rs in eng.scheduler.finished}
    assert recs[0].admit_tick == 0 and recs[1].admit_tick == 0
    # both admitted together, but the short request runs to *completion*
    # while the long prompt is still working through its 13 chunk grants
    assert [rs.rid for rs in eng.scheduler.finished] == [1, 0]
    assert recs[1].finish_time < recs[0].first_token_time
    assert len(long_req.out_tokens) == 2 and len(short_req.out_tokens) == 2


def test_prefix_cache_no_recompiles_after_warmup(params):
    """Hits, misses, COW copies, and evictions all reuse warmed traces."""
    eng = ServeEngine(CFG, params, ecfg(True, num_blocks=33))
    warm = eng.warmup()
    eng.run(shared_prefix_requests(6, seed=21))
    eng.run(shared_prefix_requests(6, seed=22, prefix_len=40))
    assert eng.compile_count() == warm


def test_prefix_cache_metrics_exposed(params):
    eng = ServeEngine(CFG, params, ecfg(True))
    eng.run(shared_prefix_requests(6))
    m = eng.metrics()
    assert m["prefix_cache"] is True
    assert m["cached_prefix_tokens"] > 0
    assert 0.0 < m["prefix_hit_rate"] < 1.0
    assert m["evictions"] == 0
    assert m["prefix_cache_hits"] > 0
    assert m["cached_prefix_tokens_per_request"] > 0
    off = ServeEngine(CFG, params, ecfg(False))
    off.run(shared_prefix_requests(6))
    mo = off.metrics()
    assert mo["prefix_hit_rate"] == 0.0 and mo["cached_prefix_tokens"] == 0


def test_prefix_cache_requires_paged(params):
    with pytest.raises(ValueError, match="paged"):
        ServeEngine(CFG, params, EngineConfig(slots=1, max_seq=64,
                                              paged=False, prefix_cache=True))
    with pytest.raises(ValueError, match="prefill_chunk"):
        ServeEngine(CFG, params, EngineConfig(slots=1, max_seq=64,
                                              page_size=16, prefill_chunk=24))
    with pytest.raises(ValueError, match="budget"):
        ServeEngine(CFG, params, EngineConfig(slots=1, max_seq=64,
                                              prefill_chunk=32,
                                              prefill_token_budget=16))


# ---------------------------------------------------------------------------
# GRAU modes: quantized streams stay bit-identical across reuse
# ---------------------------------------------------------------------------

def test_cache_on_off_bit_identical_grau_activation():
    """cfg.grau (QAT surrogate activations): integer activation math makes
    the on/off comparison exact by construction — and it must stay exact."""
    cfg = CFG.replace(grau=GRAUConfig())
    params, _ = lm.init_lm(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    out = {}
    for on in (False, True):
        eng = ServeEngine(cfg, params, ecfg(on))
        reqs = shared_prefix_requests(6, seed=31)
        eng.run(reqs)
        out[on] = {r.rid: tuple(r.out_tokens) for r in reqs}
    assert out[True] == out[False]


def test_cache_on_off_bit_identical_attn_grau_epilogue(params):
    """The fused GRAU attention-output epilogue runs in both the decode and
    the chunk-prefill attention; cached blocks must reproduce its quantized
    stream exactly."""
    g = build_lm_grau("identity", segments=6, num_exponents=8, mode="apot",
                      out_bits=8)
    out = {}
    for on in (False, True):
        eng = ServeEngine(CFG, params, ecfg(on, attn_grau=g))
        reqs = shared_prefix_requests(6, seed=41)
        eng.run(reqs)
        out[on] = {r.rid: tuple(r.out_tokens) for r in reqs}
    assert out[True] == out[False]


# ---------------------------------------------------------------------------
# Device-mesh matrix: reuse is placement-invariant
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("data,model", [(1, 2), (2, 2), (4, 1)])
def test_prefix_cache_under_mesh_matches_single_device(data, model, params):
    if jax.device_count() < data * model:
        pytest.skip(f"needs {data * model} devices")
    mesh = make_serve_mesh(data, model)
    cfg_on = ecfg(True, max_seq=64)
    base = ServeEngine(CFG, params, cfg_on)
    reqs = shared_prefix_requests(6, prefix_len=40, seed=51)
    base.run(reqs)
    base_toks = {r.rid: tuple(r.out_tokens) for r in reqs}

    sharded = ServeEngine(CFG, params, cfg_on, mesh=mesh)
    reqs2 = shared_prefix_requests(6, prefix_len=40, seed=51)
    sharded.run(reqs2)
    assert {r.rid: tuple(r.out_tokens) for r in reqs2} == base_toks
    # identical admissions => identical allocator state and hit accounting
    assert sharded.metrics()["cached_prefix_tokens"] == \
        base.metrics()["cached_prefix_tokens"]
    assert sharded.allocator.free_blocks == base.allocator.free_blocks

    # and under the mesh, reuse is still invisible vs cache-off
    off = ServeEngine(CFG, params, ecfg(False, max_seq=64), mesh=mesh)
    reqs3 = shared_prefix_requests(6, prefix_len=40, seed=51)
    off.run(reqs3)
    assert {r.rid: tuple(r.out_tokens) for r in reqs3} == base_toks
