"""Paged KV-cache management for the serving engine.

Storage is a pool of fixed-size blocks per layer (nn/attention.PagedKVCache);
this module owns everything around it: the host-side refcounted block
allocator (admission control + free-list recycling + prefix sharing), pool
construction mirroring lm.init_caches' (group, period-layer, repeats) tree
structure, the prompt / decode-block / chunk-table bucket ladders, and the
copy-on-write pool block copy. (The chunk K/V scatter itself lives with the
attention code: nn/attention.paged_prefill_update.)

Conventions
-----------
* Block 0 is the null/trash block. Unmapped block-table entries are 0, so a
  write routed through them (idle slots during the global decode step, padded
  prefill blocks past a prompt's reservation) lands in scratch storage that no
  reader ever treats as valid.
* Blocks for a request's full lifetime (prompt + max_new_tokens) are reserved
  at admission; a request that cannot reserve waits in the queue. This keeps
  decode free of out-of-block preemption while still letting the pool be
  sized to the workload instead of slots * max_seq.
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.nn.attention import PagedKVCache, QuantPagedKVCache
from repro.quant import kv as kvq
from repro.quant.policy import PrecisionPolicy

NULL_BLOCK = 0

_POOL_TYPES = (PagedKVCache, QuantPagedKVCache)


# ---------------------------------------------------------------------------
# Prompt-length buckets
# ---------------------------------------------------------------------------

def default_buckets(max_len: int, multiple: int = 1,
                    lo: int = 16) -> Tuple[int, ...]:
    """Power-of-two bucket ladder up to max_len, rounded to `multiple`.

    Prefill pads prompts up to the smallest bucket, so the engine compiles at
    most len(buckets) prefill variants and then never recompiles.
    """
    def round_up(n):
        return ((n + multiple - 1) // multiple) * multiple

    buckets = []
    b = lo
    while b < max_len:
        buckets.append(round_up(b))
        b *= 2
    buckets.append(round_up(max_len))
    return tuple(sorted(set(buckets)))


def bucket_for(n: int, buckets: Sequence[int]) -> int:
    for b in buckets:
        if b >= n:
            return b
    raise ValueError(f"length {n} exceeds largest prefill bucket {buckets[-1]}")


def decode_block_buckets(blocks_per_slot: int) -> Tuple[int, ...]:
    """Power-of-two ladder of live-block counts for the decode step.

    The engine traces its decode jit once per bucket (block-table width) and
    each tick runs the smallest bucket covering the longest live sequence, so
    per-step gather/kernel work scales with live context instead of
    `blocks_per_slot` — the decode-side analogue of the prefill buckets.
    """
    buckets = []
    b = 1
    while b < blocks_per_slot:
        buckets.append(b)
        b *= 2
    buckets.append(blocks_per_slot)
    return tuple(sorted(set(buckets)))


def chunk_starts(cached_tokens: int, ctx: int, chunk: int) -> Tuple[int, ...]:
    """Absolute chunk-grid start positions covering [cached_tokens, ctx).

    Chunked prefill always runs on the *absolute* grid (chunk k covers
    positions [k*chunk, (k+1)*chunk)), never on a grid relative to the cached
    prefix: that way cache-on and cache-off admissions execute the exact same
    compiled chunk programs on bit-identical inputs, and prefix reuse only
    decides which grid chunks are skipped. `cached_tokens` must sit on the
    grid (the engine rounds reuse down to a chunk multiple).
    """
    if cached_tokens % chunk:
        raise ValueError(f"cached prefix {cached_tokens} off the chunk grid "
                         f"(chunk={chunk})")
    return tuple(range(cached_tokens, max(ctx, cached_tokens), chunk))


def chunk_table_width(p0: int, chunk: int, block_size: int,
                      buckets: Sequence[int]) -> int:
    """Block-table width for the chunk starting at `p0`: the smallest bucket
    covering prefix + chunk. A pure function of the grid position (never of
    how much prefix was cached), so the set of traced chunk programs — and
    each position's compiled computation — is identical with and without
    prefix caching."""
    return bucket_for(blocks_for(p0 + chunk, block_size), buckets)


# ---------------------------------------------------------------------------
# Host-side block allocator
# ---------------------------------------------------------------------------

def blocks_for(tokens: int, block_size: int) -> int:
    return max(1, math.ceil(tokens / block_size))


class BlockAllocator:
    """Refcounted free-list allocator over the pool's block ids.

    Block 0 (the null/trash block) is reserved and never handed out. Blocks
    come back refcount 1 from `alloc`; prefix sharing (serve/radix_cache.py)
    takes extra references with `incref`, and `free` *decrements* — a block
    returns to the free list only when its last holder lets go.

    Every transition is guarded: freeing a block that is not currently
    allocated (double-free, never-allocated id, out-of-range id, the null
    block) raises instead of silently appending to the free list — the
    failure mode that corrupted the free list was a block appearing twice
    and then being handed to two slots at once.
    """

    def __init__(self, num_blocks: int):
        if num_blocks < 2:
            raise ValueError("need >= 2 blocks (block 0 is the null block)")
        self.num_blocks = num_blocks
        self._free: List[int] = list(range(num_blocks - 1, 0, -1))
        self._refs: Dict[int, int] = {}     # live block id -> refcount

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def live_blocks(self) -> int:
        return len(self._refs)

    @property
    def shared_blocks(self) -> int:
        """Live blocks held by more than one owner (prefix sharing)."""
        return sum(1 for r in self._refs.values() if r > 1)

    def live_block_ids(self) -> List[int]:
        """Snapshot of currently allocated block ids — the telemetry
        reachability check compares this against what slots and the radix
        cache can actually account for (anything left over is a refcount
        leak)."""
        return list(self._refs)

    def can_alloc(self, n: int) -> bool:
        return n <= len(self._free)

    def alloc(self, n: int) -> Optional[List[int]]:
        if not self.can_alloc(n):
            return None
        taken = [self._free.pop() for _ in range(n)]
        for b in taken:
            self._refs[b] = 1
        return taken

    def refcount(self, block: int) -> int:
        return self._refs.get(block, 0)

    def incref(self, blocks: Sequence[int]) -> None:
        """Take an extra reference on already-allocated blocks (prefix
        sharing: a slot pinning cached blocks, the radix cache retaining a
        retired request's prefix)."""
        for b in blocks:
            if b not in self._refs:
                raise ValueError(f"incref of unallocated block {b}")
            self._refs[b] += 1

    def free(self, blocks: Sequence[int]) -> None:
        """Drop one reference per block; recycle at refcount zero.

        Raises ValueError on the null block, out-of-range ids, and blocks
        that are not currently allocated (double-free / never-allocated).
        """
        for b in blocks:
            if b == NULL_BLOCK:
                raise ValueError("free of the null block (never allocated)")
            if not (0 < b < self.num_blocks):
                raise ValueError(f"free of out-of-range block id {b} "
                                 f"(pool has {self.num_blocks} blocks)")
            refs = self._refs.get(b)
            if refs is None:
                raise ValueError(f"double-free (or never-allocated) block {b}")
            if refs > 1:
                self._refs[b] = refs - 1
            else:
                del self._refs[b]
                self._free.append(b)


# ---------------------------------------------------------------------------
# Pool construction
# ---------------------------------------------------------------------------

def paged_supported(cfg: ModelConfig) -> bool:
    """Paged serving covers plain GQA/MHA decoders. Recurrent state (SSM) has
    no seq axis to page; MLA latent and cross-attn caches keep the dense path."""
    if cfg.mla is not None or cfg.encoder is not None:
        return False
    return all(spec.kind == "attn" and not spec.cross_attn
               for period, _ in cfg.groups for spec in period)


def pool_blocks(slots: int, max_seq: int, block_size: int) -> int:
    """Default pool size: every slot can hold max_seq tokens, + null block."""
    return slots * blocks_for(max_seq, block_size) + 1


def validate_pool_packing(cfg: ModelConfig, block_size: int,
                          bits: int, layer: str = "") -> None:
    """Eager packing validation: every assumption the packed layout makes is
    checked at pool-construction time with a pointed message, instead of
    surfacing as an opaque reshape failure inside the first traced chunk."""
    where = f" ({layer})" if layer else ""
    kvq.validate_kv_bits(bits)
    if block_size < 1:
        raise ValueError(f"block_size must be >= 1, got {block_size}")
    try:
        kvq.packed_head_dim(cfg.head_dim, bits)   # odd head_dim at 4-bit
    except ValueError as e:
        raise ValueError(f"{cfg.name}{where}: {e}") from None


def kv_bits_by_layer(cfg: ModelConfig,
                     policy: Optional[PrecisionPolicy]) -> Tuple[Tuple[int, ...], ...]:
    """Per-layer KV bit assignment from the policy (16 everywhere when None).
    Layer names follow the cache tree: ``group{gi}.l{li}``."""
    return tuple(
        tuple(policy.kv_bits_for(f"group{gi}.l{li}") if policy else 16
              for li in range(len(period)))
        for gi, (period, _) in enumerate(cfg.groups))


def init_paged_caches(cfg: ModelConfig, num_blocks: int, block_size: int, *,
                      dtype=jnp.bfloat16,
                      policy: Optional[PrecisionPolicy] = None):
    """Pool tree with lm.init_caches' structure: a tuple per group of
    per-period-layer leaves, each stacked over the group's repeats.

    Per-layer storage follows the PrecisionPolicy's ``kv_bits_for``: 16-bit
    layers keep plain float PagedKVCache pools in `dtype`; 8/4-bit layers
    get QuantPagedKVCache — packed int8 payloads (half-width head_dim at
    4-bit) plus per-(block, head) power-of-two scale-exponent planes,
    initialized to quant/kv.EXP_EMPTY so the first write into a block always
    sets the scale.  Packing assumptions are validated eagerly here.
    """
    assert paged_supported(cfg), f"{cfg.name}: arch not pageable"
    kvh, hd = cfg.kv_heads_phys, cfg.head_dim
    bits_tree = kv_bits_by_layer(cfg, policy)
    caches = []
    for gi, (period, repeats) in enumerate(cfg.groups):
        per_layer = []
        for li in range(len(period)):
            bits = bits_tree[gi][li]
            validate_pool_packing(cfg, block_size, bits,
                                  layer=f"group{gi}.l{li}")
            if bits == 16:
                per_layer.append(PagedKVCache(
                    k=jnp.zeros((repeats, num_blocks, kvh, block_size, hd),
                                dtype),
                    v=jnp.zeros((repeats, num_blocks, kvh, block_size, hd),
                                dtype),
                ))
                continue
            hdp = kvq.packed_head_dim(hd, bits)
            per_layer.append(QuantPagedKVCache(
                k=jnp.zeros((repeats, num_blocks, kvh, block_size, hdp),
                            jnp.int8),
                v=jnp.zeros((repeats, num_blocks, kvh, block_size, hdp),
                            jnp.int8),
                k_exp=jnp.full((repeats, num_blocks, kvh), kvq.EXP_EMPTY,
                               jnp.int8),
                v_exp=jnp.full((repeats, num_blocks, kvh), kvq.EXP_EMPTY,
                               jnp.int8),
                bits=bits,
            ))
        caches.append(tuple(per_layer))
    return tuple(caches)


# ---------------------------------------------------------------------------
# Pool block ops
# ---------------------------------------------------------------------------

def copy_pool_block(pools, src: jax.Array, dst: jax.Array):
    """Copy one block (every layer's K and V) from pool id `src` to `dst`.

    The copy-on-write step for partial-block prefix reuse: a cached block
    whose leading tokens match the new prompt is duplicated into a
    slot-private block before decode starts writing into it, so the shared
    cached copy is never mutated. `src`/`dst` are traced scalars — one jit
    trace covers every copy.

    Quantized pools copy payload *and* scale metadata together: the exponent
    planes have the same (stack, block, ...) leading layout as the payloads,
    so the one generic block-axis copy moves both.
    """
    def one(pool):
        assert isinstance(pool, _POOL_TYPES)

        def cp(buf):
            blk = jax.lax.dynamic_slice(
                buf, (0, src) + (0,) * (buf.ndim - 2),
                (buf.shape[0], 1) + buf.shape[2:])
            return jax.lax.dynamic_update_slice(
                buf, blk, (0, dst) + (0,) * (buf.ndim - 2))

        if isinstance(pool, QuantPagedKVCache):
            return QuantPagedKVCache(cp(pool.k), cp(pool.v), cp(pool.k_exp),
                                     cp(pool.v_exp), bits=pool.bits)
        return PagedKVCache(cp(pool.k), cp(pool.v))

    return jax.tree.map(one, pools,
                        is_leaf=lambda c: isinstance(c, _POOL_TYPES))


def scrub_pool_block(pools, blk: jax.Array):
    """Zero one block (every layer's K and V) in place of its current
    contents — the numeric-quarantine validation step before a block that
    may hold NaN/Inf payloads goes back to the allocator.

    Freeing alone would be unsound: a recycled block's stale payload is
    normally harmless (dead positions are masked by context length), but
    the paged-attention kernel still *reads* the bytes, and NaN propagates
    through `0 * NaN` in the masked softmax path on some backends. Copying
    from the null block is no better — ghost-active slots write real
    (possibly poisoned) values there. So quarantine scrubs: float pools to
    0, quant pools to zero payload + EXP_EMPTY exponents (the
    "never-written" scale state, so the first real write re-arms the
    scale). `blk` is a traced scalar — one jit trace covers every scrub.
    """
    def one(pool):
        assert isinstance(pool, _POOL_TYPES)

        def zero(buf, fill=0):
            blank = jnp.full((buf.shape[0], 1) + buf.shape[2:], fill,
                             buf.dtype)
            return jax.lax.dynamic_update_slice(
                buf, blank, (0, blk) + (0,) * (buf.ndim - 2))

        if isinstance(pool, QuantPagedKVCache):
            return QuantPagedKVCache(
                zero(pool.k), zero(pool.v),
                zero(pool.k_exp, kvq.EXP_EMPTY),
                zero(pool.v_exp, kvq.EXP_EMPTY), bits=pool.bits)
        return PagedKVCache(zero(pool.k), zero(pool.v))

    return jax.tree.map(one, pools,
                        is_leaf=lambda c: isinstance(c, _POOL_TYPES))
