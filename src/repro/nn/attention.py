"""Attention: chunked (flash-style) training/prefill + cached decode; GQA & MLA.

Memory discipline: logits are never materialized at (seq, seq). Training and
prefill run a lax.scan over query chunks with an inner scan over KV chunks
maintaining online-softmax accumulators (m, l, o) — the standard flash
recurrence, expressed in pure JAX so XLA keeps the working set at
(q_chunk x kv_chunk) per step. This is what makes the 32k-prefill dry-run
cells compile with sane memory footprints.

Decode attends one query position against the whole cache in one shot; for
sequence-sharded caches (long_500k) the contraction over the sharded seq axis
lowers to a psum — flash-decoding-style partial reduction, for free via GSPMD.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro.nn import shard_ctx
from repro.quant import kv as kvq

NEG_INF = -1e30


def _largest_divisor(n: int, cap: int) -> int:
    """Largest divisor of n that is <= cap (chunk sizes must tile the seq)."""
    cap = min(cap, n)
    for c in range(cap, 0, -1):
        if n % c == 0:
            return c
    return 1


class KVCache(NamedTuple):
    k: jax.Array          # (batch, max_seq, kv_heads, head_dim)
    v: jax.Array          # (batch, max_seq, kv_heads, head_dim)
    length: jax.Array     # (batch,) int32 — filled prefix length


class CrossKV(NamedTuple):
    """Cached cross-attention K/V (enc-dec): computed once at admission from
    the encoder output instead of per decode step (whisper decode was
    measured at useful-FLOPs ratio 0.01 without it)."""
    k: jax.Array          # (batch, frames, kv_heads, head_dim)
    v: jax.Array


def _chunk_attend(q, k, v, *, q_offset, kv_offset, causal, scale):
    """One (q_chunk, kv_chunk) tile: returns (scores_max, exp_sums, out_part).

    q: (b, qc, h, d); k/v: (b, kc, kvh, d) with h = kvh * groups.

    The causal mask is applied as a small additive (qc, kc) bias rather than a
    full-logits-shape where(): a broadcasted pred at logits shape gets
    loop-hoisted by XLA across both chunk scans into an O(nq*nk*b*h*qc*kc)
    buffer (observed in the dry-run HLO) — the 2-D additive form keeps the
    hoisted object at O(nq*nk*qc*kc).
    """
    b, qc, h, d = q.shape
    kvh = k.shape[2]
    g = h // kvh
    qg = q.reshape(b, qc, kvh, g, d)
    logits = jnp.einsum("bqkgd,bskd->bkgqs", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    # Pin the tile sharding (q-chunk rows follow the "seq" rule). The
    # constraint transposes onto the cotangent, which keeps the attention
    # backward from all-gathering full p-tiles (measured 7.2e11 B/step).
    logits = shard_ctx.constrain(logits, "batch", "kv_heads", None, "seq", None)
    if causal:
        qpos = q_offset + jnp.arange(qc)
        kpos = kv_offset + jnp.arange(k.shape[1])
        bias = jnp.where(qpos[:, None] >= kpos[None, :], 0.0, NEG_INF)  # (qc,kc)
        logits = logits + bias
    m = jnp.max(logits, axis=-1)                                   # (b,k,g,q)
    p = jnp.exp(logits - m[..., None])
    l = jnp.sum(p, axis=-1)                                        # (b,k,g,q)
    o = jnp.einsum("bkgqs,bskd->bkgqd", p, v.astype(jnp.float32))  # (b,k,g,q,d)
    return m, l, o


def chunked_attention(
    q: jax.Array,                     # (b, s_q, h, d)
    k: jax.Array,                     # (b, s_kv, kvh, d)
    v: jax.Array,
    *,
    causal: bool = True,
    q_chunk: int = 1024,
    kv_chunk: int = 1024,
    q_offset: int = 0,
    scale: Optional[float] = None,
) -> jax.Array:
    b, s_q, h, d = q.shape
    s_kv, kvh = k.shape[1], k.shape[2]
    g = h // kvh
    scale = scale if scale is not None else d ** -0.5
    q_chunk = _largest_divisor(s_q, q_chunk)
    kv_chunk = _largest_divisor(s_kv, kv_chunk)
    nq, nk = s_q // q_chunk, s_kv // kv_chunk

    q = shard_ctx.constrain(q, "batch", "seq", "heads", None)
    k = shard_ctx.constrain(k, "batch", "seq", "kv_heads", None)
    v = shard_ctx.constrain(v, "batch", "seq", "kv_heads", None)

    qs = q.reshape(b, nq, q_chunk, h, d).transpose(1, 0, 2, 3, 4)
    ks = k.reshape(b, nk, kv_chunk, kvh, d).transpose(1, 0, 2, 3, 4)
    vs = v.reshape(b, nk, kv_chunk, kvh, d).transpose(1, 0, 2, 3, 4)

    def q_step(_, qc_and_i):
        qc, iq = qc_and_i
        m0 = jnp.full((b, kvh, g, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, kvh, g, q_chunk), jnp.float32)
        o0 = jnp.zeros((b, kvh, g, q_chunk, d), jnp.float32)
        m0 = shard_ctx.constrain(m0, "batch", "kv_heads", None, "seq")
        l0 = shard_ctx.constrain(l0, "batch", "kv_heads", None, "seq")
        o0 = shard_ctx.constrain(o0, "batch", "kv_heads", None, "seq", None)

        def kv_step(carry, kv_and_j):
            m, l, o = carry
            (kc, vc), jk = kv_and_j
            mj, lj, oj = _chunk_attend(
                qc, kc, vc,
                q_offset=q_offset + iq * q_chunk,
                kv_offset=jk * kv_chunk, causal=causal, scale=scale,
            )
            m_new = jnp.maximum(m, mj)
            a = jnp.exp(m - m_new)
            bfac = jnp.exp(mj - m_new)
            l_new = l * a + lj * bfac
            o_new = o * a[..., None] + oj * bfac[..., None]
            return (m_new, l_new, o_new), None

        (m, l, o), _ = jax.lax.scan(
            kv_step, (m0, l0, o0), ((ks, vs), jnp.arange(nk)))
        out = o / jnp.maximum(l[..., None], 1e-30)       # (b,kvh,g,qc,d)
        out = out.transpose(0, 3, 1, 2, 4).reshape(b, q_chunk, h, d)
        return None, out

    _, outs = jax.lax.scan(q_step, None, (qs, jnp.arange(nq)))
    out = outs.transpose(1, 0, 2, 3, 4).reshape(b, s_q, h, d)
    return out.astype(q.dtype)


def decode_attention(
    q: jax.Array,                     # (b, 1, h, d)
    cache: KVCache,
    *,
    scale: Optional[float] = None,
) -> jax.Array:
    """One-token attention over the full cache (masked beyond `length`)."""
    b, _, h, d = q.shape
    kvh = cache.k.shape[2]
    g = h // kvh
    scale = scale if scale is not None else d ** -0.5
    qg = q.reshape(b, kvh, g, d)
    logits = jnp.einsum("bkgd,bskd->bkgs", qg.astype(jnp.float32),
                        cache.k.astype(jnp.float32)) * scale
    pos = jnp.arange(cache.k.shape[1])
    valid = pos[None] < cache.length[:, None]            # (b, s)
    logits = jnp.where(valid[:, None, None], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bkgs,bskd->bkgd", p, cache.v.astype(jnp.float32))
    return o.reshape(b, 1, h, d).astype(q.dtype)


def update_cache(cache: KVCache, k_new: jax.Array, v_new: jax.Array) -> KVCache:
    """Append one position per sequence at index `length` (decode step)."""
    b = k_new.shape[0]

    def upd(buf, new):
        return jax.vmap(
            lambda bbuf, bnew, i: jax.lax.dynamic_update_slice_in_dim(bbuf, bnew, i, axis=0)
        )(buf, new, cache.length)

    return KVCache(upd(cache.k, k_new), upd(cache.v, v_new), cache.length + 1)


# ---------------------------------------------------------------------------
# Paged KV cache (block-pool storage with slot -> block-table indirection)
# ---------------------------------------------------------------------------

class PagedKVCache(NamedTuple):
    """Block-pool KV storage for continuous-batching decode.

    Instead of a dense (slots, max_seq, ...) buffer per layer, K/V live in a
    shared pool of fixed-size blocks; a slot owns only the blocks its sequence
    actually occupies (serve/kv_cache.py manages the allocator). Block 0 is
    reserved as the null/trash block: unmapped block-table entries point at it,
    so writes from idle slots or padded prefill blocks land there harmlessly.
    """
    k: jax.Array          # (num_blocks, kv_heads, block_size, head_dim)
    v: jax.Array


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class QuantPagedKVCache:
    """Quantized block-pool KV storage: packed ints + a scale-exponent plane.

    The quantized sibling of :class:`PagedKVCache` under one PrecisionPolicy
    (quant/policy.py): K/V live as int8 words — one value per byte at
    ``bits=8``, two packed nibbles at ``bits=4`` (quant/kv.py's split-halves
    layout) — and each (block, kv_head) carries one signed-byte power-of-two
    scale exponent per tensor, so dequantization is an exponent add (shift),
    never a float multiply by an arbitrary scale.  ``bits`` is pytree aux
    data: it is static under jit, rides through lax.scan / donation / device
    placement unchanged, and never retraces when values change.

    Write-path ownership of scales (the serving bit-exactness contract):
    exponents are set by whole-block prefill writes and monotonically bumped
    (with a rounding requantization shift of the resident payload) by decode
    writes — both in the shared jnp update paths below, never by a reader.
    """
    k: jax.Array        # (num_blocks, kvh, block_size, packed_hd) int8
    v: jax.Array
    k_exp: jax.Array    # (num_blocks, kvh) int8 power-of-two scale exponents
    v_exp: jax.Array
    bits: int = 8       # static: 8 or 4

    def tree_flatten(self):
        return (self.k, self.v, self.k_exp, self.v_exp), self.bits

    @classmethod
    def tree_unflatten(cls, bits, children):
        return cls(*children, bits=bits)


AnyPagedKVCache = Union[PagedKVCache, QuantPagedKVCache]


class PagedState(NamedTuple):
    """Per-step slot metadata shared by every layer (not part of the pools)."""
    block_table: jax.Array   # (slots, blocks_per_slot) int32; 0 = unmapped
    length: jax.Array        # (slots,) int32 — valid prefix length per slot
    ctx: Optional[jax.Array] = None   # (slots,) int32, chunked prefill only:
    # real context length per row. Quantized pools mask positions >= ctx out
    # of the block-exponent amax so chunk *padding* (garbage K/V past the
    # prompt) can never coarsen the scale real tokens are stored at; decode
    # and the attention masks ignore it (padding is handled by `length`)


def _quant_paged_update(cache: QuantPagedKVCache, k_new, v_new,
                        st: PagedState) -> QuantPagedKVCache:
    """Decode write into a quantized pool: one position per slot.

    The block's scale exponent can only rise: new_e = max(resident_e,
    token_e).  When it rises, the resident payload is requantized by a
    rounding right shift (exact power-of-two regridding) before the new
    position lands — so a block's stored values are always on one grid.
    Scale metadata and payload move together, and identically for every
    schedule that issues the same writes (the cache-on/off invariant).
    """
    bits = cache.bits
    block_size = cache.k.shape[2]
    blk = jnp.take_along_axis(
        st.block_table, (st.length // block_size)[:, None], axis=1)[:, 0]
    off = st.length % block_size

    def upd(buf, exp, new):                       # new: (slots, kvh, hd) f32
        amax = jnp.max(jnp.abs(new.astype(jnp.float32)), axis=-1)
        e_tok = kvq.pot_exponent(amax, bits)      # (slots, kvh)
        e_old = exp[blk]
        e_new = jnp.maximum(e_old, e_tok)
        delta = e_new.astype(jnp.int32) - e_old.astype(jnp.int32)
        resident = buf[blk]                       # (slots, kvh, bs, hdp)
        q = kvq.unpack_int4(resident) if bits == 4 else resident
        q = kvq.requant_shift(q, delta[:, :, None, None], bits)
        qtok = kvq.quantize_pot(new, e_new[..., None], bits)
        q = jax.vmap(
            lambda qb, qt, o: jax.lax.dynamic_update_slice(qb, qt[:, None],
                                                           (0, o, 0))
        )(q, qtok, off)
        payload = kvq.pack_int4(q) if bits == 4 else q
        return buf.at[blk].set(payload), exp.at[blk].set(e_new)

    k, k_exp = upd(cache.k, cache.k_exp, k_new[:, 0])
    v, v_exp = upd(cache.v, cache.v_exp, v_new[:, 0])
    return QuantPagedKVCache(k, v, k_exp, v_exp, bits=bits)


def paged_update(cache: AnyPagedKVCache, k_new: jax.Array, v_new: jax.Array,
                 st: PagedState) -> AnyPagedKVCache:
    """Write one position per slot at logical index `length` via the table."""
    if isinstance(cache, QuantPagedKVCache):
        return _quant_paged_update(cache, k_new, v_new, st)
    block_size = cache.k.shape[2]
    blk = jnp.take_along_axis(
        st.block_table, (st.length // block_size)[:, None], axis=1)[:, 0]
    off = st.length % block_size
    return PagedKVCache(
        k=cache.k.at[blk, :, off].set(k_new[:, 0].astype(cache.k.dtype)),
        v=cache.v.at[blk, :, off].set(v_new[:, 0].astype(cache.v.dtype)),
    )


def paged_view(cache: AnyPagedKVCache, st: PagedState,
               max_blocks: Optional[int] = None) -> Tuple[jax.Array, jax.Array]:
    """Gather each slot's blocks into a dense (slots, logical_seq, ...) view.

    The view is transient (one decode step); persistent storage stays paged.
    Garbage read through null-block entries is masked by `length` downstream.
    With `max_blocks`, only the first `max_blocks` table columns are gathered
    — the engine passes its live-block bucket here, so the view's footprint
    scales with live context instead of slot capacity (the serving engine
    usually pre-slices the table instead; both spellings are equivalent).
    Under a sharding context the gathered view is pinned to the pool's layout
    (kv heads / head_dim on `model`, slots on the data axes) so GSPMD doesn't
    rematerialize the view when the reshape changes the dim structure.

    Quantized pools gather the *packed* payload (int8 words, half-width at
    4-bit) plus the per-(block, head) exponent plane, and only then
    dequantize to an f32 view: the pool-side reads — the HBM traffic that
    scales with context — move at kv_bits width, and roofline/hlo's gather
    accounting sizes them by the gather's own (packed) output, even when
    XLA fuses the dequant into the gather.
    """
    table = (st.block_table if max_blocks is None
             else st.block_table[:, :max_blocks])
    slots, blocks_per_slot = table.shape
    kvh, block_size = cache.k.shape[1], cache.k.shape[2]
    seq = blocks_per_slot * block_size
    quantized = isinstance(cache, QuantPagedKVCache)

    def view(pool, exp):
        dense = pool[table]                       # (slots, nbl, kvh, bs, hd')
        dense = shard_ctx.constrain(dense, "batch", None, "kv_heads", None,
                                    "head_dim")
        if quantized:
            dense = kvq.load_block(dense, exp[table], cache.bits)
        dense = dense.transpose(0, 1, 3, 2, 4).reshape(slots, seq, kvh, -1)
        return shard_ctx.constrain(dense, "batch", None,
                                   "kv_heads", "head_dim")

    if quantized:
        return view(cache.k, cache.k_exp), view(cache.v, cache.v_exp)
    return view(cache.k, None), view(cache.v, None)


class AttnQuant(NamedTuple):
    """GRAU register file + scales for the fused attention-output epilogue.

    `spec` is the unit's register file, `s_in` maps the f32 attention output
    into its int32 MAC domain, `s_out` dequantizes the 8-bit bus back to f32
    for the output projection (serve/engine wires this from a GRAUActivation).
    """
    spec: Any
    s_in: float
    s_out: float


def paged_decode_attention(
    q: jax.Array,                     # (b, 1, h, d)
    cache: AnyPagedKVCache,
    st: PagedState,                   # table possibly bucket-sliced; length =
                                      # positions already written - 1
    *,
    impl: str = "gather",             # "gather" | "kernel"
    quant: Optional[AttnQuant] = None,
    scale: Optional[float] = None,
) -> jax.Array:
    """One-token attention over a slot's mapped blocks (current token already
    written via `paged_update`, hence `st.length + 1` attended positions).

    impl="kernel" runs the Pallas flash-decode kernel
    (kernels/paged_attention.py); impl="gather" is the dense-view fallback and
    differential-test oracle.  Both honor the optional fused GRAU output
    epilogue and return (b, 1, h, d) float (dequantized when quantizing).
    """
    b, _, h, d = q.shape
    lengths = st.length + 1
    if impl == "kernel":
        from repro.kernels import paged_attention as paged_kernel
        quantized = isinstance(cache, QuantPagedKVCache)
        o = paged_kernel.paged_attention(
            q[:, 0], cache.k, cache.v, st.block_table, lengths, scale=scale,
            k_exp=cache.k_exp if quantized else None,
            v_exp=cache.v_exp if quantized else None,
            kv_bits=cache.bits if quantized else 16,
            spec=quant.spec if quant is not None else None,
            s_in=quant.s_in if quant is not None else None)
        if quant is not None:
            o = o.astype(jnp.float32) * quant.s_out
        return o[:, None].astype(q.dtype)
    if impl != "gather":
        raise ValueError(f"unknown paged decode impl {impl!r}")
    kd, vd = paged_view(cache, st)
    o = decode_attention(q, KVCache(kd, vd, lengths), scale=scale)
    if quant is not None:
        from repro.kernels.ref import attn_output_quant
        oq = attn_output_quant(o[:, 0], quant.spec, quant.s_in)
        o = (oq.astype(jnp.float32) * quant.s_out)[:, None].astype(q.dtype)
    return o


def paged_prefill_update(cache: AnyPagedKVCache, k_new: jax.Array,
                         v_new: jax.Array, st: PagedState) -> AnyPagedKVCache:
    """Scatter one prefill chunk's K/V into the pool through the table.

    k_new/v_new: (b, C, kvh, hd) with C a block multiple; st.length holds
    each row's block-aligned chunk start, so the chunk occupies table columns
    start//bs .. start//bs + C//bs - 1. Columns past a slot's reservation are
    NULL_BLOCK and land in trash, like every other unmapped write.

    Quantized pools *set* (never bump) each written block's scale exponent:
    a chunk on the absolute grid always covers whole blocks, so the block's
    entire payload and its exponent are one atomic function of the chunk's
    f32 K/V — identical for every schedule that runs the chunk (the prefix
    cache's bit-exactness relies on this).
    """
    block_size = cache.k.shape[2]
    b, chunk = k_new.shape[0], k_new.shape[1]
    assert chunk % block_size == 0, (chunk, block_size)
    quantized = isinstance(cache, QuantPagedKVCache)
    k, v = cache.k, cache.v
    k_exp = cache.k_exp if quantized else None
    v_exp = cache.v_exp if quantized else None
    for i in range(b):
        base = st.length[i] // block_size
        for j in range(chunk // block_size):
            blk = st.block_table[i, base + j]
            sl = slice(j * block_size, (j + 1) * block_size)
            kb = k_new[i, sl].swapaxes(0, 1)          # (kvh, bs, hd)
            vb = v_new[i, sl].swapaxes(0, 1)
            if quantized:
                valid = None
                if st.ctx is not None:
                    # scale exponents follow *real* tokens only: rows past
                    # the prompt are chunk padding and must not coarsen the
                    # block's grid (published full blocks are all-real, so
                    # prefix sharing sees identical exponents either way)
                    pos = (st.length[i] + j * block_size
                           + jnp.arange(block_size))
                    valid = pos < st.ctx[i]
                kb, ke = kvq.store_block(kb, cache.bits, valid=valid)
                vb, ve = kvq.store_block(vb, cache.bits, valid=valid)
                k = jax.lax.dynamic_update_slice(k, kb[None], (blk, 0, 0, 0))
                v = jax.lax.dynamic_update_slice(v, vb[None], (blk, 0, 0, 0))
                k_exp = jax.lax.dynamic_update_slice(k_exp, ke[None], (blk, 0))
                v_exp = jax.lax.dynamic_update_slice(v_exp, ve[None], (blk, 0))
                continue
            k = jax.lax.dynamic_update_slice(k, kb[None].astype(k.dtype),
                                             (blk, 0, 0, 0))
            v = jax.lax.dynamic_update_slice(v, vb[None].astype(v.dtype),
                                             (blk, 0, 0, 0))
    if quantized:
        return QuantPagedKVCache(k, v, k_exp, v_exp, bits=cache.bits)
    return PagedKVCache(k, v)


def paged_prefill_attention(
    q: jax.Array,                     # (b, C, h, d) — one prefill chunk
    cache: AnyPagedKVCache,
    st: PagedState,                   # table sliced to the chunk-position
                                      # bucket; length = chunk start position
    *,
    impl: str = "gather",             # "gather" | "kernel"
    quant: Optional[AttnQuant] = None,
    scale: Optional[float] = None,
) -> jax.Array:
    """Chunked-prefill attention over a slot's mapped blocks: row r of the
    chunk attends positions 0..start+r — the already-cached/computed prefix
    plus the chunk itself (its K/V written first via `paged_prefill_update`,
    the multi-token analogue of decode's write-then-attend).

    impl="kernel" is the Pallas multi-query mode; impl="gather" the dense-
    view fallback and oracle. Both honor the fused GRAU output epilogue and
    return (b, C, h, d) float (dequantized when quantizing).
    """
    b, chunk, h, d = q.shape
    if impl == "kernel":
        from repro.kernels import paged_attention as paged_kernel
        quantized = isinstance(cache, QuantPagedKVCache)
        o = paged_kernel.paged_prefill_attention(
            q, cache.k, cache.v, st.block_table, st.length, scale=scale,
            k_exp=cache.k_exp if quantized else None,
            v_exp=cache.v_exp if quantized else None,
            kv_bits=cache.bits if quantized else 16,
            spec=quant.spec if quant is not None else None,
            s_in=quant.s_in if quant is not None else None)
        if quant is not None:
            o = o.astype(jnp.float32) * quant.s_out
        return o.astype(q.dtype)
    if impl != "gather":
        raise ValueError(f"unknown paged prefill impl {impl!r}")
    kd, vd = paged_view(cache, st)
    kvh = kd.shape[2]
    g = h // kvh
    scale = scale if scale is not None else d ** -0.5
    qg = q.reshape(b, chunk, kvh, g, d)
    logits = jnp.einsum("bqkgd,bskd->bkgqs", qg.astype(jnp.float32),
                        kd.astype(jnp.float32)) * scale
    logits = shard_ctx.constrain(logits, "batch", "kv_heads", None, "seq",
                                 None)
    pos = jnp.arange(kd.shape[1])
    row_end = st.length[:, None] + jnp.arange(chunk)[None]    # (b, C)
    valid = pos[None, None] <= row_end[..., None]             # (b, C, s)
    logits = jnp.where(valid[:, None, None], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bkgqs,bskd->bkgqd", p, vd.astype(jnp.float32))
    o = o.transpose(0, 3, 1, 2, 4).reshape(b, chunk, h, d)
    if quant is not None:
        from repro.kernels.ref import attn_output_quant
        oq = attn_output_quant(o, quant.spec, quant.s_in)
        o = oq.astype(jnp.float32) * quant.s_out
    return o.astype(q.dtype)


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V3 Multi-head Latent Attention)
# ---------------------------------------------------------------------------

class MLACache(NamedTuple):
    ckv: jax.Array        # (batch, max_seq, kv_lora_rank)  compressed latent
    k_rope: jax.Array     # (batch, max_seq, rope_dim)      shared rope key
    length: jax.Array


def mla_decode_attention(
    q_nope_abs: jax.Array,   # (b, 1, h, kv_lora_rank)  — q_nope @ W_uk absorbed
    q_rope: jax.Array,       # (b, 1, h, rope_dim)
    cache: MLACache,
    *,
    scale: float,
) -> jax.Array:
    """Absorbed-form MLA decode: attends in the latent space.

    score = q_nope_abs . ckv + q_rope . k_rope ; value = attn-weighted ckv
    (the per-head value up-projection W_uv is applied by the caller).
    Cache traffic per token is (kv_lora_rank + rope_dim) — the property that
    makes the long_500k cell feasible for deepseek-v3.
    """
    b, _, h, dc = q_nope_abs.shape
    logits = (
        jnp.einsum("bhd,bsd->bhs", q_nope_abs[:, 0].astype(jnp.float32),
                   cache.ckv.astype(jnp.float32))
        + jnp.einsum("bhr,bsr->bhs", q_rope[:, 0].astype(jnp.float32),
                     cache.k_rope.astype(jnp.float32))
    ) * scale
    pos = jnp.arange(cache.ckv.shape[1])
    valid = pos[None] < cache.length[:, None]
    logits = jnp.where(valid[:, None], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bhs,bsd->bhd", p, cache.ckv.astype(jnp.float32))
    return o[:, None].astype(q_nope_abs.dtype)          # (b,1,h,dc)
