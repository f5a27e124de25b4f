"""Pallas paged-attention kernels: flash decode *and* chunked (multi-query)
prefill *through* the block table, so per-step HBM traffic scales with live
tokens, not pool capacity.

The serving engine stores K/V in a shared pool of fixed-size blocks
(nn/attention.PagedKVCache); a slot owns only the blocks its sequence
occupies.  The pre-existing decode path gathered every slot's whole
block-table row into a dense (slots, blocks_per_slot * block_size, kvh, hd)
view per layer per tick — O(slot capacity) HBM reads regardless of how short
the live sequences are.  This kernel is the vLLM-style fix: the block table
and per-slot lengths are *scalar-prefetched*, the BlockSpec index map resolves
`block_table[slot, j]` to pick which pool block the next grid step DMAs, and
an online-softmax (flash) recurrence accumulates over exactly the mapped
blocks.  Dead grid steps (j beyond a slot's live blocks) clamp the index map
to the last live block — Pallas elides the re-fetch when consecutive indices
match — and `pl.when` skips their compute, so both DMA bytes and FLOPs follow
`lengths`, not `blocks_per_slot`.

Grid: (slots, kv_heads, nblocks), block axis innermost with the online-softmax
carry (m, l, acc) in VMEM scratch — the decode analogue of
kernels/flash_attention.py.  GQA is native: one grid row loads a kv head's
block once and attends all `h // kvh` query heads against it.

Epilogue: optionally fused GRAU quantization ("End-to-End MAC to Quant" for
the attention output) — the normalized f32 output is scaled into the int32
MAC domain and pushed through the same `grau_datapath` as the GEMM kernels,
writing int8/uint8 straight to HBM.  The register file rides in as scalar
prefetch, so reconfiguring the activation/precision never recompiles.

Quantized KV pools (`kv_bits` = 8 or 4, assigned per layer by
quant/policy.PrecisionPolicy): the pools hold packed int8 payloads
(half-width head_dim at 4-bit) plus per-(block, kv_head) power-of-two
scale-exponent planes.  The block's exponent row rides in as a (1, 1, kvh)
int32 tile indexed through the same table-resolved map as the K/V tiles
(the grid's kv head selects its lane), and each DMA'd tile is dequantized
*in VMEM* (`_dequant_tile`: unpack + exponent
add, via the exact quant/kv helpers the gather fallback uses) right before
the flash recurrence — so HBM traffic per step follows kv_bits while the
recurrence stays f32 and bit-consistent with the dense-view oracle.

Multi-query prefill mode (`paged_prefill_attention`): the chunked-prefill
state machine (serve/engine) feeds C query positions at once, each row r
attending positions 0..start+r — the pinned cached-prefix blocks *and* the
chunk's own just-written blocks, all resolved through the same
scalar-prefetched table. The kernel is the decode kernel with the online-
softmax carry widened to (C*g, ·) and the position mask made per-row, so a
prompt suffix never re-reads more than prefix+chunk bytes per layer.  Decode
is the same kernel at C = 1 with ``start = length - 1``: one body, one jit.

TPU tiling: every block's last two dims are the array's own.  The pools are
laid out (num_blocks, kv_heads, block_size, head_dim'), so a K/V tile is one
(block_size, head_dim') plane; the exponent planes are viewed as
(num_blocks, 1, kv_heads).  ``tests/test_tpu_compile.py`` compiles every
variant for a described v5e chip at published widths.

On non-TPU backends the kernel runs in interpret mode (functionally exact,
used by the differential tests); the serving engine's CPU hot path is the
bucketed dense gather (nn/attention.paged_view with `max_blocks`), which
scales the same way — see docs/perf.md.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.grau import grau_datapath
from repro.pwlf.spec import GRAUSpec
from repro.quant import kv as kvq

NEG_INF = -1e30


def _dequant_tile(tile, e, kv_bits: int):
    """In-VMEM dequant of one (block_size, packed_hd) K or V tile.

    At kv_bits < 16 the DMA'd tile is packed int8 (two nibbles per byte at
    4-bit, quant/kv.py's split-halves layout) and ``e`` holds the tile's
    (block, head) power-of-two scale exponent as a (1, 1) int32; dequant is
    unpack + exponent-add, using the same quant/pot helpers as the gather
    fallback so both readers see bit-identical f32 values.  At 16 bits this
    is the plain f32 upcast.
    """
    if kv_bits == 16:
        return tile.astype(jnp.float32)
    q = tile.astype(jnp.int32)
    if kv_bits == 4:
        q = jnp.concatenate([(q << 28) >> 28, q >> 4], axis=-1)
    return kvq.dequantize_pot(q, e)


def _head_exp(exp_ref, hh):
    """(1, 1) exponent of kv head ``hh`` from the block's (1, 1, kvh) row."""
    row = exp_ref[0]                                          # (1, kvh)
    lane = jax.lax.broadcasted_iota(jnp.int32, row.shape, 1)
    return jnp.sum(jnp.where(lane == hh, row, 0), axis=1, keepdims=True)


def decode_grid(slots: int, kv_heads: int, nblocks: int) -> Tuple[int, int, int]:
    """The kernel's grid over `nblocks` table columns.

    Exposed so tests can assert the work scales with the live-block bucket
    (`nblocks`), never with the pool's block count.
    """
    return (slots, kv_heads, nblocks)


def _live_blocks(length, block_size: int):
    # ceil(length / block_size), clamped to >= 1 so idle slots (length 0)
    # still resolve a block index (the null block; output is ignored).
    return jnp.maximum(pl.cdiv(length, block_size), 1)


def _make_paged_kernel(*, block_size: int, nblocks: int, chunk: int,
                       scale: float, groups: int, kv_bits: int = 16,
                       quant: Optional[Tuple[int, int, int]] = None):
    """One kernel body for every epilogue/storage combination; `quant`
    (num_exponents, qmin, qmax) switches the finish step to the fused GRAU
    datapath (whose register-file refs then precede the tensor refs as
    scalar prefetch), and `kv_bits` < 16 adds the two scale-exponent refs
    after v_ref and dequantizes each DMA'd tile in VMEM."""

    def kernel(bt_ref, start_ref, *refs):
        sbits_ref = None
        if quant is not None:
            (bp_ref, encp_ref, sign_ref, bias_ref, pre_ref,
             sbits_ref), refs = refs[:6], refs[6:]
        kexp_ref = vexp_ref = None
        if kv_bits < 16:
            (q_ref, k_ref, v_ref, kexp_ref, vexp_ref, o_ref,
             m_ref, l_ref, acc_ref) = refs
        else:
            q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref = refs
        # read outside the pl.when bodies: interpret mode cannot substitute
        # program_id inside them
        s, hh, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)

        @pl.when(j == 0)
        def _init():
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        # the chunk's last row attends start + chunk positions; every block
        # past that is dead (skipped compute, index map clamps the DMA)
        @pl.when(j < _live_blocks(start_ref[s] + chunk, block_size))
        def _blk():
            # q rows are (chunk_row, group)-flattened; row r attends pool
            # positions <= start + r — causal over the chunk, unrestricted
            # over the already-written prefix
            q = q_ref[0, 0].astype(jnp.float32)               # (C*g, d)
            k = _dequant_tile(k_ref[0, 0],                    # (bs, d)
                              _head_exp(kexp_ref, hh) if kv_bits < 16
                              else None, kv_bits)
            lg = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
            pos = j * block_size + jax.lax.broadcasted_iota(
                jnp.int32, lg.shape, 1)
            row = jax.lax.broadcasted_iota(jnp.int32, lg.shape, 0) // groups
            lg = jnp.where(pos <= start_ref[s] + row, lg, NEG_INF)
            m_prev, l_prev = m_ref[...], l_ref[...]
            m_new = jnp.maximum(m_prev, jnp.max(lg, axis=-1, keepdims=True))
            p = jnp.exp(lg - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[...] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
            v = _dequant_tile(v_ref[0, 0],
                              _head_exp(vexp_ref, hh) if kv_bits < 16
                              else None, kv_bits)
            acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
                p, v, preferred_element_type=jnp.float32)
            m_ref[...] = m_new

        @pl.when(j == nblocks - 1)
        def _finish():
            out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
            if quant is None:
                o_ref[0, 0] = out.astype(o_ref.dtype)
                return
            num_exponents, qmin, qmax = quant
            # the f32 -> int32 MAC-domain scale rides in as raw float bits
            # (scalar prefetch is int32); reconstructing via bitcast keeps
            # it runtime data
            inv_s = jax.lax.bitcast_convert_type(sbits_ref[0, 0],
                                                 jnp.float32)
            xq = jnp.round(out * inv_s).astype(jnp.int32)
            y = grau_datapath(xq, bp_ref, encp_ref, sign_ref, bias_ref,
                              pre_ref, num_exponents=num_exponents,
                              qmin=qmin, qmax=qmax)
            o_ref[0, 0] = y.astype(o_ref.dtype)

    return kernel


@functools.partial(jax.jit,
                   static_argnames=("scale", "s_in", "kv_bits", "interpret"))
def _paged_jit(
    q: jax.Array,             # (b, C, h, d) — C query positions per row
    k_pool: jax.Array,        # (num_blocks, kvh, block_size, d_packed)
    v_pool: jax.Array,
    block_table: jax.Array,   # (b, nblocks) int32; 0 = null block
    start: jax.Array,         # (b,) int32 — absolute position of chunk row 0
    spec: Optional[GRAUSpec],
    k_exp: Optional[jax.Array],   # (num_blocks, kvh) int8 scale exponents
    v_exp: Optional[jax.Array],
    *,
    scale: Optional[float],
    s_in: Optional[float],
    kv_bits: int,
    interpret: bool,
) -> jax.Array:
    b, chunk, h, d = q.shape
    nb, kvh, block_size, dp = k_pool.shape   # dp: d at >= 8 bits, d//2 at 4
    assert h % kvh == 0, (h, kvh)
    g = h // kvh
    nblocks = block_table.shape[1]
    scale = scale if scale is not None else d ** -0.5
    # (chunk_row, group)-flattened query rows, one tile per kv head
    qg = (q.reshape(b, chunk, kvh, g, d).transpose(0, 2, 1, 3, 4)
          .reshape(b, kvh, chunk * g, d))

    def q_index(s, hh, j, *_refs):
        return (s, hh, 0, 0)

    def live_col(s, j, bt_ref, start_ref):
        # clamp dead steps to the last live block: consecutive equal indices
        # make Pallas skip the re-fetch, so dead capacity costs no DMA
        jj = jnp.minimum(
            j, _live_blocks(start_ref[s] + chunk, block_size) - 1)
        return bt_ref[s, jj]

    def kv_index(s, hh, j, bt_ref, start_ref, *_rest):
        return (live_col(s, j, bt_ref, start_ref), hh, 0, 0)

    def exp_index(s, hh, j, bt_ref, start_ref, *_rest):
        return (live_col(s, j, bt_ref, start_ref), 0, 0)

    scalars = [block_table.astype(jnp.int32), start.astype(jnp.int32)]
    quant = None
    out_dtype = q.dtype
    if spec is not None:
        assert s_in is not None, "GRAU epilogue needs the MAC-domain scale"
        from repro.kernels.ops import pack_spec
        bp, encp, sign, bias, pre = pack_spec(spec)
        sbits = jnp.asarray(np.float32(1.0 / s_in).view(np.int32))
        scalars += [bp.reshape(1, -1), encp.reshape(1, -1),
                    sign.reshape(1, -1), bias.reshape(1, -1),
                    pre.reshape(1, 1), sbits.reshape(1, 1)]
        quant = (spec.num_exponents, spec.qmin, spec.qmax)
        out_dtype = jnp.int8 if spec.qmin < 0 else jnp.uint8
    kernel = _make_paged_kernel(block_size=block_size, nblocks=nblocks,
                                chunk=chunk, scale=scale, groups=g,
                                kv_bits=kv_bits, quant=quant)

    in_specs = [
        pl.BlockSpec((1, 1, chunk * g, d), q_index),
        pl.BlockSpec((1, 1, block_size, dp), kv_index),
        pl.BlockSpec((1, 1, block_size, dp), kv_index),
    ]
    operands = [qg, k_pool, v_pool]
    if kv_bits < 16:
        assert k_exp is not None and v_exp is not None
        in_specs += [pl.BlockSpec((1, 1, kvh), exp_index)] * 2
        operands += [e.astype(jnp.int32).reshape(nb, 1, kvh)
                     for e in (k_exp, v_exp)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=decode_grid(b, kvh, nblocks),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, chunk * g, d), q_index),
        scratch_shapes=[
            pltpu.VMEM((chunk * g, 1), jnp.float32),
            pltpu.VMEM((chunk * g, 1), jnp.float32),
            pltpu.VMEM((chunk * g, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, chunk * g, d), out_dtype),
        interpret=interpret,
        name="paged_attention",
    )(*scalars, *operands)
    return (out.reshape(b, kvh, chunk, g, d).transpose(0, 2, 1, 3, 4)
            .reshape(b, chunk, h, d))


def paged_attention(
    q: jax.Array,             # (slots, h, d)
    k_pool: jax.Array,        # (num_blocks, kvh, block_size, d_packed)
    v_pool: jax.Array,
    block_table: jax.Array,   # (slots, nblocks) int32; 0 = null block
    lengths: jax.Array,       # (slots,) int32 — positions to attend per slot
    *,
    scale: Optional[float] = None,
    spec: Optional[GRAUSpec] = None,
    s_in: Optional[float] = None,
    k_exp: Optional[jax.Array] = None,
    v_exp: Optional[jax.Array] = None,
    kv_bits: int = 16,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Flash decode over the mapped blocks of each slot.

    `nblocks` (the table width) is the live-block bucket the caller chose —
    the engine slices its full table to the smallest bucket covering the
    longest live sequence, so the grid never covers dead capacity.  With
    `spec` (+ `s_in`, the f32->MAC-domain scale), the GRAU epilogue quantizes
    the output to the spec's 8-bit bus; otherwise output dtype follows q.

    With `kv_bits` < 16 the pools are packed int8 payloads (quant/kv.py) and
    `k_exp`/`v_exp` are the per-(block, head) power-of-two scale-exponent
    planes: each DMA'd KV tile moves at its packed width and is dequantized
    in VMEM (unpack + exponent add) right before the flash recurrence — HBM
    traffic per step follows kv_bits, not the compute dtype.

    The one-query case of the multi-query kernel: a single row at
    ``start = length - 1`` attends exactly positions < length.  The GRAUSpec
    register file is a pytree argument, so reconfiguring the epilogue's
    activation or precision never retraces.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    start = lengths.astype(jnp.int32) - 1
    return _paged_jit(q[:, None], k_pool, v_pool, block_table, start, spec,
                      k_exp, v_exp, scale=scale, s_in=s_in, kv_bits=kv_bits,
                      interpret=interpret)[:, 0]


def paged_prefill_attention(
    q: jax.Array,             # (b, C, h, d) — one chunk of query positions
    k_pool: jax.Array,        # (num_blocks, kvh, block_size, d_packed)
    v_pool: jax.Array,
    block_table: jax.Array,   # (b, nblocks) int32; 0 = null block
    start: jax.Array,         # (b,) int32 — absolute position of chunk row 0
    *,
    scale: Optional[float] = None,
    spec: Optional[GRAUSpec] = None,
    s_in: Optional[float] = None,
    k_exp: Optional[jax.Array] = None,
    v_exp: Optional[jax.Array] = None,
    kv_bits: int = 16,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Flash attention for one prefill chunk over a slot's mapped blocks.

    Row r attends pool positions 0..start+r (the pinned cached-prefix blocks
    plus the chunk's own blocks — the chunk's K/V must already be written
    through the table, exactly like decode's write-then-attend). `nblocks`
    is the chunk-position bucket the caller chose; with `spec` (+ `s_in`)
    the fused GRAU epilogue quantizes the output to the 8-bit bus, matching
    the decode kernel's epilogue bit for bit.  With `kv_bits` < 16 the pools
    are packed int8 + scale-exponent planes and each tile dequantizes in
    VMEM, exactly like the decode kernel.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _paged_jit(q, k_pool, v_pool, block_table, start, spec, k_exp,
                      v_exp, scale=scale, s_in=s_in, kv_bits=kv_bits,
                      interpret=interpret)
