"""Pure-jnp oracles for the Pallas kernels (the correctness ground truth)."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.grau import grau_apply_int
from repro.pwlf.spec import GRAUSpec
from repro.quant import kv as kvq

NEG_INF = -1e30


def _dense_kv_views(k_pool, v_pool, block_table, *, k_exp=None, v_exp=None,
                    kv_bits: int = 16):
    """Gather + (optionally) dequantize the per-slot dense K/V views.

    Quantized pools (kv_bits < 16) dequantize via quant/kv.load_block — the
    same helper nn/attention.paged_view uses, so the oracle, the gather
    fallback, and the kernel's in-VMEM dequant all read identical f32 values.
    """
    rows, nblocks = block_table.shape
    kvh, block_size = k_pool.shape[1], k_pool.shape[2]
    seq = nblocks * block_size
    if kv_bits < 16:
        kd = kvq.load_block(k_pool[block_table], k_exp[block_table], kv_bits)
        vd = kvq.load_block(v_pool[block_table], v_exp[block_table], kv_bits)
    else:
        kd, vd = k_pool[block_table], v_pool[block_table]
    # (rows, nblocks, kvh, bs, hd) -> (rows, seq, kvh, hd)
    return tuple(x.transpose(0, 1, 3, 2, 4).reshape(rows, seq, kvh, -1)
                 for x in (kd, vd))


def _out_dtype(spec: GRAUSpec):
    # matches the kernels: signed modes emit int8, unsigned uint8 (a [0, 255]
    # clamp does not fit int8 without wrapping)
    return jnp.int8 if spec.qmin < 0 else jnp.uint8


def grau_ref(x: jax.Array, spec: GRAUSpec) -> jax.Array:
    """Oracle for kernels/grau.py: int32 MAC outputs -> 8-bit quantized acts."""
    return grau_apply_int(x, spec).astype(_out_dtype(spec))


def matmul_grau_ref(x: jax.Array, w: jax.Array, spec: GRAUSpec) -> jax.Array:
    """Oracle for kernels/matmul_grau.py: int8 GEMM -> GRAU epilogue -> int8.

    x: (M, K) int8, w: (K, N) int8; accumulation is int32 (MXU int8 path).
    """
    acc = jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32
    )
    return grau_apply_int(acc, spec).astype(_out_dtype(spec))


def attn_output_quant(o: jax.Array, spec: GRAUSpec, s_in: float) -> jax.Array:
    """The GRAU attention-output epilogue's math, on an f32 attention output:
    scale into the int32 MAC domain, run the datapath, emit the 8-bit bus."""
    xq = jnp.round(o.astype(jnp.float32) * (1.0 / s_in)).astype(jnp.int32)
    return grau_apply_int(xq, spec).astype(_out_dtype(spec))


def matmul_wq_ref(x: jax.Array, w, spec: Optional[GRAUSpec] = None,
                  s_in: float = 1.0) -> jax.Array:
    """Oracle for kernels/matmul_wq.py: f32 activations x packed weight.

    ``w`` is a quant/weights.QuantWeight (or a raw array, making this plain
    dense).  Dequantizes through the same quant/weights.dense fallback every
    CPU/mesh forward uses — exp2i-constructed scales, so oracle, fallback
    and kernel agree bit-for-bit — then optionally composes the GRAU
    epilogue exactly as attn_output_quant does.
    """
    from repro.quant import weights as wq
    out = x.astype(jnp.float32) @ wq.dense(w)
    if spec is None:
        return out
    return attn_output_quant(out, spec, s_in)


def paged_attention_ref(
    q: jax.Array,             # (slots, h, d)
    k_pool: jax.Array,        # (num_blocks, kvh, block_size, d)
    v_pool: jax.Array,
    block_table: jax.Array,   # (slots, nblocks) int32
    lengths: jax.Array,       # (slots,) int32 — attended positions per slot
    *,
    scale: Optional[float] = None,
    spec: Optional[GRAUSpec] = None,
    s_in: Optional[float] = None,
    k_exp: Optional[jax.Array] = None,
    v_exp: Optional[jax.Array] = None,
    kv_bits: int = 16,
) -> jax.Array:
    """Oracle for kernels/paged_attention.py: gather the dense per-slot view
    through the block table (exactly nn/attention.paged_view's layout —
    packed quantized pools dequantize through the same quant/kv helpers),
    run masked softmax attention, optionally apply the GRAU output epilogue."""
    slots, h, d = q.shape
    kvh, block_size = k_pool.shape[1], k_pool.shape[2]
    nblocks = block_table.shape[1]
    g = h // kvh
    scale = scale if scale is not None else d ** -0.5
    seq = nblocks * block_size
    kd, vd = _dense_kv_views(k_pool, v_pool, block_table, k_exp=k_exp,
                             v_exp=v_exp, kv_bits=kv_bits)
    qg = q.reshape(slots, kvh, g, d)
    logits = jnp.einsum("bkgd,bskd->bkgs", qg.astype(jnp.float32),
                        kd.astype(jnp.float32)) * scale
    valid = jnp.arange(seq)[None] < lengths[:, None]
    logits = jnp.where(valid[:, None, None], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bkgs,bskd->bkgd", p, vd.astype(jnp.float32))
    o = o.reshape(slots, h, d)
    if spec is not None:
        assert s_in is not None
        return attn_output_quant(o, spec, s_in)
    return o.astype(q.dtype)


def paged_prefill_ref(
    q: jax.Array,             # (b, C, h, d) — one prefill chunk per row
    k_pool: jax.Array,        # (num_blocks, kvh, block_size, d)
    v_pool: jax.Array,
    block_table: jax.Array,   # (b, nblocks) int32
    start: jax.Array,         # (b,) int32 — absolute position of chunk row 0
    *,
    scale: Optional[float] = None,
    spec: Optional[GRAUSpec] = None,
    s_in: Optional[float] = None,
    k_exp: Optional[jax.Array] = None,
    v_exp: Optional[jax.Array] = None,
    kv_bits: int = 16,
) -> jax.Array:
    """Oracle for the multi-query (chunked-prefill) paged-attention mode:
    gather the dense per-slot view through the block table (dequantizing
    packed pools via quant/kv), then run masked softmax attention where
    chunk row r attends positions 0..start+r."""
    b, chunk, h, d = q.shape
    kvh, block_size = k_pool.shape[1], k_pool.shape[2]
    nblocks = block_table.shape[1]
    g = h // kvh
    scale = scale if scale is not None else d ** -0.5
    seq = nblocks * block_size
    kd, vd = _dense_kv_views(k_pool, v_pool, block_table, k_exp=k_exp,
                             v_exp=v_exp, kv_bits=kv_bits)
    qg = q.reshape(b, chunk, kvh, g, d)
    logits = jnp.einsum("bqkgd,bskd->bkgqs", qg.astype(jnp.float32),
                        kd.astype(jnp.float32)) * scale
    pos = jnp.arange(seq)
    row_end = start[:, None] + jnp.arange(chunk)[None]        # (b, C)
    valid = pos[None, None] <= row_end[..., None]             # (b, C, s)
    logits = jnp.where(valid[:, None, None], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bkgqs,bskd->bkgqd", p, vd.astype(jnp.float32))
    o = o.transpose(0, 3, 1, 2, 4).reshape(b, chunk, h, d)
    if spec is not None:
        assert s_in is not None
        return attn_output_quant(o, spec, s_in)
    return o.astype(q.dtype)
