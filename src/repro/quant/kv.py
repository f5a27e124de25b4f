"""Packed KV-cache quantization with power-of-two (GRAU-style) scales.

The paged KV pools (nn/attention.QuantPagedKVCache) store K/V as int8 words —
one value per byte at kv_bits=8, two packed nibbles per byte at kv_bits=4 —
plus a *scale-exponent plane*: one signed-byte exponent ``e`` per
(block, kv_head) per tensor.  A stored value ``q`` represents ``q * 2**e``;
dequantization is an exponent-add (a shift in fixed-point hardware), never a
float multiply by an arbitrary calibrated scale.  This mirrors the paper's
PoT datapath: the GRAU unit's segment slopes are power-of-two for exactly the
same reason, and carrying the convention into the KV cache keeps the whole
serving datapath shift-only.

The scale arithmetic itself (exp2i, frexp exponents, nibble packing, shift
requantization) lives in quant/pot.py, shared verbatim with the weight
planes (quant/weights.py) — this module re-exports it under the historical
names and keeps only the KV-pool-specific layer (block store/load, head-dim
packing validation).

Determinism contract (load-bearing for serving bit-exactness):

* Exponents are computed *at write time* by the shared jnp write paths
  (nn/attention.paged_update / paged_prefill_update), which both the Pallas
  kernel and the gather fallback read from — readers never re-derive scales.
* ``pot_exponent`` is frexp-based integer arithmetic (no log2 rounding
  hazard), so the same values always produce the same exponent.
* Re-scaling an already-written block when a later write raises its exponent
  is a rounding right-shift of the stored integers (``requant_shift``) — the
  power-of-two grid makes requantization exact integer arithmetic.

int4 packing: the head dim is split in halves — byte ``i`` holds element
``i`` in its low nibble and element ``i + head_dim//2`` in its high nibble —
so unpacking is a concat, not an interleave (lane-friendly inside kernels).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.quant.pot import (
    EXP_EMPTY as EXP_EMPTY,
    dequantize_pot as dequantize_pot,
    exp2i as exp2i,
    pack_int4 as pack_int4,
    pot_exponent as pot_exponent,
    pot_qmax,
    quantize_pot as quantize_pot,
    requant_shift as requant_shift,
    unpack_int4 as unpack_int4,
)

KV_BITS = (16, 8, 4)


def kv_qmax(bits: int) -> int:
    """Symmetric integer range (see quant/pot.pot_qmax)."""
    return pot_qmax(bits)


def validate_kv_bits(bits: int) -> None:
    if bits not in KV_BITS:
        raise ValueError(f"kv_bits must be one of {KV_BITS}, got {bits}")


def packed_head_dim(head_dim: int, bits: int) -> int:
    """Storage width of the head_dim axis (two nibbles per byte at 4-bit)."""
    validate_kv_bits(bits)
    if bits == 4:
        if head_dim % 2:
            raise ValueError(
                f"kv_bits=4 packs two values per byte along head_dim; "
                f"head_dim={head_dim} is odd — pad the model's head_dim to "
                "an even value or use kv_bits >= 8")
        return head_dim // 2
    return head_dim


def store_block(x: jax.Array, bits: int, valid=None):
    """Quantize one full pool block (kv_heads, positions, head_dim) -> packed
    payload + per-head exponent.  The whole-block write path (prefill chunks,
    which always cover complete blocks on the absolute chunk grid).

    `valid` ((positions,) bool, optional) restricts the exponent's amax to
    real rows: chunk *padding* past the prompt writes deterministic garbage
    K/V into the block, and letting its magnitude pick the scale would
    coarsen the grid every real token in the block is stored at.  Invalid
    rows still get quantized (clipped) payloads — they are overwritten by
    decode or masked by `length` before any reader attends them.
    """
    ax = jnp.abs(x.astype(jnp.float32))
    if valid is not None:
        ax = jnp.where(valid[..., None], ax, 0.0)
    amax = jnp.max(ax, axis=(-2, -1))                              # (... kvh)
    e = pot_exponent(amax, bits)
    q = quantize_pot(x, e[..., None, None], bits)
    return pack_int4(q) if bits == 4 else q, e


def load_block(payload: jax.Array, e: jax.Array, bits: int) -> jax.Array:
    """Inverse of store_block: packed (..., kv_heads, positions, hd')
    payload + (..., kv_heads) exponents -> f32 block.
    Shared verbatim by the gather fallback, the jnp oracle, and (via
    unpack_int4/dequantize_pot on refs) the Pallas kernel, so every reader
    dequantizes bit-identically."""
    q = unpack_int4(payload) if bits == 4 else payload
    return dequantize_pot(q, e[..., None, None])
