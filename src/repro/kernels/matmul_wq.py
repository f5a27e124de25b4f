"""Weight-quantized GEMM with in-VMEM power-of-two dequant (+ GRAU epilogue).

The serving twin of kernels/matmul_grau.py for the *weight* planes packed by
quant/weights.py: the f32 activation tile meets an int8/int4 weight tile
that is DMA'd into VMEM **packed**, dequantized there by exponent add, and
fed to the MXU — HBM weight traffic moves at weight_bits width, the paper's
shift-only scaling applied to the decode bandwidth's dominant term.

Grid: (M/bm, N/bn, K/tile), K innermost, one grid step per pack tile.  Each
K step DMAs the tile's packed payload block plus its ``(1, bn)`` exponent
row (one signed byte per (tile, out-channel)); the exponent plane is viewed
as (k_tiles, 1, N) so that row's block is legal on the TPU (its last two
dims are (1, bn) of a (1, N) plane).  2^e is *constructed* by
bitcast (quant/pot.exp2i) — never the approximate ``exp2`` — so the kernel,
the jnp oracle (kernels/ref.matmul_wq_ref) and the dense fallback
(quant/weights.dense) dequantize bit-identically.  Accumulation is f32 in a
VMEM scratch tile.

int4 payload blocks hold the tile split-halves *within the tile* (byte i =
tile elements i and i + tile/2), so unpacking is a sign-extend + concat
along the sublane axis — no interleave.

The optional epilogue composes the fused GRAU datapath exactly like the
paged-attention output quant: on the last K step the f32 accumulator is
scaled onto the GRAU input grid (static ``s_in``), pushed through
kernels/grau.grau_datapath against the SMEM register file, and written back
at 8 bits — matmul in, activations out, never touching HBM at f32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.grau import grau_datapath
from repro.pwlf.spec import MAX_SEGMENTS
from repro.quant.pot import exp2i

DEFAULT_TILES = (256, 256)   # (bm, bn); the K tile is the pack tile


def _dequant_w_block(w_ref, e_ref, bits: int) -> jax.Array:
    """Packed (t_p, bn) payload + (1, 1, bn) exponent row -> f32 (tile, bn).

    Same split-halves discipline as quant/pot.unpack_int4, along the sublane
    axis: rows [0, t/2) are sign-extended low nibbles (tile elements
    0..t/2-1), rows [t/2, t) the high nibbles.  2^e comes from exp2i's
    bitcast construction, so the dequant is an exact exponent add.
    """
    q = w_ref[...].astype(jnp.int32)      # Mosaic has no int8 shifts
    if bits == 4:
        q = jnp.concatenate([(q << 28) >> 28, q >> 4], axis=0)
    return q.astype(jnp.float32) * exp2i(e_ref[0])


def _mm_wq_kernel(*refs, bits, k_steps, fuse, num_exponents, qmin, qmax,
                  inv_s_in):
    if fuse:
        (bp_ref, encp_ref, sign_ref, bias_ref, pre_ref,
         x_ref, w_ref, e_ref, o_ref, acc_ref) = refs
    else:
        x_ref, w_ref, e_ref, o_ref, acc_ref = refs

    @pl.when(pl.program_id(2) == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], _dequant_w_block(w_ref, e_ref, bits),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _epilogue():
        if fuse:
            # mirror kernels/ref.attn_output_quant: static input scale onto
            # the GRAU integer grid, then the in-register datapath
            xq = jnp.round(acc_ref[...] * inv_s_in).astype(jnp.int32)
            y = grau_datapath(xq, bp_ref, encp_ref, sign_ref, bias_ref,
                              pre_ref, num_exponents=num_exponents,
                              qmin=qmin, qmax=qmax)
            o_ref[...] = y.astype(o_ref.dtype)
        else:
            o_ref[...] = acc_ref[...]


@functools.partial(
    jax.jit,
    static_argnames=("bits", "kdim", "num_exponents", "qmin", "qmax", "s_in",
                     "tiles", "interpret"),
)
def matmul_wq_pallas(
    x: jax.Array,            # (M, K) float
    qw: jax.Array,           # (K_packed, N) int8 payload (quant/weights)
    e: jax.Array,            # (k_tiles, N) int8 exponent plane
    *,
    bits: int,
    kdim: int,
    bp: jax.Array = None,    # GRAU register file — all five present => fused
    enc_packed: jax.Array = None,
    sign: jax.Array = None,
    bias: jax.Array = None,
    pre_shift: jax.Array = None,
    num_exponents: int = 0,
    qmin: int = 0,
    qmax: int = 0,
    s_in: float = 1.0,
    tiles: tuple = DEFAULT_TILES,
    interpret: bool = False,
) -> jax.Array:
    m, k = x.shape
    assert k == kdim, (x.shape, kdim)
    k_tiles, n = e.shape
    assert kdim % k_tiles == 0, (kdim, k_tiles)
    tile = kdim // k_tiles
    t_p = qw.shape[0] // k_tiles              # packed rows per tile
    assert qw.shape == (k_tiles * t_p, n), (qw.shape, e.shape)
    fuse = bp is not None
    bm, bn = min(tiles[0], m), min(tiles[1], n)
    grid = (pl.cdiv(m, bm), pl.cdiv(n, bn), k_tiles)
    out_dtype = (jnp.int8 if qmin < 0 else jnp.uint8) if fuse else x.dtype
    smem = lambda shape: pl.BlockSpec(shape, lambda i, j, kk: (0, 0),
                                      memory_space=pltpu.SMEM)
    reg_specs = [
        smem((1, MAX_SEGMENTS - 1)),
        smem((1, MAX_SEGMENTS)),
        smem((1, MAX_SEGMENTS)),
        smem((1, MAX_SEGMENTS)),
        smem((1, 1)),
    ] if fuse else []
    reg_args = (
        bp.reshape(1, -1), enc_packed.reshape(1, -1), sign.reshape(1, -1),
        bias.reshape(1, -1), pre_shift.reshape(1, 1),
    ) if fuse else ()
    return pl.pallas_call(
        functools.partial(
            _mm_wq_kernel, bits=bits, k_steps=k_tiles, fuse=fuse,
            num_exponents=num_exponents, qmin=qmin, qmax=qmax,
            inv_s_in=1.0 / s_in,
        ),
        grid=grid,
        in_specs=reg_specs + [
            pl.BlockSpec((bm, tile), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((t_p, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, 1, bn), lambda i, j, kk: (kk, 0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
        name="matmul_wq",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
    )(*reg_args, x.astype(jnp.float32), qw, e.reshape(k_tiles, 1, n))
