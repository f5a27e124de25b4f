"""Fused int8 GEMM + GRAU epilogue — "End-to-End MAC to Quant" on the MXU.

The paper places GRAU directly after the MAC array so activations never leave
the accelerator at high precision. The TPU analogue: an int8 x int8 -> int32
matmul on the MXU whose epilogue applies the GRAU datapath in-register before
writing int8 back to HBM. Compared with `matmul -> requant` as separate XLA
ops this removes an entire int32 round-trip of activation traffic (4x the
int8 bytes) — the memory-roofline win quantified in EXPERIMENTS.md §Perf.

Grid: (M/bm, N/bn, K/bk), K innermost; int32 accumulation in a VMEM scratch
accumulator, GRAU epilogue fires on the final K step.

Tiling: bm=256, bn=256, bk=512 -> VMEM per step
  x: 256*512 B + w: 512*256 B + acc: 256*256*4 B = 0.5 MB; MXU-aligned
  (int8 native tile is (32, 128); 256/512 are multiples).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.grau import grau_datapath
from repro.pwlf.spec import MAX_SEGMENTS

DEFAULT_TILES = (256, 256, 512)


def _mm_grau_kernel(
    bp_ref, encp_ref, sign_ref, bias_ref, pre_ref,   # SMEM register file
    x_ref,      # (bm, bk) int8
    w_ref,      # (bk, bn) int8
    o_ref,      # (bm, bn) int8
    acc_ref,    # (bm, bn) int32 VMEM scratch
    *,
    num_exponents: int,
    qmin: int,
    qmax: int,
    k_steps: int,
):
    @pl.when(pl.program_id(2) == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _epilogue():
        y = grau_datapath(acc_ref[...], bp_ref, encp_ref, sign_ref, bias_ref,
                          pre_ref, num_exponents=num_exponents, qmin=qmin,
                          qmax=qmax)
        o_ref[...] = y.astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("num_exponents", "qmin", "qmax", "tiles", "interpret"),
)
def matmul_grau_pallas(
    x: jax.Array,            # (M, K) int8
    w: jax.Array,            # (K, N) int8
    bp: jax.Array,
    enc_packed: jax.Array,
    sign: jax.Array,
    bias: jax.Array,
    pre_shift: jax.Array,
    *,
    num_exponents: int,
    qmin: int,
    qmax: int,
    tiles: tuple = DEFAULT_TILES,
    interpret: bool = False,
) -> jax.Array:
    m, k = x.shape
    k2, n = w.shape
    assert k == k2, (x.shape, w.shape)
    bm, bn, bk = tiles
    # output bus signedness comes from the mode register (see kernels/grau.py)
    out_dtype = jnp.int8 if qmin < 0 else jnp.uint8
    grid = (pl.cdiv(m, bm), pl.cdiv(n, bn), pl.cdiv(k, bk))
    smem = lambda shape: pl.BlockSpec(shape, lambda i, j, kk: (0, 0), memory_space=pltpu.SMEM)
    return pl.pallas_call(
        functools.partial(
            _mm_grau_kernel,
            num_exponents=num_exponents, qmin=qmin, qmax=qmax, k_steps=grid[2],
        ),
        grid=grid,
        in_specs=[
            smem((1, MAX_SEGMENTS - 1)),
            smem((1, MAX_SEGMENTS)),
            smem((1, MAX_SEGMENTS)),
            smem((1, MAX_SEGMENTS)),
            smem((1, 1)),
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
    )(
        bp.reshape(1, -1), enc_packed.reshape(1, -1), sign.reshape(1, -1),
        bias.reshape(1, -1), pre_shift.reshape(1, 1),
        x, w,
    )
