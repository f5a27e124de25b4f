"""Pallas TPU flash attention (forward) — the VMEM-resident tile version of
nn/attention.chunked_attention.

Motivation (EXPERIMENTS.md §Perf): the pure-JAX chunked attention's f32
logits tiles round-trip through HBM (≈38% of the llama3 train memory term);
this kernel keeps the (block_q x block_kv) tile, the online-softmax
accumulators and the output block in VMEM for the whole q-row, so per-block
HBM traffic is just q/k/v reads + one output write.

Grid: (batch*q_heads, s_q/block_q, s_kv/block_kv), kv innermost with
online-softmax carry in VMEM scratch. GQA is handled by the index map
(q head h reads kv head h // group).

Tiling: block_q=512, block_kv=512, d<=256 -> VMEM per step ~
q 512*256*4 + k/v 2*512*256*4 + p 512*512*4 + acc 512*256*4 ~= 3.7 MB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30
DEFAULT_BLOCKS = (512, 512)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, block_q: int, block_kv: int,
                  kv_steps: int):
    iq = pl.program_id(1)
    jk = pl.program_id(2)

    @pl.when(jk == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    run = True
    if causal:
        # whole tile above the diagonal -> skip
        run = (iq + 1) * block_q - 1 >= jk * block_kv

    @pl.when(jnp.asarray(run))
    def _tile():
        q = q_ref[0].astype(jnp.float32)               # (bq, d)
        k = k_ref[0].astype(jnp.float32)               # (bkv, d)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = iq * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 0)
            kpos = jk * block_kv + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_cur = jnp.max(s, axis=-1, keepdims=True)     # (bq, 1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        v = v_ref[0].astype(jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new
        l_ref[...] = l_new

    @pl.when(jk == kv_steps - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "blocks", "interpret"))
def flash_attention(
    q: jax.Array,            # (b, s_q, h, d)
    k: jax.Array,            # (b, s_kv, kvh, d)
    v: jax.Array,
    *,
    causal: bool = True,
    blocks: tuple = DEFAULT_BLOCKS,
    interpret: bool = False,
) -> jax.Array:
    b, s_q, h, d = q.shape
    s_kv, kvh = k.shape[1], k.shape[2]
    g = h // kvh
    bq = min(blocks[0], s_q)
    bkv = min(blocks[1], s_kv)
    assert s_q % bq == 0 and s_kv % bkv == 0, (s_q, bq, s_kv, bkv)
    scale = d ** -0.5

    # layout: (b*h, s, d) for q/o; kv indexed via head grouping
    qr = q.transpose(0, 2, 1, 3).reshape(b * h, s_q, d)
    kr = k.transpose(0, 2, 1, 3).reshape(b * kvh, s_kv, d)
    vr = v.transpose(0, 2, 1, 3).reshape(b * kvh, s_kv, d)

    grid = (b * h, s_q // bq, s_kv // bkv)

    def kv_index(ih, iq, jk):
        # q row ih = bi*h + hi  ->  kv row bi*kvh + hi//g
        bi = ih // h
        hi = ih % h
        return (bi * kvh + hi // g, jk, 0)

    out = pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, causal=causal,
                          block_q=bq, block_kv=bkv, kv_steps=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda ih, iq, jk: (ih, iq, 0)),
            pl.BlockSpec((1, bkv, d), kv_index),
            pl.BlockSpec((1, bkv, d), kv_index),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda ih, iq, jk: (ih, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, s_q, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qr, kr, vr)
    return out.reshape(b, h, s_q, d).transpose(0, 2, 1, 3)
