"""Decoder-layer assembly: attention (GQA / MLA), gated MLP, MoE, Mamba2.

A layer is described by a LayerSpec(kind, mlp): kind in {"attn", "mamba"},
mlp in {"dense", "moe", "none"}. Heterogeneous stacks (jamba 1:7, deepseek
3-dense-then-MoE) are expressed as repeated *periods* of LayerSpecs and
scanned period-wise (models/lm.py).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.nn import attention as attn_lib
from repro.nn import shard_ctx
from repro.nn.attention import CrossKV, KVCache, MLACache, PagedKVCache, PagedState
from repro.nn.common import ParamBuilder, layernorm, rmsnorm
from repro.nn.mamba2 import SSMConfig, SSMState, apply_mamba2, decode_mamba2, init_mamba2
from repro.nn.moe import MoEConfig, apply_moe, init_moe
from repro.nn.rope import apply_rope
from repro.quant import weights as wq_lib


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    kind: str = "attn"        # "attn" | "mamba"
    mlp: str = "dense"        # "dense" | "moe" | "none"
    cross_attn: bool = False  # whisper decoder


# ---------------------------------------------------------------------------
# Norm dispatch
# ---------------------------------------------------------------------------

def init_norm(pb: ParamBuilder, name: str, dim: int, kind: str):
    if kind == "rmsnorm":
        pb.add(f"{name}_w", (dim,), ("embed",), init="zeros")
    else:
        pb.add(f"{name}_w", (dim,), ("embed",), init="ones")
        pb.add(f"{name}_b", (dim,), ("embed",), init="zeros")


def apply_norm(params, name: str, x, kind: str, eps: float):
    if kind == "rmsnorm":
        return rmsnorm(x, params[f"{name}_w"], eps)
    return layernorm(x, params[f"{name}_w"], params[f"{name}_b"], eps)


# ---------------------------------------------------------------------------
# GQA attention
# ---------------------------------------------------------------------------

def init_attention(pb: ParamBuilder, cfg) -> None:
    d, hd = cfg.d_model, cfg.head_dim
    h, kv = cfg.heads_phys, cfg.kv_heads_phys
    wq = pb.add("wq", (d, h, hd), ("embed", "heads", "head_dim"))
    wk = pb.add("wk", (d, kv, hd), ("embed", "kv_heads", "head_dim"))
    wv = pb.add("wv", (d, kv, hd), ("embed", "kv_heads", "head_dim"))
    wo = pb.add("wo", (h, hd, d), ("heads", "head_dim", "embed"))
    if cfg.attn_pad is not None:
        # zero the padded heads: with wo pad rows zero, padded-head grads are
        # identically zero -> the pad is inert and the function equals the
        # unpadded architecture (see ModelConfig.attn_pad)
        hl, kvl = cfg.num_heads, cfg.num_kv_heads
        pb.params["wq"] = wq.at[:, hl:, :].set(0)
        pb.params["wk"] = wk.at[:, kvl:, :].set(0)
        pb.params["wv"] = wv.at[:, kvl:, :].set(0)
        pb.params["wo"] = wo.at[hl:, :, :].set(0)
    if cfg.qkv_bias:
        pb.add("bq", (h, hd), ("heads", "head_dim"), init="zeros")
        pb.add("bk", (kv, hd), ("kv_heads", "head_dim"), init="zeros")
        pb.add("bv", (kv, hd), ("kv_heads", "head_dim"), init="zeros")


def _qkv(params, x, cfg):
    # wq_lib.dense is identity on raw arrays and the exact dequant fallback
    # on packed QuantWeight leaves (weight-quantized serving)
    q = jnp.einsum("bsd,dhk->bshk", x, wq_lib.dense(params["wq"]))
    k = jnp.einsum("bsd,dhk->bshk", x, wq_lib.dense(params["wk"]))
    v = jnp.einsum("bsd,dhk->bshk", x, wq_lib.dense(params["wv"]))
    if cfg.qkv_bias:
        q, k, v = q + params["bq"], k + params["bk"], v + params["bv"]
    return q, k, v


def apply_attention(
    params, x, cfg, *, positions, cache: Optional[KVCache] = None,
    kv_source: Optional[jax.Array] = None, causal: bool = True,
    q_chunk: int = 1024, kv_chunk: int = 1024,
) -> Tuple[jax.Array, Optional[KVCache]]:
    """Training/prefill path (full sequence). Returns (out, prefill_cache)."""
    src = x if kv_source is None else kv_source
    q = jnp.einsum("bsd,dhk->bshk", x, wq_lib.dense(params["wq"]))
    k = jnp.einsum("bsd,dhk->bshk", src, wq_lib.dense(params["wk"]))
    v = jnp.einsum("bsd,dhk->bshk", src, wq_lib.dense(params["wv"]))
    if cfg.qkv_bias:
        q, k, v = q + params["bq"], k + params["bk"], v + params["bv"]
    if kv_source is None:  # self-attention: rope
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    o = attn_lib.chunked_attention(
        q, k, v, causal=causal and kv_source is None,
        q_chunk=q_chunk, kv_chunk=kv_chunk)
    out = jnp.einsum("bshk,hkd->bsd", o, wq_lib.dense(params["wo"]))
    new_cache = None
    if cache is not None:
        # prefill: write k/v into the pre-allocated max-seq cache buffers
        s = x.shape[1]
        new_cache = KVCache(
            k=jax.lax.dynamic_update_slice(cache.k, k.astype(cache.k.dtype),
                                           (0, 0, 0, 0)),
            v=jax.lax.dynamic_update_slice(cache.v, v.astype(cache.v.dtype),
                                           (0, 0, 0, 0)),
            length=jnp.full((x.shape[0],), s, jnp.int32),
        )
    return out, new_cache


def decode_attention_block(
    params, x, cfg, *, cache, paged: Optional[PagedState] = None,
    paged_impl: str = "gather", attn_quant=None,
) -> Tuple[jax.Array, Any]:
    """One-token decode. x: (b, 1, d).

    With `paged`, `cache` is a PagedKVCache (or, under a quantized
    PrecisionPolicy, QuantPagedKVCache) pool: the new position is written
    through the block table — packed + scale-exponent-bumped when quantized
    — and attention runs over the mapped blocks via the Pallas flash-decode
    kernel (paged_impl="kernel") or the gathered dense-view fallback
    ("gather"); `attn_quant` fuses the GRAU output epilogue on either path.
    Storage precision is carried by the cache leaf itself, so this layer is
    policy-agnostic."""
    q, k, v = _qkv(params, x, cfg)
    if paged is not None:
        pos = paged.length[:, None]                              # (b,1)
        q = apply_rope(q, pos, cfg.rope_theta)
        k = apply_rope(k, pos, cfg.rope_theta)
        cache = attn_lib.paged_update(cache, k, v, paged)
        o = attn_lib.paged_decode_attention(q, cache, paged, impl=paged_impl,
                                            quant=attn_quant)
        return jnp.einsum("bshk,hkd->bsd", o,
                          wq_lib.dense(params["wo"])), cache
    pos = cache.length[:, None]                                  # (b,1)
    q = apply_rope(q, pos, cfg.rope_theta)
    k = apply_rope(k, pos, cfg.rope_theta)
    cache = attn_lib.update_cache(cache, k.astype(cache.k.dtype),
                                  v.astype(cache.v.dtype))
    o = attn_lib.decode_attention(q, cache)
    return jnp.einsum("bshk,hkd->bsd", o, wq_lib.dense(params["wo"])), cache


def paged_prefill_attention_block(
    params, x, cfg, *, positions, cache, paged: PagedState,
    paged_impl: str = "gather", attn_quant=None,
) -> Tuple[jax.Array, PagedKVCache]:
    """One prefill chunk through the paged pool. x: (b, C, d).

    The chunk's K/V are scattered into the pool through the block table
    first, then multi-query attention runs over the already-written prefix
    blocks plus the chunk itself — write-then-attend, exactly like decode,
    so a suffix chunk attends the pinned cached-prefix blocks without any
    dense re-materialization. `positions` are absolute (chunk start +
    offset) and `paged.length` carries the chunk start per batch row."""
    q, k, v = _qkv(params, x, cfg)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    cache = attn_lib.paged_prefill_update(cache, k, v, paged)
    o = attn_lib.paged_prefill_attention(q, cache, paged, impl=paged_impl,
                                         quant=attn_quant)
    return jnp.einsum("bshk,hkd->bsd", o, wq_lib.dense(params["wo"])), cache


# ---------------------------------------------------------------------------
# MLA attention (deepseek-v3)
# ---------------------------------------------------------------------------

def init_mla(pb: ParamBuilder, cfg) -> None:
    d, h = cfg.d_model, cfg.num_heads
    m: MLAConfig = cfg.mla
    qk_dim = m.qk_nope_dim + m.qk_rope_dim
    pb.add("wq_a", (d, m.q_lora_rank), ("embed", None))
    pb.add("q_norm_w", (m.q_lora_rank,), (None,), init="zeros")
    pb.add("wq_b", (m.q_lora_rank, h, qk_dim), (None, "heads", "head_dim"))
    pb.add("wkv_a", (d, m.kv_lora_rank + m.qk_rope_dim), ("embed", None))
    pb.add("kv_norm_w", (m.kv_lora_rank,), (None,), init="zeros")
    pb.add("wk_b", (m.kv_lora_rank, h, m.qk_nope_dim), (None, "heads", "head_dim"))
    pb.add("wv_b", (m.kv_lora_rank, h, m.v_head_dim), (None, "heads", "head_dim"))
    pb.add("wo", (h, m.v_head_dim, d), ("heads", "head_dim", "embed"))


def apply_mla(
    params, x, cfg, *, positions, cache: Optional[MLACache] = None,
    q_chunk: int = 1024, kv_chunk: int = 1024,
) -> Tuple[jax.Array, Optional[MLACache]]:
    """Prefill/training MLA in expanded form (per-head K/V materialized
    chunk-wise inside chunked_attention)."""
    m: MLAConfig = cfg.mla
    b, s, _ = x.shape
    h = cfg.num_heads
    ql = rmsnorm(x @ params["wq_a"], params["q_norm_w"])
    q = jnp.einsum("bsr,rhk->bshk", ql, params["wq_b"])
    q_nope, q_rope = jnp.split(q, [m.qk_nope_dim], axis=-1)
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)

    kv = x @ params["wkv_a"]
    ckv, k_rope = jnp.split(kv, [m.kv_lora_rank], axis=-1)
    ckv = rmsnorm(ckv, params["kv_norm_w"])
    k_rope = apply_rope(k_rope[:, :, None, :], positions, cfg.rope_theta)  # (b,s,1,r)

    k_nope = jnp.einsum("bsr,rhk->bshk", ckv, params["wk_b"])
    v = jnp.einsum("bsr,rhk->bshk", ckv, params["wv_b"])
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (b, s, h, m.qk_rope_dim))],
                        axis=-1)
    qf = jnp.concatenate([q_nope, q_rope], axis=-1)
    scale = (m.qk_nope_dim + m.qk_rope_dim) ** -0.5
    # pad v's head_dim up to q/k head_dim for the shared attention helper
    o = attn_lib.chunked_attention(qf, k, jnp.pad(v, ((0, 0), (0, 0), (0, 0),
                                                      (0, k.shape[-1] - v.shape[-1]))),
                                   causal=True, q_chunk=q_chunk, kv_chunk=kv_chunk,
                                   scale=scale)
    o = o[..., : m.v_head_dim]
    out = jnp.einsum("bshk,hkd->bsd", o, params["wo"])
    new_cache = None
    if cache is not None:
        new_cache = MLACache(
            ckv=jax.lax.dynamic_update_slice(cache.ckv,
                                             ckv.astype(cache.ckv.dtype), (0, 0, 0)),
            k_rope=jax.lax.dynamic_update_slice(
                cache.k_rope, k_rope[:, :, 0].astype(cache.k_rope.dtype), (0, 0, 0)),
            length=jnp.full((b,), s, jnp.int32),
        )
    return out, new_cache


def decode_mla(params, x, cfg, *, cache: MLACache) -> Tuple[jax.Array, MLACache]:
    """Absorbed-form single-token MLA decode over the latent cache."""
    m: MLAConfig = cfg.mla
    b = x.shape[0]
    ql = rmsnorm(x @ params["wq_a"], params["q_norm_w"])
    q = jnp.einsum("bsr,rhk->bshk", ql, params["wq_b"])
    q_nope, q_rope = jnp.split(q, [m.qk_nope_dim], axis=-1)
    pos = cache.length[:, None]
    q_rope = apply_rope(q_rope, pos, cfg.rope_theta)

    kv = x[:, 0] @ params["wkv_a"]
    ckv_new, k_rope_new = jnp.split(kv, [m.kv_lora_rank], axis=-1)
    ckv_new = rmsnorm(ckv_new, params["kv_norm_w"])
    k_rope_new = apply_rope(k_rope_new[:, None, None, :], pos, cfg.rope_theta)[:, :, 0]

    def upd(buf, new):
        return jax.vmap(
            lambda bb, nn_, i: jax.lax.dynamic_update_slice_in_dim(bb, nn_, i, axis=0)
        )(buf, new, cache.length)

    cache = MLACache(upd(cache.ckv, ckv_new[:, None].astype(cache.ckv.dtype)),
                     upd(cache.k_rope, k_rope_new.astype(cache.k_rope.dtype)),
                     cache.length + 1)

    # absorb W_uk into the query: q_eff = q_nope @ W_uk^T  (b,1,h,dc)
    q_abs = jnp.einsum("bshk,rhk->bshr", q_nope, params["wk_b"])
    scale = (m.qk_nope_dim + m.qk_rope_dim) ** -0.5
    o_lat = attn_lib.mla_decode_attention(q_abs, q_rope, cache, scale=scale)
    o = jnp.einsum("bshr,rhk->bshk", o_lat, params["wv_b"])       # up-project
    out = jnp.einsum("bshk,hkd->bsd", o, params["wo"])
    return out, cache


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------

def init_mlp(pb: ParamBuilder, d_model: int, d_ff: int, gated: bool = True):
    if gated:
        pb.add("w_gate", (d_model, d_ff), ("embed", "mlp"))
    pb.add("w_up", (d_model, d_ff), ("embed", "mlp"))
    pb.add("w_down", (d_ff, d_model), ("mlp", "embed"))


def apply_mlp(params, x, act: Callable, gated: bool = True):
    # wq_lib.matmul is plain `@` on raw arrays; on packed QuantWeight leaves
    # it dispatches to the in-VMEM dequant Pallas kernel on TPU and to the
    # exact dense fallback on CPU / under a mesh
    if gated:
        h = (act(wq_lib.matmul(x, params["w_gate"]))
             * wq_lib.matmul(x, params["w_up"]))
    else:
        h = act(wq_lib.matmul(x, params["w_up"]))
    h = shard_ctx.constrain(h, "batch", "seq", "mlp")
    return wq_lib.matmul(h, params["w_down"])


# ---------------------------------------------------------------------------
# Full decoder layer
# ---------------------------------------------------------------------------

def init_layer(pb: ParamBuilder, spec: LayerSpec, cfg):
    init_norm(pb, "ln1", cfg.d_model, cfg.norm)
    if spec.kind == "attn":
        sub = pb.sub("attn")
        (init_mla if cfg.mla is not None else init_attention)(sub, cfg)
    else:
        sub = pb.sub("mamba")
        init_mamba2(sub, cfg.d_model, cfg.ssm)
    if spec.cross_attn:
        init_norm(pb, "ln_x", cfg.d_model, cfg.norm)
        init_attention(pb.sub("xattn"), cfg)
    if spec.mlp != "none":
        init_norm(pb, "ln2", cfg.d_model, cfg.norm)
        if spec.mlp == "moe":
            init_moe(pb.sub("moe"), cfg.d_model, cfg.moe)
        else:
            init_mlp(pb.sub("mlp"), cfg.d_model, cfg.d_ff, cfg.gated_mlp)


def apply_layer(
    params, x, spec: LayerSpec, cfg, *, positions, act: Callable,
    cache: Any = None, encoder_out: Optional[jax.Array] = None,
    mode: str = "train",        # "train" | "prefill" | "decode"
    q_chunk: int = 1024, kv_chunk: int = 1024,
    paged: Optional[PagedState] = None,
    paged_impl: str = "gather", attn_quant=None,
) -> Tuple[jax.Array, Any, jax.Array]:
    """Returns (x, new_cache, aux_loss).

    For cross-attention layers the cache is a pair (self_cache, CrossKV):
    prefill fills both, decode reads the cached cross K/V."""
    aux = jnp.zeros((), jnp.float32)
    cross_cache = None
    if spec.cross_attn and cache is not None:
        cache, cross_cache = cache
    x = shard_ctx.constrain(x, "batch", "seq", "embed")
    with jax.named_scope("attention" if spec.kind == "attn" else "ssm"):
        h = apply_norm(params, "ln1", x, cfg.norm, cfg.norm_eps)
        if spec.kind == "attn":
            p = params["attn"]
            if mode == "decode":
                if cfg.mla is not None:
                    a, cache = decode_mla(p, h, cfg, cache=cache)
                else:
                    a, cache = decode_attention_block(p, h, cfg, cache=cache,
                                                      paged=paged,
                                                      paged_impl=paged_impl,
                                                      attn_quant=attn_quant)
            elif mode == "prefill" and paged is not None:
                # chunked prefill into the paged pool (cache is the block pool)
                a, cache = paged_prefill_attention_block(
                    p, h, cfg, positions=positions, cache=cache, paged=paged,
                    paged_impl=paged_impl, attn_quant=attn_quant)
            else:
                want_cache = cache if mode == "prefill" else None
                if cfg.mla is not None:
                    a, cache = apply_mla(p, h, cfg, positions=positions,
                                         cache=want_cache, q_chunk=q_chunk,
                                         kv_chunk=kv_chunk)
                else:
                    a, cache = apply_attention(p, h, cfg, positions=positions,
                                               cache=want_cache, q_chunk=q_chunk,
                                               kv_chunk=kv_chunk)
        else:
            p = params["mamba"]
            if mode == "decode":
                a, cache = decode_mamba2(p, h, cfg.d_model, cfg.ssm, cache)
            else:
                a, cache = apply_mamba2(p, h, cfg.d_model, cfg.ssm, state=None)
                if mode != "prefill":
                    cache = None
        x = x + a

    if spec.cross_attn:
        h = apply_norm(params, "ln_x", x, cfg.norm, cfg.norm_eps)
        p_x = params["xattn"]
        if mode == "decode" and cross_cache is not None:
            # cached cross K/V: only the query projection runs per token
            q = jnp.einsum("bsd,dhk->bshk", h, wq_lib.dense(p_x["wq"]))
            o = attn_lib.chunked_attention(
                q, cross_cache.k, cross_cache.v, causal=False,
                q_chunk=q_chunk, kv_chunk=kv_chunk)
            a = jnp.einsum("bshk,hkd->bsd", o, wq_lib.dense(p_x["wo"]))
        else:
            assert encoder_out is not None
            a, _ = apply_attention(p_x, h, cfg, positions=positions,
                                   kv_source=encoder_out, causal=False,
                                   q_chunk=q_chunk, kv_chunk=kv_chunk)
            if cross_cache is not None:   # prefill: fill the cross cache
                ck = jnp.einsum("bsd,dhk->bshk", encoder_out,
                                wq_lib.dense(p_x["wk"]))
                cv = jnp.einsum("bsd,dhk->bshk", encoder_out,
                                wq_lib.dense(p_x["wv"]))
                cross_cache = CrossKV(k=ck.astype(cross_cache.k.dtype),
                                      v=cv.astype(cross_cache.v.dtype))
        x = x + a
    if spec.cross_attn and cross_cache is not None:
        cache = (cache, cross_cache)

    if spec.mlp != "none":
        with jax.named_scope("mlp"):
            h = apply_norm(params, "ln2", x, cfg.norm, cfg.norm_eps)
            if spec.mlp == "moe":
                # Inference never drops tokens: capacity-factor drops are a
                # training-time load-balancing discipline, and in decode they
                # couple co-batched slots (one slot's routing could evict
                # another's token). Full capacity keeps serving batch-invariant
                # and prefill/decode consistent.
                cap = None if mode == "train" else h.shape[0] * h.shape[1]
                m, aux = apply_moe(params["moe"], h, cfg.moe, act, capacity=cap)
            else:
                m = apply_mlp(params["mlp"], h, act, cfg.gated_mlp)
            x = x + m
    return x, cache, aux
