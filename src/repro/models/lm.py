"""LM assembly: init / train forward / prefill / decode for every arch family.

Layer stacks are lax.scan'd period-wise: each group (period, repeats) stores
its params stacked along a leading `stack` axis of size `repeats`, and the
traced body contains only one period — this keeps the HLO small enough to
SPMD-partition for 512 devices even for 61-layer models.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.nn import blocks, shard_ctx
from repro.nn.attention import CrossKV, KVCache, MLACache, PagedState
from repro.nn.blocks import LayerSpec
from repro.nn.common import (ParamBuilder, act_fn, make_activation, stack_axes,
                             stack_params)
from repro.nn.mamba2 import SSMState
from repro.quant import weights as wq_lib


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _auto_axes(tree):
    isleaf = lambda x: hasattr(x, "ndim")
    return jax.tree.map(lambda x: (None,) * x.ndim, tree, is_leaf=isleaf)


def init_lm(cfg: ModelConfig, key: jax.Array, dtype=jnp.bfloat16):
    """Returns (params, logical_axes). Layer groups stacked for scanning."""
    pb = ParamBuilder(key, dtype)
    pb.add("embed", (cfg.vocab_size, cfg.d_model), ("vocab", "embed"),
           init="normal", scale=0.02)
    if not cfg.tie_embeddings:
        pb.add("head", (cfg.d_model, cfg.vocab_size), ("embed", "vocab"))
    blocks.init_norm(pb, "ln_f", cfg.d_model, cfg.norm)

    if cfg.encoder is not None:
        enc_pb = pb.sub("encoder")
        enc_spec = LayerSpec(kind="attn", mlp="dense")
        layers, axes = [], None
        for _ in range(cfg.encoder.num_layers):
            lp = ParamBuilder(enc_pb._next(), dtype)
            blocks.init_layer(lp, enc_spec, cfg)
            layers.append(lp.params)
            axes = lp.axes
        enc_pb.params["layers"] = stack_params(layers)
        enc_pb.axes["layers"] = stack_axes(axes)
        blocks.init_norm(enc_pb, "ln_enc", cfg.d_model, cfg.norm)

    for gi, (period, repeats) in enumerate(cfg.groups):
        gkey = pb._next()
        axes = {}

        def init_repeat(k, period=period, axes=axes):
            lp = ParamBuilder(k, dtype)
            for li, spec in enumerate(period):
                blocks.init_layer(lp.sub(f"l{li}"), spec, cfg)
            axes.update(lp.axes)
            return lp.params

        # one vmapped init per group, not `repeats` unrolled copies: the same
        # keys give the same values, and a deep model's init program
        # compiles as fast as a one-layer one
        keys = jax.vmap(lambda r: jax.random.fold_in(gkey, r))(
            jnp.arange(repeats))
        pb.params[f"group{gi}"] = jax.vmap(init_repeat)(keys)
        pb.axes[f"group{gi}"] = stack_axes(axes)

    return pb.params, pb.axes


def make_act(cfg: ModelConfig):
    if cfg.grau is None:
        return act_fn(cfg.activation)
    from repro.nn.common import build_lm_grau
    g = cfg.grau
    return build_lm_grau(cfg.activation, segments=g.segments,
                         num_exponents=g.num_exponents, mode=g.mode,
                         out_bits=g.out_bits, bias_mode=g.bias_mode)


# ---------------------------------------------------------------------------
# Caches
# ---------------------------------------------------------------------------

def _layer_cache(spec: LayerSpec, cfg: ModelConfig, batch: int, max_seq: int,
                 length: int, dtype):
    lengths = jnp.full((batch,), length, jnp.int32)
    if spec.kind == "mamba":
        s = cfg.ssm
        conv_dim = s.d_inner(cfg.d_model) + 2 * s.n_groups * s.d_state
        return SSMState(
            conv=jnp.zeros((batch, s.conv_width - 1, conv_dim), dtype),
            ssm=jnp.zeros((batch, s.n_heads(cfg.d_model), s.head_dim, s.d_state),
                          jnp.float32),
        )
    if cfg.mla is not None:
        m = cfg.mla
        return MLACache(
            ckv=jnp.zeros((batch, max_seq, m.kv_lora_rank), dtype),
            k_rope=jnp.zeros((batch, max_seq, m.qk_rope_dim), dtype),
            length=lengths,
        )
    return KVCache(
        k=jnp.zeros((batch, max_seq, cfg.kv_heads_phys, cfg.head_dim), dtype),
        v=jnp.zeros((batch, max_seq, cfg.kv_heads_phys, cfg.head_dim), dtype),
        length=lengths,
    )


def init_caches(cfg: ModelConfig, batch: int, max_seq: int, *,
                length: int = 0, dtype=jnp.bfloat16, policy=None):
    """Cache pytree: tuple per group, each stacked over repeats.
    Cross-attention layers carry (self_cache, CrossKV) pairs.

    `policy` (quant.policy.PrecisionPolicy) is the end-to-end precision
    object: dense caches only exist at kv_bits=16 (SSM state is recurrent
    and MLA latents are already compressed — neither pages, so neither
    quantizes), so a policy that quantizes any layer's KV is rejected here
    with a pointer at the paged backend (serve/kv_cache.init_paged_caches),
    which consumes the same policy and builds packed pools from it.
    """
    if policy is not None and policy.kv_quantized:
        raise ValueError(
            f"{cfg.name}: dense caches cannot hold quantized KV "
            "(kv_bits < 16); use the paged backend "
            "(serve/kv_cache.init_paged_caches) with this policy")
    caches = []
    for period, repeats in cfg.groups:
        per_layer = []
        for spec in period:
            c = _layer_cache(spec, cfg, batch, max_seq, length, dtype)
            if spec.cross_attn:
                frames = cfg.encoder.num_frames
                c = (c, CrossKV(
                    k=jnp.zeros((batch, frames, cfg.kv_heads_phys,
                                 cfg.head_dim), dtype),
                    v=jnp.zeros((batch, frames, cfg.kv_heads_phys,
                                 cfg.head_dim), dtype)))
            per_layer.append(jax.tree.map(
                lambda x: jnp.broadcast_to(x, (repeats,) + x.shape), c))
        caches.append(tuple(per_layer))
    return tuple(caches)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

REMAT_POLICIES = {
    "full": None,  # save nothing, recompute everything
    "dots": "dots_with_no_batch_dims_saveable",
}


def _run_group(params, caches, x, period, cfg, *, positions, act, encoder_out,
               mode, q_chunk, kv_chunk, remat=None, paged=None,
               paged_impl="gather", attn_quant=None):
    """Scan one (period, repeats) group. caches: tuple per period-layer or None."""
    use_caches = caches is not None

    def body(carry, xs):
        h, aux = carry
        if use_caches:
            layer_params, layer_caches = xs
        else:
            layer_params, layer_caches = xs, None
        new_caches = []
        for li, spec in enumerate(period):
            c = layer_caches[li] if use_caches else None
            h, c_new, a = blocks.apply_layer(
                layer_params[f"l{li}"], h, spec, cfg, positions=positions,
                act=act, cache=c, encoder_out=encoder_out, mode=mode,
                q_chunk=q_chunk, kv_chunk=kv_chunk, paged=paged,
                paged_impl=paged_impl, attn_quant=attn_quant,
            )
            new_caches.append(c_new)
            aux = aux + a
        ys = tuple(new_caches) if use_caches else None
        return (h, aux), ys

    if remat is not None:
        policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                  if remat == "dots" else None)
        body = jax.checkpoint(body, policy=policy, prevent_cse=False)

    xs = (params, caches) if use_caches else params
    (x, aux), ys = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)), xs)
    return x, aux, ys


def apply_lm(
    params: Dict[str, Any],
    cfg: ModelConfig,
    tokens: jax.Array,                    # (b, s) int32
    *,
    mode: str = "train",                  # "train" | "prefill" | "decode"
    caches=None,
    positions: Optional[jax.Array] = None,
    encoder_frames: Optional[jax.Array] = None,   # (b, frames, d) whisper stub
    encoder_out: Optional[jax.Array] = None,      # precomputed (serving path)
    patch_embeds: Optional[jax.Array] = None,     # (b, patches, d) llava stub
    act=None,
    q_chunk: int = 1024,
    kv_chunk: int = 1024,
    remat: Optional[str] = None,          # None | "dots" | "full"
    paged: Optional[PagedState] = None,   # paged-KV decode (serve/kv_cache.py)
    paged_impl: str = "gather",           # "gather" | "kernel" (Pallas)
    attn_quant=None,                      # nn.attention.AttnQuant epilogue
) -> Tuple[jax.Array, Any, jax.Array]:
    """Returns (logits, new_caches, aux_loss)."""
    act = act or make_act(cfg)
    # gathers packed rows + exponent rows when the vocab table is quantized
    x = wq_lib.take_rows(params["embed"], tokens)
    x = shard_ctx.constrain(x, "batch", "seq", "embed")

    if patch_embeds is not None:
        x = jnp.concatenate([patch_embeds.astype(x.dtype), x], axis=1)

    b, s, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))

    if (cfg.encoder is not None and encoder_out is None
            and not (mode == "decode" and caches is not None)):
        # decode reads the cached cross K/V; no encoder pass needed per token
        assert encoder_frames is not None, "whisper needs stub frames"
        encoder_out = run_encoder(params, cfg, encoder_frames, act=act,
                                  q_chunk=q_chunk, kv_chunk=kv_chunk)

    aux_total = jnp.zeros((), jnp.float32)
    new_caches = []
    for gi, (period, repeats) in enumerate(cfg.groups):
        gcaches = caches[gi] if caches is not None else None
        x, aux, ys = _run_group(
            params[f"group{gi}"], gcaches, x, period, cfg,
            positions=positions, act=act, encoder_out=encoder_out, mode=mode,
            q_chunk=q_chunk, kv_chunk=kv_chunk, remat=remat, paged=paged,
            paged_impl=paged_impl, attn_quant=attn_quant)
        aux_total = aux_total + aux
        new_caches.append(ys)

    with jax.named_scope("head"):
        x = blocks.apply_norm(params, "ln_f", x, cfg.norm, cfg.norm_eps)
        if cfg.tie_embeddings:
            logits = jnp.einsum("bsd,vd->bsv", x,
                                wq_lib.dense(params["embed"]))
        else:
            logits = jnp.einsum("bsd,dv->bsv", x,
                                wq_lib.dense(params["head"]))
        logits = shard_ctx.constrain(logits, "batch", "seq", "vocab")
    return logits, (tuple(new_caches) if caches is not None else None), aux_total


def lm_loss(params, cfg: ModelConfig, batch: Dict[str, jax.Array], *,
            act=None, q_chunk: int = 1024, kv_chunk: int = 1024,
            remat: Optional[str] = None) -> jax.Array:
    """Next-token cross-entropy (+ MoE aux). batch: tokens, labels, [stubs]."""
    logits, _, aux = apply_lm(
        params, cfg, batch["tokens"], mode="train", act=act,
        encoder_frames=batch.get("encoder_frames"),
        patch_embeds=batch.get("patch_embeds"),
        q_chunk=q_chunk, kv_chunk=kv_chunk, remat=remat)
    labels = batch["labels"]
    # vision prefix positions carry no labels
    logits = logits[:, -labels.shape[1]:]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    mask = (labels >= 0).astype(jnp.float32)
    labels_safe = jnp.maximum(labels, 0)
    ll = jnp.take_along_axis(logp, labels_safe[..., None], axis=-1)[..., 0]
    loss = -jnp.sum(ll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return loss + aux


def run_encoder(params, cfg: ModelConfig, frames: jax.Array, *, act=None,
                q_chunk: int = 1024, kv_chunk: int = 1024) -> jax.Array:
    """Whisper encoder stack (bidirectional self-attention + dense MLP)."""
    act = act or make_act(cfg)
    enc = params["encoder"]
    e = frames
    epos = jnp.broadcast_to(
        jnp.arange(e.shape[1], dtype=jnp.int32)[None], e.shape[:2])

    def body(carry, layer_params):
        h = carry
        hn = blocks.apply_norm(layer_params, "ln1", h, cfg.norm, cfg.norm_eps)
        a, _ = blocks.apply_attention(
            layer_params["attn"], hn, cfg, positions=epos, causal=False,
            q_chunk=q_chunk, kv_chunk=kv_chunk)
        h = h + a
        hn = blocks.apply_norm(layer_params, "ln2", h, cfg.norm, cfg.norm_eps)
        h = h + blocks.apply_mlp(layer_params["mlp"], hn, act, cfg.gated_mlp)
        return h, None

    e, _ = jax.lax.scan(body, e, enc["layers"])
    return blocks.apply_norm(enc, "ln_enc", e, cfg.norm, cfg.norm_eps)


def decode_step(params, cfg: ModelConfig, tokens: jax.Array, caches, *,
                act=None, encoder_out: Optional[jax.Array] = None,
                paged: Optional[PagedState] = None,
                paged_impl: str = "gather", attn_quant=None):
    """One serving step: tokens (b, 1) + caches -> (logits, new caches).

    For enc-dec models pass precomputed `encoder_out` (computed once at
    request admission, not per token). With `paged`, caches are PagedKVCache
    pools and per-slot positions come from `paged.length`; `paged.block_table`
    may be bucket-sliced to the live-block count, and `paged_impl` picks the
    Pallas flash-decode kernel vs the gathered dense-view fallback."""
    logits, new_caches, _ = apply_lm(
        params, cfg, tokens, mode="decode", caches=caches, act=act,
        encoder_out=encoder_out, positions=None, paged=paged,
        paged_impl=paged_impl, attn_quant=attn_quant)
    return logits, new_caches


def set_cache_lengths(caches, lengths: jax.Array):
    """Override the valid-prefix `length` of every seq-indexed cache leaf.

    Used after bucket-padded prefill: the prefill path stamps length = padded
    seq, but only `lengths` (b,) positions per sequence hold real tokens."""
    seq_caches = (KVCache, MLACache)
    leaf_types = (KVCache, MLACache, SSMState, CrossKV)

    def fix(c):
        if isinstance(c, seq_caches):
            return c._replace(length=jnp.broadcast_to(
                lengths.astype(jnp.int32), c.length.shape))
        return c

    return jax.tree.map(fix, caches,
                        is_leaf=lambda c: isinstance(c, leaf_types))


def prefill_step(params, cfg: ModelConfig, tokens: jax.Array, caches, *,
                 true_length: Optional[jax.Array] = None, act=None,
                 encoder_frames: Optional[jax.Array] = None,
                 q_chunk: int = 1024, kv_chunk: int = 1024,
                 paged: Optional[PagedState] = None,
                 paged_impl: str = "gather", attn_quant=None):
    """Jitted prompt ingestion: one call per admitted prompt batch.

    tokens: (b, s) right-padded to a bucket length so serving never traces a
    new shape per prompt; `true_length` (b,) marks the real prefix (padding
    beyond it is causally downstream of every real token, and the cache
    lengths are overridden so decode masks it out). Returns the logits at the
    last real position (b, vocab) and the filled caches.

    With `paged`, this is one *chunk* of the chunked-prefill state machine:
    `caches` are the PagedKVCache pools, `paged.block_table` is the slot's
    (bucket-sliced) table row and `paged.length` the chunk's absolute start
    position. The chunk's K/V are written through the table and attention
    covers the already-resident prefix blocks (cached or previously
    computed) plus the chunk — serve/engine drives one call per grid chunk.
    Positions past the prompt write deterministic garbage into the slot's
    own (or trash) blocks; decode overwrites them before they are ever
    attended.

    NOTE: bucket padding is only sound for attention-style caches; recurrent
    (SSM) state absorbs padded tokens, so SSM-bearing archs must be prefilled
    at exact length (the engine enforces this).
    """
    if paged is not None:
        b, s = tokens.shape
        positions = (paged.length[:, None]
                     + jnp.arange(s, dtype=jnp.int32)[None])
        logits, new_caches, _ = apply_lm(
            params, cfg, tokens, mode="prefill", caches=caches, act=act,
            positions=positions, paged=paged, paged_impl=paged_impl,
            attn_quant=attn_quant, q_chunk=q_chunk, kv_chunk=kv_chunk)
        return logits[:, -1], new_caches
    logits, new_caches, _ = apply_lm(
        params, cfg, tokens, mode="prefill", caches=caches, act=act,
        encoder_frames=encoder_frames, q_chunk=q_chunk, kv_chunk=kv_chunk)
    if true_length is None:
        return logits[:, -1], new_caches
    idx = jnp.clip(true_length - 1, 0, tokens.shape[1] - 1)
    last = jnp.take_along_axis(logits, idx[:, None, None], axis=1)[:, 0]
    return last, set_cache_lengths(new_caches, true_length)
