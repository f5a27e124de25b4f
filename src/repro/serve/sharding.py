"""Placement for sharded serving: params, KV storage, per-tick slot state.

The serving scheme differs from training's FSDP x TP (launch/steps.py):

  * params — pure tensor parallelism: `model`-axis shards on heads / kv-heads
    / mlp / experts / vocab, everything replicated over `data`. Serving reads
    weights every tick, so FSDP's embed-dim sharding would all-gather the
    full matrix per decode step; replication trades HBM for zero gather.
  * paged KV pool — kv-head axis over `model`; the block axis is replicated
    over `data` (any slot may own any block, so a data-sharded pool would
    need per-shard allocators — that is the multi-host follow-up, not this
    layer). Decode batch (slots) shards over `data` via the activation rules.
    Quantized pools (PrecisionPolicy kv_bits < 16) shard the same way, with
    the packed payload's storage head_dim deciding the fallback and the
    (repeats, blocks, kvh) scale-exponent planes sharding their kv-head axis
    alongside the payload — a block and its scales share a shard.
  * dense caches — launch/steps.cache_pspecs: slot batch over `data`,
    kv heads over `model`.
  * slot state (last token, lengths, decode budget, active mask) — a tiny
    device-resident tree donated through the decode jit each tick, placed
    slot-over-`data` at construction (place_slot_state); the engine pins
    every step's donated outputs to their construction placement, so no
    program ever sees a second input placement.  The sampler batch and
    PRNG key ride in uncommitted, and the embed-lookup constraint
    re-shards the token batch over `data` on entry to the model.
  * paged decode impl — under a mesh the engine uses the dense-gather path
    (the Pallas paged-attention kernel has no GSPMD partitioning rule, so
    the engine rejects an explicit kernel+mesh combination; sharding it via
    shard_map over the kv-head axis is the follow-up). Both impls are
    O(live blocks) per step: the mesh path gathers through the
    bucket-sliced block table (docs/perf.md).
  * chunked prefill — the per-chunk forward (engine._chunk_fn) traces under
    the same shard_ctx as decode: the chunk's (1, C) activations follow the
    usual batch/seq rules, its K/V scatter lands in the head-sharded pools,
    and the multi-query attention gathers through the chunk-table bucket
    with paged_view's layout pins. Radix prefix reuse is pure host-side
    table bookkeeping, so it composes with any placement — shared blocks
    are shards of the same pool every replica already holds (tested across
    the mesh matrix in tests/test_prefix_cache.py).

Everything resolves through the same logical-axis rules as training
(nn/common.DEFAULT_RULES, nn/shard_ctx._ACT_RULES) so a future mesh axis
(e.g. `pod`) composes without touching the engine.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.launch import steps as steps_lib
# make_serve_mesh/parse_mesh_spec re-exported so engine callers can build
# meshes without touching launch/
from repro.launch.mesh import (make_serve_mesh, named_shardings,  # noqa: F401
                               parse_mesh_spec)
from repro.models import lm
from repro.models.config import ModelConfig
from repro.nn.attention import PagedKVCache, QuantPagedKVCache
from repro.quant.weights import QuantWeight


def _axis_size(mesh: Mesh, axis: str) -> int:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    return sizes.get(axis, 1)


def activation_overrides(cfg: ModelConfig, mesh: Mesh):
    """Serving reuses training's rule overrides (sequence parallelism for
    archs whose heads don't divide the model axis)."""
    return steps_lib.act_rules(cfg, mesh)


def with_shard_ctx(fn, mesh: Mesh, cfg: ModelConfig):
    """Wrap a jit body so activation constraints resolve while it traces."""
    return steps_lib._with_shard_ctx(fn, mesh, activation_overrides(cfg, mesh))


def _axis_prod(mesh: Mesh, entry) -> int:
    if entry is None:
        return 1
    names = entry if isinstance(entry, tuple) else (entry,)
    out = 1
    for name in names:
        out *= _axis_size(mesh, name)
    return out


def _wq_leaf_spec(spec, w: QuantWeight, mesh: Mesh):
    """Spec pair for one packed weight (quant/weights.QuantWeight).

    Packing preserves rank, so the payload keeps the unpacked tensor's TP
    placement on every *non-contraction* axis.  The contraction axis always
    replicates: dequantization reshapes it into (tiles, tile) in place, and
    XLA's SPMD partitioner miscompiles that axis-splitting reshape on
    sharded int8 payloads (wrong nibble-shift results on the CPU backend
    despite value-equal inputs) — replicating the one axis sidesteps it,
    and only w_down (whose TP axis IS its contraction axis) pays with full
    replication.  The exponent plane shards alongside the payload with its
    tile-count axis replicated (negligible bytes).  The result is a
    QuantWeight *of PartitionSpecs* carrying the same static aux as the
    array leaf, so the sharding tree's treedef matches the param tree's
    for device_put.
    """
    nd = w.q.ndim
    entries = list(spec) + [None] * (nd - len(spec))
    pos = nd + w.caxis
    entries[pos] = None
    for i, entry in enumerate(entries):
        if i != pos and w.q.shape[i] % _axis_prod(mesh, entry):
            entries[i] = None
    e_entries = list(entries)
    return dataclasses.replace(w, q=P(*entries), e=P(*e_entries))


def init_params(cfg: ModelConfig, key: jax.Array, *, dtype=jnp.float32,
                mesh: Optional[Mesh] = None):
    """Seeded random parameters, built by one jitted program straight into
    their serving placement: tensor-parallel shards under `mesh` (no device
    ever holds the whole tree — a published-width model can be larger than
    one chip), the default device otherwise."""
    def init(k):
        return lm.init_lm(cfg, k, dtype=dtype)[0]

    if mesh is None:
        return jax.jit(init)(key)
    _, pspecs = steps_lib.param_pspecs(cfg, mesh, fsdp=False)
    return jax.jit(init, out_shardings=named_shardings(mesh, pspecs))(key)


def place_params(params, cfg: ModelConfig, mesh: Mesh):
    """Tensor-parallel placement (no FSDP): returns the committed param tree.

    Weight-quantized trees place packed leaves natively: the base pspecs
    (built from the unpacked tree structure — P leaves pair with whole
    QuantWeight subtrees under flatten_up_to) are refined per packed leaf
    by _wq_leaf_spec, so payload and exponent planes shard together and no
    dense materialization ever happens on the way to the devices.
    """
    _, pspecs = steps_lib.param_pspecs(cfg, mesh, fsdp=False)
    pspecs = jax.tree.map(
        lambda spec, leaf: (_wq_leaf_spec(spec, leaf, mesh)
                            if isinstance(leaf, QuantWeight) else spec),
        pspecs, params,
        is_leaf=lambda x: isinstance(x, P))
    return jax.device_put(params, named_shardings(mesh, pspecs))


def place_dense_caches(caches, cfg: ModelConfig, mesh: Mesh, slots: int):
    """Dense (slots, max_seq) caches: slot batch over data, heads over model."""
    pspecs = steps_lib.cache_pspecs(cfg, mesh, slots)
    return jax.device_put(caches, named_shardings(mesh, pspecs))


def _pool_leaf_spec(mesh: Mesh, kv_heads: int, packed_hd: int):
    """Payload spec for one (repeats, blocks, kvh, block_size, hd') leaf:
    kv heads shard over `model` when divisible (the *packed* head_dim as the
    fallback, matching cache_pspecs), blocks stay whole on every replica."""
    m = _axis_size(mesh, "model")
    if kv_heads % m == 0:
        return P(None, None, "model", None, None)
    if packed_hd % m == 0:
        return P(None, None, None, None, "model")
    return P(None, None, None, None, None)


def paged_pool_pspecs(cfg: ModelConfig, mesh: Mesh, pools=None):
    """PartitionSpec tree mirroring kv_cache.init_paged_caches' structure.

    With `pools` (the actual cache tree), specs are derived leaf-by-leaf so
    quantized layers shard correctly: packed payloads use their *storage*
    head_dim (half-width at 4-bit) for the fallback divisibility check, and
    the (repeats, blocks, kvh) scale-exponent planes shard their kv-head
    axis alongside the payload's — a block's payload and its scale metadata
    always land on the same shard.  Without `pools`, the all-float layout is
    assumed (back-compat for callers that never quantize).
    """
    m = _axis_size(mesh, "model")

    def leaf_spec(c):
        if isinstance(c, QuantPagedKVCache):
            spec = _pool_leaf_spec(mesh, c.k.shape[-3], c.k.shape[-1])
            espec = (P(None, None, "model") if c.k_exp.shape[-1] % m == 0
                     else P(None, None, None))
            return QuantPagedKVCache(spec, spec, espec, espec, bits=c.bits)
        return PagedKVCache(k=leaf_spec_f, v=leaf_spec_f)

    leaf_spec_f = _pool_leaf_spec(mesh, cfg.kv_heads_phys, cfg.head_dim)
    if pools is None:
        return tuple(
            tuple(PagedKVCache(k=leaf_spec_f, v=leaf_spec_f) for _ in period)
            for period, _ in cfg.groups)
    return jax.tree.map(
        leaf_spec, pools,
        is_leaf=lambda c: isinstance(c, (PagedKVCache, QuantPagedKVCache)))


def place_paged_pools(pools, cfg: ModelConfig, mesh: Mesh):
    return jax.device_put(
        pools, named_shardings(mesh, paged_pool_pspecs(cfg, mesh, pools)))


def place_slot_state(state, mesh: Mesh):
    """Per-slot decode state: the slot axis over `data`, as the decode
    activations shard the slot batch (replicated when slots don't divide)."""
    d = _axis_size(mesh, "data")
    return jax.tree.map(
        lambda x: jax.device_put(x, NamedSharding(
            mesh, P("data") if x.shape[0] % d == 0 else P())), state)


def mesh_summary(mesh: Mesh) -> str:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    body = "x".join(f"{a}={sizes[a]}" for a in mesh.axis_names)
    return f"mesh({body}, devices={int(np.prod(mesh.devices.shape))})"


def publish_mesh_metrics(sm, mesh: Optional[Mesh]) -> None:
    """Record the mesh topology in the engine's metric registry.

    Serving metrics are *engine-level aggregates*: the tick loop is SPMD, so
    every device sees the same schedule, the same admissions, and the same
    token counts — a per-device breakdown of those series would carry no
    information. The per-device quantities that DO differ (a shard's slice
    of the KV pool, a shard's share of gather traffic) are the engine-level
    value divided by the axis size recorded here; dashboards derive them
    from this gauge instead of the engine exporting near-duplicate series.
    `sm` is a telemetry.ServingMetrics; with no mesh every axis reads 1.
    """
    if mesh is None:
        sm.mesh_devices.set(1.0, axis="data")
        sm.mesh_devices.set(1.0, axis="model")
        return
    for axis, size in zip(mesh.axis_names, mesh.devices.shape):
        sm.mesh_devices.set(float(size), axis=str(axis))
