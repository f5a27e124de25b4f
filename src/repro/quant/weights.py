"""Weight-only quantization with power-of-two scales — packed param planes.

The serving engine's dominant decode-bandwidth term at small batch is the
weight stream: every parameter byte is read once per step.  This module
packs parameter tensors into the same storage discipline as the quantized
KV pools (quant/kv.py): int8 planes at 8 bits, split-halves int4 nibbles at
4 bits, plus a *scale-exponent plane* — one signed-byte exponent per
(contraction tile, out-channel), frexp-derived so a stored ``q`` represents
``q * 2**e`` and dequantization is an exponent add (a shift), never a float
multiply.  All scale arithmetic comes from quant/pot.py, shared verbatim
with the KV cache.

Layout.  Each packable tensor designates one *contraction axis* (the axis a
matmul reduces over), indexed **from the right** (negative) so the same
static metadata stays correct when ``lax.scan`` strips a stacked group's
leading repeats axis.  The contraction axis of length K is split into
``K // tile`` tiles (``tile`` = the largest divisor of K that is <=
``tile_k``, so no padding is ever needed); the exponent plane replaces the
contraction axis with the tile count.  At 4 bits each tile is packed
split-halves *within the tile* — byte ``i`` holds tile element ``i`` (low
nibble) and ``i + tile//2`` (high nibble) — so a Pallas kernel's k-th tile
block unpacks with a sign-extend + concat and dequantizes against a single
``(1, out)`` exponent row in VMEM (kernels/matmul_wq.py).

:class:`QuantWeight` is a registered pytree whose children are the payload
and exponent arrays; bits/axis/K/tile ride as static aux data, so packed
params thread through jit, donation, ``lax.scan`` and the sharding layer
with zero recompiles and no special cases.

Which tensors pack (per ``PrecisionPolicy.weight_bits_for``, layer names
``group{gi}.l{li}`` plus ``embed`` / ``head``): plain attention projections
(wq/wk/wv/wo, self- and cross-attention), the MLP matmuls
(w_gate/w_up/w_down), and the vocabulary tensors.  Norm scales and biases
stay float (negligible bytes); MLA / SSM / MoE subtrees keep the float path.
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.quant.pot import (dequantize_pot, pack_int4, pot_exponent,
                             quantize_pot)

WEIGHT_BITS = (16, 8, 4)

# default contraction-tile width: one exponent per 512 reduced elements per
# out-channel (<= 0.2% metadata at int8); per-tensor the effective tile is
# the largest divisor of K not exceeding this, so small dims collapse to a
# single whole-K tile
WQ_TILE_K = 512


def validate_weight_bits(bits: int) -> None:
    if bits not in WEIGHT_BITS:
        raise ValueError(
            f"weight_bits must be one of {WEIGHT_BITS}, got {bits}")


def effective_tile(kdim: int, tile_k: int = WQ_TILE_K) -> int:
    """Largest divisor of the contraction length <= tile_k (whole K when it
    already fits).  Deterministic and padding-free by construction."""
    return kdim if kdim <= tile_k else math.gcd(kdim, tile_k)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class QuantWeight:
    """One packed parameter tensor: payload + exponent plane.

    ``q``     int8 payload; the original shape with the contraction axis
              halved at 4 bits (split-halves nibbles within each tile).
    ``e``     int8 exponent plane; the original shape with the contraction
              axis replaced by the tile count ``kdim // tile``.
    ``bits``  4 or 8 (16-bit tensors are never wrapped).
    ``caxis`` contraction axis as a negative index — stable under scan
              slicing of a stacked group's leading repeats axis.
    ``kdim``  original (unpacked) contraction length.
    ``tile``  effective contraction-tile width (divides kdim).
    """
    q: jax.Array
    e: jax.Array
    bits: int
    caxis: int
    kdim: int
    tile: int

    def tree_flatten(self):
        return (self.q, self.e), (self.bits, self.caxis, self.kdim, self.tile)

    @classmethod
    def tree_unflatten(cls, aux, children):
        q, e = children
        bits, caxis, kdim, tile = aux
        return cls(q=q, e=e, bits=bits, caxis=caxis, kdim=kdim, tile=tile)


def pack_tensor(w: jax.Array, bits: int, caxis: int,
                tile_k: int = WQ_TILE_K) -> QuantWeight:
    """Quantize one f32 tensor onto the 2^e grid along ``caxis``.

    The exponent is per (contraction tile, out-channel): amax reduces over
    the tile axis only, so every other axis (including a stacked group's
    repeats axis) keeps its own scale row.
    """
    validate_weight_bits(bits)
    if bits == 16:
        raise ValueError("16-bit tensors stay raw float — do not pack them")
    ca = caxis if caxis < 0 else caxis - w.ndim
    k = w.shape[ca]
    t = effective_tile(k, tile_k)
    if bits == 4 and t % 2:
        raise ValueError(
            f"weight_bits=4 packs two values per byte along the contraction "
            f"axis; axis length {k} (tile {t}) is odd — use an even dim or "
            "weight_bits >= 8")
    wt = jnp.moveaxis(w.astype(jnp.float32), ca, -1)
    lead = wt.shape[:-1]
    wt = wt.reshape(lead + (k // t, t))
    amax = jnp.max(jnp.abs(wt), axis=-1)                     # (..., k_tiles)
    e = pot_exponent(amax, bits)
    q = quantize_pot(wt, e[..., None], bits)                 # (..., kt, t)
    if bits == 4:
        q = pack_int4(q)                                     # (..., kt, t//2)
    payload = jnp.moveaxis(q.reshape(lead + (-1,)), -1, ca)
    return QuantWeight(q=payload, e=jnp.moveaxis(e, -1, ca),
                       bits=bits, caxis=ca, kdim=k, tile=t)


def dense(w: Any) -> jax.Array:
    """Materialize the f32 view of a packed tensor; identity on raw arrays.

    This is the gather/dense fallback every forward path routes through on
    CPU and under a mesh — the same unpack_int4/dequantize_pot helpers the
    Pallas kernel applies per tile in VMEM, so kernel and fallback
    dequantize bit-identically.
    """
    if not isinstance(w, QuantWeight):
        return w
    # everything happens in place along the contraction axis — no transposes,
    # and no concatenate: XLA's SPMD partitioner miscompiles concat along an
    # axis it shards (wrong values on the CPU backend, any dtype), and GSPMD
    # may shard any internal axis regardless of the input specs.  The nibble
    # halves land via two complementary pads + add instead — pad partitions
    # correctly, and the padded regions are zeros so the add is exact.
    ca = w.caxis + w.q.ndim
    kt = w.kdim // w.tile
    shape = w.q.shape
    q = w.q.reshape(shape[:ca] + (kt, shape[ca] // kt) + shape[ca + 1:])
    if w.bits == 4:
        # split-halves within each tile: low nibbles are tile elements
        # [0, t/2), high nibbles [t/2, t) along the tile axis
        half = w.tile // 2
        pads = [(0, 0)] * q.ndim
        lo_pads, hi_pads = list(pads), list(pads)
        lo_pads[ca + 1] = (0, half)
        hi_pads[ca + 1] = (half, 0)
        q = (jnp.pad((q << 4) >> 4, lo_pads) + jnp.pad(q >> 4, hi_pads))
    e = jnp.expand_dims(w.e, ca + 1)          # (..., kt, 1, ...) broadcast
    out = dequantize_pot(q, e)
    return out.reshape(shape[:ca] + (w.kdim,) + shape[ca + 1:])


def take_rows(w: Any, idx: jax.Array) -> jax.Array:
    """Embedding lookup: gather *packed* rows + exponent rows along axis 0,
    then dequantize only the gathered slice — lookup traffic moves at
    weight_bits width, like the KV gather fallback."""
    if not isinstance(w, QuantWeight):
        return jnp.take(w, idx, axis=0)
    if w.caxis == -w.q.ndim:
        raise ValueError("take_rows needs axis 0 distinct from the packed "
                         f"contraction axis (caxis={w.caxis})")
    sub = QuantWeight(q=jnp.take(w.q, idx, axis=0),
                      e=jnp.take(w.e, idx, axis=0),
                      bits=w.bits, caxis=w.caxis, kdim=w.kdim, tile=w.tile)
    return dense(sub)


# ---------------------------------------------------------------------------
# Matmul dispatch: Pallas kernel on TPU, dense fallback elsewhere
# ---------------------------------------------------------------------------

# None = auto (kernel on TPU, dense elsewhere); "dense" | "kernel" |
# "kernel_interpret" force a path (tests drive the engine through the
# interpreted kernel on CPU with use_impl)
_IMPL: Optional[str] = None


@contextlib.contextmanager
def use_impl(impl: Optional[str]):
    """Force the weight-matmul implementation within a scope (static Python
    state read at trace time — switching it changes the traced program, so
    hold it fixed across an engine's lifetime)."""
    global _IMPL
    if impl not in (None, "dense", "kernel", "kernel_interpret"):
        raise ValueError(f"unknown weight-matmul impl {impl!r}")
    prev, _IMPL = _IMPL, impl
    try:
        yield
    finally:
        _IMPL = prev


def active_impl() -> str:
    if _IMPL is not None:
        return _IMPL
    return "kernel" if jax.default_backend() == "tpu" else "dense"


def matmul(x: jax.Array, w: Any) -> jax.Array:
    """``x @ w`` where ``w`` may be a packed 2-D weight.

    Kernel path (TPU, or forced via use_impl; a forced "kernel" off the TPU
    raises rather than interpreting): tiles DMA'd packed into VMEM
    and dequantized per k-tile inside the Pallas matmul.  Everywhere else —
    raw arrays, >2-D projections, mesh/CPU runs — the dense fallback keeps
    results exact.
    """
    if not isinstance(w, QuantWeight):
        return x @ w
    impl = active_impl()
    if impl != "dense" and w.q.ndim == 2 and w.caxis == -2:
        if impl == "kernel" and jax.default_backend() != "tpu":
            raise RuntimeError(
                "weight-matmul impl 'kernel' needs a TPU backend, found "
                f"{jax.default_backend()!r}; use 'kernel_interpret' to run "
                "the kernel in the Pallas interpreter")
        from repro.kernels import ops
        return ops.matmul_wq(x, w, interpret=(impl == "kernel_interpret"))
    return x @ dense(w)


# ---------------------------------------------------------------------------
# Parameter-tree packing under a PrecisionPolicy
# ---------------------------------------------------------------------------

# contraction axes, from the right, of every packable tensor (stacked group
# leaves carry one extra leading repeats axis — negative indices don't care)
_ATTN_AXES = {"wq": -3, "wk": -3, "wv": -3, "wo": -2}
_MLP_AXES = {"w_gate": -2, "w_up": -2, "w_down": -2}


def weight_bits_by_layer(cfg, policy) -> Dict[str, int]:
    """Per-layer weight bits from the policy (16 everywhere when None).
    Names follow the param tree — ``group{gi}.l{li}`` — plus ``embed`` and
    (untied) ``head``."""
    out: Dict[str, int] = {}
    for gi, (period, _) in enumerate(cfg.groups):
        for li in range(len(period)):
            name = f"group{gi}.l{li}"
            out[name] = policy.weight_bits_for(name) if policy else 16
    out["embed"] = policy.weight_bits_for("embed") if policy else 16
    if not cfg.tie_embeddings:
        out["head"] = policy.weight_bits_for("head") if policy else 16
    return out


def validate_weight_packing(cfg, policy) -> None:
    """Eager packing validation, mirroring serve/kv_cache.validate_pool_
    packing: every int4 evenness assumption is checked at policy-build time
    with a pointed message instead of surfacing as an opaque reshape failure
    inside the first traced step."""
    def _even(dim_name: str, dim: int, where: str):
        if dim % 2:
            raise ValueError(
                f"{cfg.name} ({where}): weight_bits=4 packs two values per "
                f"byte along the contraction axis; {dim_name}={dim} is odd "
                "— pad the model to an even value or use weight_bits >= 8")
    for name, bits in weight_bits_by_layer(cfg, policy).items():
        validate_weight_bits(bits)
        if bits != 4:
            continue
        if name in ("embed", "head"):
            _even("d_model", cfg.d_model, name)
            continue
        gname, lname = name.split(".")
        spec = cfg.groups[int(gname[len("group"):])][0][int(lname[1:])]
        if spec.kind == "attn" and cfg.mla is None:
            _even("d_model", cfg.d_model, name)
            _even("head_dim", cfg.head_dim, name)
        if spec.cross_attn:
            _even("d_model", cfg.d_model, name)
            _even("head_dim", cfg.head_dim, name)
        if spec.mlp not in ("none", "moe"):
            _even("d_model", cfg.d_model, name)
            _even("d_ff", cfg.d_ff, name)


def _pack_subtree(sub: dict, axes: Dict[str, int], bits: int,
                  tile_k: int) -> dict:
    out = dict(sub)
    for key, caxis in axes.items():
        if key in out and not isinstance(out[key], QuantWeight):
            out[key] = pack_tensor(out[key], bits, caxis, tile_k)
    return out


def pack_params(params: dict, cfg, policy, tile_k: int = WQ_TILE_K) -> dict:
    """Pack a model's parameter tree once, per the policy's weight rules.

    Returns a new tree sharing every untouched leaf; packable tensors in
    <16-bit layers become :class:`QuantWeight` leaves.  Stacked group
    params pack whole (the exponent plane keeps a scale row per repeat —
    amax reduces over the tile axis only), and ``lax.scan`` slices the
    payload/exponent children along the repeats axis while the static aux
    (negative caxis) stays valid.
    """
    validate_weight_packing(cfg, policy)
    out = dict(params)
    for gi, (period, _) in enumerate(cfg.groups):
        group = dict(out[f"group{gi}"])
        changed = False
        for li, spec in enumerate(period):
            bits = policy.weight_bits_for(f"group{gi}.l{li}")
            if bits == 16:
                continue
            layer = dict(group[f"l{li}"])
            if spec.kind == "attn" and cfg.mla is None and "attn" in layer:
                layer["attn"] = _pack_subtree(layer["attn"], _ATTN_AXES,
                                              bits, tile_k)
            if spec.cross_attn and "xattn" in layer:
                layer["xattn"] = _pack_subtree(layer["xattn"], _ATTN_AXES,
                                               bits, tile_k)
            if "mlp" in layer:
                layer["mlp"] = _pack_subtree(layer["mlp"], _MLP_AXES,
                                             bits, tile_k)
            group[f"l{li}"] = layer
            changed = True
        if changed:
            out[f"group{gi}"] = group
    eb = policy.weight_bits_for("embed")
    if eb != 16:
        # caxis = d_model (the tied-logits contraction); vocab rows stay
        # whole so take_rows can gather packed rows + their exponent rows
        out["embed"] = pack_tensor(out["embed"], eb, -1, tile_k)
    if "head" in out:
        hb = policy.weight_bits_for("head")
        if hb != 16:
            out["head"] = pack_tensor(out["head"], hb, -2, tile_k)
    return out


def packed_param_bytes(params) -> int:
    """Total bytes of the parameter tree as stored (packed payloads +
    exponent planes + raw float leaves) — the model-bytes/step term every
    decode tick streams."""
    return int(sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(params)
                   if hasattr(leaf, "nbytes")))
