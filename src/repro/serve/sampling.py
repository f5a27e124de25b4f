"""Static-shape token sampling for continuous-batching decode.

Every slot carries its own SamplingParams; the engine packs them into dense
(slots,)-shaped arrays so one jitted `sample` call serves a heterogeneous
batch (greedy next to top-p next to top-k) without any shape dependence on
the mix — the serving invariant is that nothing here ever retraces.

temperature <= 0 means greedy; top_k <= 0 disables top-k; top_p >= 1 disables
nucleus filtering. Filters compose (top-k mask AND top-p mask), matching the
usual serving semantics.

The work follows the batch at run time, in one compiled program: a batch
whose slots are all greedy pays one argmax over the vocabulary; a batch with
any sampling slot pays the full path (a sort of every slot's vocabulary, a
softmax and a cumsum for the thresholds, a categorical draw).  The engine
marks the slots that are not decoding greedy (`for_active`), so a finished
sampled request whose parameters stay packed until its slot is reused does
not hold the batch off the argmax.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -1e30


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Per-request decode parameters (host-side, hashable)."""
    temperature: float = 0.0      # <= 0 -> greedy
    top_k: int = 0                # <= 0 -> off
    top_p: float = 1.0            # >= 1 -> off

    @property
    def greedy(self) -> bool:
        return self.temperature <= 0.0


class SamplerBatch(NamedTuple):
    """SamplingParams packed per slot for the jitted sampler."""
    temperature: jax.Array    # (slots,) f32
    top_k: jax.Array          # (slots,) i32
    top_p: jax.Array          # (slots,) f32
    greedy: jax.Array         # (slots,) bool


def pack(params: Sequence[SamplingParams]) -> SamplerBatch:
    return SamplerBatch(
        temperature=np.array([p.temperature for p in params], np.float32),
        top_k=np.array([p.top_k for p in params], np.int32),
        top_p=np.array([p.top_p for p in params], np.float32),
        greedy=np.array([p.greedy for p in params], bool),
    )


class SlotKeys(NamedTuple):
    """Per-slot PRNG keys, not yet folded: slot i draws with
    fold_in(fold_in(key, seed[i]), step[i]).  `sample` folds them only when
    some slot samples."""
    key: jax.Array
    seed: jax.Array     # (slots,) int32
    step: jax.Array     # (slots,) int32

    def fold(self) -> jax.Array:
        return jax.vmap(
            lambda s, c: jax.random.fold_in(jax.random.fold_in(self.key, s), c)
        )(self.seed, self.step)


def for_active(sp: SamplerBatch, active: jax.Array) -> SamplerBatch:
    """`sp` with every slot that is not decoding marked greedy.  Such a
    slot's token is dropped, so drawing it greedily changes nothing served,
    and a batch whose active slots are all greedy takes the argmax path."""
    return sp._replace(greedy=sp.greedy | ~active)


def sample(logits: jax.Array, sp: SamplerBatch, key) -> jax.Array:
    """Draw one token per slot. logits: (slots, vocab) -> (slots,) int32.

    One `lax.cond` on the batch: when every slot is greedy the tokens are
    the argmax and nothing else runs; otherwise `_draw` runs for the whole
    batch.  Both branches sit in the named scope ``sample``.

    `key` is one PRNG key for the whole batch, a (slots,)-batch of per-slot
    keys, or `SlotKeys`.  The engine derives one key per slot from the
    request's identity and its decode progress, never from the global tick —
    sampled streams are then invariant to scheduling (prefix-cache hits and
    chunked prefill change *when* a slot decodes, and must not change its
    tokens).
    """
    with jax.named_scope("sample"):
        logits = logits.astype(jnp.float32)
        return jax.lax.cond(jnp.all(sp.greedy), _argmax, _draw,
                            logits, sp, key)


def _argmax(logits: jax.Array, sp: SamplerBatch, key) -> jax.Array:
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def _draw(logits: jax.Array, sp: SamplerBatch, key) -> jax.Array:
    """The full path: greedy slots take the argmax, the others draw.

    One full-vocab descending sort is shared by the top-k threshold and the
    top-p cumulative cutoff; both reduce to per-slot scalar thresholds applied
    in the original token order, so ties never permute token identity.
    """
    if isinstance(key, SlotKeys):
        key = key.fold()
    vocab = logits.shape[-1]
    greedy_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    temp = jnp.maximum(sp.temperature, 1e-6)[:, None]
    scaled = logits / temp
    sorted_desc = -jnp.sort(-scaled, axis=-1)

    # top-k: keep everything >= the k-th largest value
    k = jnp.where(sp.top_k > 0, jnp.clip(sp.top_k, 1, vocab), vocab)
    kth = jnp.take_along_axis(sorted_desc, (k - 1)[:, None], axis=-1)
    keep_k = scaled >= kth

    # top-p: keep the smallest prefix of the sorted distribution covering p;
    # the top token is always kept (top_p=0 must not empty the nucleus)
    probs = jax.nn.softmax(sorted_desc, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep_sorted = (cum - probs) < jnp.clip(sp.top_p, 0.0, 1.0)[:, None]
    keep_sorted = keep_sorted.at[:, 0].set(True)
    cutoff = jnp.min(jnp.where(keep_sorted, sorted_desc, jnp.inf), axis=-1)
    keep_p = scaled >= cutoff[:, None]

    masked = jnp.where(keep_k & keep_p, scaled, NEG_INF)
    typed = jnp.issubdtype(key.dtype, jax.dtypes.prng_key)
    if key.ndim >= 2 or (typed and key.ndim >= 1):
        sampled = jax.vmap(jax.random.categorical)(key, masked)
        sampled = sampled.astype(jnp.int32)
    else:
        sampled = jax.random.categorical(key, masked, axis=-1)
        sampled = sampled.astype(jnp.int32)
    return jnp.where(sp.greedy, greedy_tok, sampled)
