"""Continuous-batching serving engine: paged KV cache, chunked prefill with
radix-tree prefix reuse, decode-length buckets, pluggable admission
scheduling, and static-shape sampling — with a decode hot loop that stays on
device.

Request lifecycle: `submit()` enqueues; each `step()` (one decode tick) the
scheduler admits waiting requests into free slots, then a single fused
decode+sample+terminate jit advances every live slot one token. Slots whose
sequence hits EOS / max_tokens are flagged *inside* the decode jit; the host
learns about completions (and delivers tokens, recycles slots and blocks)
only when the pending tick buffer is drained — `poll()`, a tick with
admission pressure, or the pending cap — so the decode loop never blocks on a
device->host sync per token.

Paged prefill is a chunked state machine on an *absolute* grid: an admitted
prompt's context is computed in fixed `prefill_chunk`-token chunks (each a
jitted multi-query forward that writes the chunk's K/V through the slot's
block table and attends the already-resident prefix blocks), interleaved
with decode ticks under the scheduler's prefill-token budget — a long prompt
can no longer stall decode for its whole prefill. With
`EngineConfig.prefix_cache`, admission first matches the prompt against a
radix tree of block-aligned cached prefixes (serve/radix_cache.py), pins the
matched blocks into the slot's table, and prefills only the suffix chunks;
a partially-matched final block is duplicated copy-on-write. Because the
chunk grid, chunk-table buckets, and per-position programs never depend on
how much prefix was cached, cache-on and cache-off admissions produce
bit-identical pool contents and token streams — reuse only *skips* work.
(Dense/SSM backends keep the one-shot bucketed or exact-length prefill.)

Decode cost scales with live tokens, not pool capacity: the paged decode jit
is traced once per *decode block bucket* (kv_cache.decode_block_buckets) and
each tick slices the block table to the smallest bucket covering the longest
live sequence. Attention then runs either through the Pallas flash-decode
kernel (kernels/paged_attention.py — block-table-driven DMA, the TPU path) or
the bucketed dense gather (nn/attention.paged_view — the oracle and host-CPU
path); both touch O(live blocks) of KV, never O(blocks_per_slot).

KV precision is policy-driven, end to end: `EngineConfig.precision` (a
quant.policy.PrecisionPolicy; `kv_bits` is the uniform shorthand) assigns
per-layer KV-cache bits. 16-bit layers keep float pools; 8/4-bit layers
store packed int8 pools with per-(block, head) power-of-two scale exponents
(quant/kv.py) — written by the shared update paths, dequantized identically
by the Pallas kernel (in VMEM) and the gather fallback, sharded alongside
the payloads, COW-copied with their blocks, and accounted at packed width
by decode_cost's gather bytes. Everything below (buckets, chunk grid,
warmup, donation) is precision-agnostic: quantization changes array
contents and dtypes, never shapes, schedules, or trace counts. The one
behavioral difference: partial-block COW prefix reuse is disabled at
kv_bits < 16 (a donor block's shared scale exponent depends on its trailing
positions — see _match_prefix), so reuse rounds down to the chunk grid and
cache-on/off streams stay bit-identical at any fixed kv_bits.

Overload control is preemption, not refusal: when a waiting request cannot
reserve blocks while free slots exist, the engine evicts last-admitted
decode slots (LIFO — least progress lost), folds their generated tokens
into the prompt, and requeues them for bit-exact recompute through the same
chunk-grid prefill (see _preempt_slot; serve/frontdoor.py drives this from
an asyncio streaming API). cancel() releases a request's blocks/pins at any
lifecycle stage. Both reuse the ghost-slot mechanism drains already rely
on, so neither adds device ops or jit traces.

Static-shape invariants (serving never recompiles after warmup):
  * the decode+sample step sees (slots, 1) tokens, the same cache tree,
    (slots,)-shaped slot state and sampler params, and one block-table shape
    per decode bucket — `warmup()` traces every bucket up front;
  * prefill traces once per bucket length (len(buckets) variants, bounded);
  * per-request sampling heterogeneity lives in array *values*, never shapes,
    and the packed sampler batch is rebuilt only on admission, not per tick.
`compile_count()` reports the number of traces (not a cache-size proxy that
donation or cache eviction could mask) so tests can assert the invariant.

Cache backends:
  * paged (default for plain GQA/MHA decoders): block-pool storage with
    slot -> block-table indirection; long-context slots pay for the blocks
    they occupy, and pool admission control replaces slot * max_seq memory.
  * dense (SSM / MLA / enc-dec archs): the classic (slots, max_seq) buffers;
    prefill inserts one slot's rows via lax.dynamic_update_slice. SSM state
    is recurrent, so SSM-bearing archs prefill at exact prompt length
    (correct, but one trace per distinct length) instead of buckets.
"""
from __future__ import annotations

import dataclasses
import functools
import json
import re
import time
from collections import Counter, deque
from typing import (Any, Callable, Dict, List, NamedTuple, Optional,
                    Tuple)

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import lm
from repro.models.config import ModelConfig
from repro.nn.attention import (AttnQuant, CrossKV, KVCache, MLACache,
                                PagedState)
from repro.nn.mamba2 import SSMState
from repro.quant import weights as wq_lib
from repro.serve import faults as faults_lib
from repro.serve import kv_cache as kvc
from repro.serve import sampling as samp_lib
from repro.serve import spans as spans_lib
from repro.serve import telemetry as tel
from repro.serve import trace as trace_lib
from repro.serve.sampling import SamplingParams
from repro.serve.scheduler import RequestState, Scheduler

# Engine health states (docs/serving.md, Failure handling). HEALTHY serves
# normally; DEGRADED keeps in-flight streams running but the front door
# refuses new submits (watchdog trip, contained internal error); DRAINING is
# terminal — no new admissions (begin_draining lets queued work wait out a
# snapshot; close() drains and shuts down); HANDOFF is the transient state
# while live requests transfer to another engine, ending in DRAINING.
# Exported as the serve_health gauge (0/1/2/3) and on /healthz (200 only
# when healthy).
HEALTHY = "healthy"
DEGRADED = "degraded"
DRAINING = "draining"
HANDOFF = "handoff"
_HEALTH_CODE = {HEALTHY: 0, DEGRADED: 1, DRAINING: 2, HANDOFF: 3}


@dataclasses.dataclass
class Request:
    """User-facing request record. `out_tokens` is filled in as the engine
    generates (it aliases the live RequestState token list)."""
    rid: int
    prompt: np.ndarray            # (len,) int
    max_new_tokens: int = 32
    sampling: SamplingParams = dataclasses.field(default_factory=SamplingParams)
    encoder_frames: Optional[np.ndarray] = None   # (frames, d_model), enc-dec
    out_tokens: Optional[List[int]] = None
    deadline_ms: Optional[float] = None   # wall-clock budget from submit();
    # an expired request retires with reason "deadline" at the next tick
    # boundary, releasing blocks/pins/spans exactly like cancel()


@dataclasses.dataclass
class EngineConfig:
    slots: int = 8                # decode batch size (static)
    max_seq: int = 512            # per-slot prompt+generation capacity
    eos_id: int = 1
    paged: Optional[bool] = None  # None = auto (paged when arch supports it)
    page_size: int = 16           # tokens per KV block
    num_blocks: Optional[int] = None   # pool size; None = no oversubscription
    prefill_buckets: Optional[Tuple[int, ...]] = None
    decode_buckets: Optional[Tuple[int, ...]] = None  # live-block ladder;
    # None = auto power-of-two ladder up to blocks_per_slot (paged only)
    paged_impl: Optional[str] = None   # None = auto ("kernel" on TPU,
    # "gather" elsewhere/under a mesh) | "kernel" | "gather"
    attn_grau: Optional[Any] = None    # GRAUActivation-like (spec/s_in/s_out):
    # fuse the GRAU quantization epilogue on the paged attention output
    prefill_chunk: Optional[int] = None   # chunked-prefill grid step (paged;
    # must be a page_size multiple). None = auto: 32, rounded up to one page
    # for large page sizes. Prompts prefill in fixed chunks on an *absolute*
    # grid, interleaved with decode ticks
    prefill_token_budget: Optional[int] = None  # max prefill tokens per
    # tick across all admitted slots; None = one chunk per tick
    prefix_cache: bool = False    # radix-tree shared-prefix KV reuse
    # (paged only): admissions pin the longest cached block-aligned prefix
    # and prefill only the suffix
    precision: Optional[Any] = None   # quant.policy.PrecisionPolicy: per-layer
    # KV-cache bits (16 = float pools; 8/4 = packed int pools with per-block
    # power-of-two scale exponents). The one precision object the whole
    # datapath consumes — pools, kernels, gather fallback, COW all follow it
    kv_bits: Optional[int] = None     # shorthand: uniform KV precision
    # (builds kv_policy(kv_bits)); mutually exclusive with `precision`
    weight_bits: Optional[int] = None  # shorthand: uniform serving-weight
    # precision (16/8/4). <16 packs the parameter tree once at construction
    # into power-of-two-scaled int planes (quant/weights.py) that every
    # jitted step consumes directly. Composes with kv_bits (the two
    # shorthands build one PrecisionPolicy); mutually exclusive with
    # `precision`
    policy: str = "fcfs"          # "fcfs" | "prefill" (see serve/scheduler.py)
    max_prefills_per_tick: Optional[int] = None
    max_pending_ticks: int = 32   # force a host drain after this many
    # undelivered decode ticks (bounds ghost decode past an unseen EOS)
    preemption: bool = True       # KV-pressure preemption (paged only): when
    # a waiting request cannot reserve blocks while free slots exist, evict
    # last-admitted decode slots (their generated tokens fold into the
    # prompt; re-admission recomputes bit-exactly through chunked prefill)
    # instead of stalling the queue until blocks happen to free
    preempt_after_ticks: int = 8  # a blocked head must have waited this many
    # ticks (since submit, or since its own last preemption — anti-ping-pong)
    # before it may evict running requests
    admission_lookahead: int = 8  # scheduler head-of-line fix: how many
    # unadmittable queue entries pick() may look past (0 = strict FCFS)
    head_age_cap: int = 64        # fairness: once a blocked head has waited
    # this many ticks, lookahead is suspended (strict arrival order again)
    watchdog_ticks: Optional[float] = 8.0   # tick watchdog: a device step
    # exceeding watchdog_ticks x the rolling p99 tick time (and the floor
    # below) degrades the engine to DEGRADED instead of blocking forever;
    # None disables the watchdog
    watchdog_floor_s: float = 0.25          # absolute minimum trip threshold
    # (host-CPU tick noise is microseconds; a multiplier alone would trip on
    # scheduler jitter, not hangs)
    watchdog_recovery: int = 8    # consecutive in-threshold device steps
    # after a watchdog trip before the engine recovers to HEALTHY
    faults: Optional[Any] = None  # serve/faults.FaultPlan: deterministic
    # fault injection for chaos tests/benches. None (production) keeps every
    # injection site a single host-side None check
    journal: Optional[Any] = None  # serve/journal.RequestJournal: write-
    # ahead ledger of client-visible state (submits, delivered tokens,
    # retirements). The engine appends an epoch header at attach and
    # journals every submit / drained token / retire; ServeEngine.recover()
    # replays the file after a crash and resumes every live request
    # bit-exactly
    audit_interval: Optional[int] = None  # run audit() automatically every
    # N ticks (None = on-demand only); every run — automatic or on-demand —
    # increments the serve_audit_runs_total counter
    telemetry: bool = True        # metrics registry + lifecycle traces +
    # tick-phase timing. Entirely host-side: enabling it adds zero jit
    # traces and zero device syncs (benchmarks/serving_bench.py gates the
    # tokens/sec overhead at <= 5%); disabling compiles every publish site
    # down to a dead branch / no-op recorder
    trace_capacity: int = 8192    # lifecycle-trace ring-buffer bound
    seed: int = 0


# EngineConfig fields that hold live objects (or policies built from them)
# and therefore cannot round-trip through a JSON snapshot; snapshot() lists
# the non-None ones under "non_serializable" and restore() expects the
# caller to re-supply them via `overrides` when needed.
_ECFG_SKIP = ("faults", "journal", "attn_grau", "precision")


def _ecfg_to_dict(ecfg: EngineConfig) -> Tuple[Dict[str, Any], List[str]]:
    """(json-safe field dict, names of skipped non-serializable fields)."""
    d: Dict[str, Any] = {}
    skipped: List[str] = []
    for f in dataclasses.fields(EngineConfig):
        v = getattr(ecfg, f.name)
        if f.name in _ECFG_SKIP:
            if v is not None:
                skipped.append(f.name)
            continue
        d[f.name] = list(v) if isinstance(v, tuple) else v
    return d, skipped


def _ecfg_from_dict(d: Dict[str, Any],
                    overrides: Optional[Dict[str, Any]] = None
                    ) -> EngineConfig:
    kw = dict(d)
    for k in ("prefill_buckets", "decode_buckets"):
        if kw.get(k) is not None:
            kw[k] = tuple(kw[k])
    if overrides:
        kw.update(overrides)
    return EngineConfig(**kw)


class _CountingJit:
    """jax.jit wrapper counting actual traces (distinct compilations).

    The count increments inside the traced function, so nothing can mask a
    retrace: not donation-induced signature churn, not jit-cache eviction,
    and not the shape-only hashing a host-side fallback would do (weak-type
    or sharding-driven retraces have identical shapes). The previous
    implementation read the jit cache size, which a retrace that *replaces*
    an evicted entry leaves unchanged.

    Every call opens the host span ``serve.call.<name>`` (a compile lands
    inside the span of the program that compiled), and the program's ops
    carry the named scope ``<name>`` in the device trace.
    """

    def __init__(self, fn, name: str, donate_argnums=(), on_trace=None,
                 out_shardings=None):
        self.name = name
        self._span = f"serve.call.{name}"
        self._traces = 0

        def counted(*args):
            self._traces += 1
            if on_trace is not None:
                # host-side callback, runs only while tracing (never in the
                # compiled program): publishes the trace event to telemetry
                on_trace()
            with jax.named_scope(name):
                return fn(*args)

        kw = {} if out_shardings is None else {"out_shardings": out_shardings}
        self._jit = jax.jit(counted, donate_argnums=donate_argnums, **kw)

    def __call__(self, *args):
        with jax.profiler.TraceAnnotation(self._span):
            return self._jit(*args)

    @property
    def compiles(self) -> int:
        return self._traces


class _SlotState(NamedTuple):
    """Device-resident per-slot decode state, donated through the decode jit
    every tick (no host round-trip, no per-step buffer copies)."""
    last_tok: jax.Array    # (slots, 1) int32 — token fed to the next decode
    lengths: jax.Array     # (slots,) int32 — valid context length (paged pos)
    remaining: jax.Array   # (slots,) int32 — decode budget left
    active: jax.Array      # (slots,) bool — slot is generating
    sample_seed: jax.Array  # (slots,) int32 — per-request PRNG stream id
    sample_step: jax.Array  # (slots,) int32 — draws made for this request;
    # keys fold (seed, step), never the global tick, so sampled streams are
    # schedule-invariant (prefix-cache hits change ticks, not tokens)


class _TickRecord(NamedTuple):
    """One enqueued decode tick awaiting host-side delivery."""
    tick: int
    slots: Tuple[int, ...]   # host-believed active slots at enqueue time
    tokens: jax.Array        # (slots,) int32 sampled tokens (on device)
    done: jax.Array          # (slots,) bool fused EOS/max-token flags
    ok: jax.Array            # (slots,) bool per-slot finite-logits flags
    # (computed inside the decode jit — a (slots,) reduction, no extra
    # sync; checked host-side at drain so a NaN/Inf slot is quarantined
    # without touching its co-batched neighbours)


class ServeEngine:
    def __init__(self, cfg: ModelConfig, params, ecfg: EngineConfig,
                 dtype=jnp.float32, mesh=None):
        """`mesh` (optional jax Mesh with ("data", "model") axes) turns on
        sharded serving: params are placed tensor-parallel, KV storage is
        head-sharded over `model`, and the decode slot batch shards over
        `data` — see serve/sharding.py for the placement scheme and
        docs/sharding.md for how to run this on forced host devices."""
        self.cfg, self.params, self.ecfg = cfg, params, ecfg
        self.dtype = dtype
        self.mesh = mesh
        self._act = lm.make_act(cfg)

        # telemetry first, so every component below can publish into it.
        # All of it is host-side bookkeeping: registering metrics and
        # recording spans never enters a traced function, so the telemetry
        # flag cannot change shapes, schedules, trace counts, or token
        # streams — only whether the ledger is written.
        self.telemetry_enabled = bool(ecfg.telemetry)
        if self.telemetry_enabled:
            self.registry: Optional[tel.MetricsRegistry] = \
                tel.MetricsRegistry()
            self._tel: Optional[tel.ServingMetrics] = \
                tel.ServingMetrics(self.registry)
            self.trace = trace_lib.TraceRecorder(ecfg.trace_capacity)
        else:
            self.registry = None
            self._tel = None
            self.trace = trace_lib.NullTraceRecorder()
        self.trace.attach_owner(self)
        # host spans of the tick; with telemetry on they also time the
        # tick phases
        self.span = spans_lib.Spans(
            None if self._tel is None else self._tel.tick_phase)
        self._has_ssm = any(spec.kind == "mamba"
                            for period, _ in cfg.groups for spec in period)
        self.bucketed = not self._has_ssm

        paged_ok = kvc.paged_supported(cfg)
        self.paged = paged_ok if ecfg.paged is None else bool(ecfg.paged)
        if self.paged and not paged_ok:
            raise ValueError(f"{cfg.name}: paged KV cache unsupported "
                             "(SSM/MLA/enc-dec arch); use paged=False")

        if ecfg.paged_impl not in (None, "kernel", "gather"):
            raise ValueError(f"unknown paged_impl {ecfg.paged_impl!r}")
        if ecfg.paged_impl is not None and not self.paged:
            raise ValueError("paged_impl requires the paged backend")
        if ecfg.attn_grau is not None and not self.paged:
            raise ValueError("attn_grau epilogue requires the paged backend")
        if ecfg.paged_impl == "kernel" and mesh is not None:
            # the Pallas kernel has no GSPMD partitioning rule: under a mesh
            # it would silently rematerialize per-slot tensors on every step
            # (see serve/sharding.py); shard_map'ing it is the follow-up
            raise ValueError("paged_impl='kernel' is not supported under a "
                             "mesh; use the gather path (auto) for now")
        if ecfg.paged_impl is not None:
            self.paged_impl = ecfg.paged_impl
        else:
            # the Pallas kernel is the TPU fast path; on host backends its
            # interpret mode is correctness-only, so serving uses the
            # bucketed gather there (same O(live tokens) scaling)
            self.paged_impl = ("kernel" if jax.default_backend() == "tpu"
                               and mesh is None else "gather")
        self._attn_quant = None
        if ecfg.attn_grau is not None:
            g = ecfg.attn_grau
            self._attn_quant = AttnQuant(spec=g.spec, s_in=float(g.s_in),
                                         s_out=float(g.s_out))

        if ecfg.prefix_cache and not self.paged:
            raise ValueError("prefix_cache requires the paged backend")

        if ecfg.precision is not None and ecfg.kv_bits is not None:
            raise ValueError("pass either precision (a PrecisionPolicy) or "
                             "kv_bits (uniform shorthand), not both")
        if ecfg.precision is not None and ecfg.weight_bits is not None:
            raise ValueError("pass either precision (a PrecisionPolicy) or "
                             "weight_bits (uniform shorthand), not both")
        if ecfg.kv_bits is not None or ecfg.weight_bits is not None:
            from repro.quant.policy import PrecisionPolicy
            self.precision = PrecisionPolicy(
                kv_default_bits=(16 if ecfg.kv_bits is None
                                 else ecfg.kv_bits),
                weight_default_bits=(16 if ecfg.weight_bits is None
                                     else ecfg.weight_bits))
        else:
            self.precision = ecfg.precision
        self._kv_quant = (self.precision is not None
                          and self.precision.kv_quantized)
        self._wq = (self.precision is not None
                    and self.precision.weights_quantized)
        if self._kv_quant and not self.paged:
            raise ValueError("quantized KV (kv_bits < 16) requires the paged "
                             "backend: dense/SSM/MLA caches stay float")

        if self.paged:
            self.blocks_per_slot = kvc.blocks_for(ecfg.max_seq, ecfg.page_size)
            num_blocks = (ecfg.num_blocks if ecfg.num_blocks is not None else
                          kvc.pool_blocks(ecfg.slots, ecfg.max_seq,
                                          ecfg.page_size))
            self.allocator = kvc.BlockAllocator(num_blocks)
            self.caches = kvc.init_paged_caches(cfg, num_blocks,
                                                ecfg.page_size, dtype=dtype,
                                                policy=self.precision)
            if ecfg.prefill_chunk is None:
                # auto: 32 tokens, rounded up to a whole page so any valid
                # page_size works out of the box
                self.prefill_chunk = max(32, ecfg.page_size)
                self.prefill_chunk -= self.prefill_chunk % ecfg.page_size
            else:
                self.prefill_chunk = int(ecfg.prefill_chunk)
            if (self.prefill_chunk < ecfg.page_size
                    or self.prefill_chunk % ecfg.page_size):
                raise ValueError(
                    f"prefill_chunk={self.prefill_chunk} must be a positive "
                    f"multiple of page_size={ecfg.page_size}")
            budget = (ecfg.prefill_token_budget
                      if ecfg.prefill_token_budget is not None
                      else self.prefill_chunk)
            if budget < self.prefill_chunk:
                raise ValueError(
                    f"prefill_token_budget={budget} below one chunk "
                    f"({self.prefill_chunk}): admitted prompts could never "
                    "finish prefilling")
            self._prefill_budget = budget
            # the table carries chunk-grid spill columns past blocks_per_slot
            # (always NULL): the last grid chunk of a near-max_seq prompt may
            # cover positions past the slot's reservation, and those writes
            # must land in trash, not in a clamped (wrong) block
            self._chunk_cols = (self.blocks_per_slot
                                + self.prefill_chunk // ecfg.page_size)
            self.chunk_buckets = kvc.decode_block_buckets(self._chunk_cols)
            # widths organic traffic can actually reach (warmup traces
            # exactly these; ladder entries past the last grid chunk never
            # occur and would be wasted compiles)
            self.chunk_widths = tuple(sorted({
                kvc.chunk_table_width(p0, self.prefill_chunk,
                                      ecfg.page_size, self.chunk_buckets)
                for p0 in range(0, ecfg.max_seq - 1, self.prefill_chunk)}))
            self.block_table = np.zeros(
                (ecfg.slots, self._chunk_cols), np.int32)
            from repro.serve.radix_cache import RadixCache
            self.radix = (RadixCache(self.allocator, ecfg.page_size)
                          if ecfg.prefix_cache else None)
            if ecfg.decode_buckets is not None:
                self.decode_buckets = tuple(sorted(set(ecfg.decode_buckets)))
                if (self.decode_buckets[0] < 1
                        or self.decode_buckets[-1] != self.blocks_per_slot):
                    raise ValueError(
                        f"decode_buckets {self.decode_buckets} must be >= 1 "
                        f"and end at blocks_per_slot={self.blocks_per_slot}")
            else:
                self.decode_buckets = kvc.decode_block_buckets(
                    self.blocks_per_slot)
        else:
            self.caches = lm.init_caches(cfg, ecfg.slots, ecfg.max_seq,
                                         dtype=dtype)
            self.decode_buckets = ()
            self.radix = None

        if self._wq:
            # pack the parameter tree once at construction (validates int4
            # evenness eagerly); QuantWeight leaves carry bits/axis/K/tile
            # as static pytree aux, so every jitted step below traces once
            # per shape exactly as with raw float params — zero extra
            # compiles at any width
            # (one jitted program: eager packing of a published-width
            # vocab table would hold several f32 copies of it at once)
            self.params = jax.jit(functools.partial(
                wq_lib.pack_params, cfg=cfg, policy=self.precision))(
                    self.params)

        if mesh is not None:
            from repro.serve import sharding as shard_lib
            self.params = shard_lib.place_params(self.params, cfg, mesh)
            if self.paged:
                self.caches = shard_lib.place_paged_pools(self.caches, cfg,
                                                          mesh)
            else:
                self.caches = shard_lib.place_dense_caches(self.caches, cfg,
                                                           mesh, ecfg.slots)
            if self._tel is not None:
                shard_lib.publish_mesh_metrics(self._tel, mesh)
        elif self._tel is not None:
            # unsharded: every axis is size 1 (metrics are engine-level
            # aggregates either way — see sharding.publish_mesh_metrics)
            self._tel.mesh_devices.set(1.0, axis="data")
            self._tel.mesh_devices.set(1.0, axis="model")

        if ecfg.prefill_buckets is not None:
            self.buckets = tuple(sorted(ecfg.prefill_buckets))
        else:
            self.buckets = kvc.default_buckets(
                ecfg.max_seq, multiple=ecfg.page_size if self.paged else 1)
        if self.bucketed:
            # any admissible context (<= max_seq - 1 tokens) must fit a
            # bucket, or _admit would fail after resources were committed
            if max(self.buckets) < ecfg.max_seq - 1:
                raise ValueError(
                    f"largest prefill bucket {max(self.buckets)} does not "
                    f"cover max_seq - 1 = {ecfg.max_seq - 1}")
            if self.paged and any(b % ecfg.page_size for b in self.buckets):
                raise ValueError("paged prefill buckets must be multiples of "
                                 f"page_size={ecfg.page_size}: {self.buckets}")

        # host-side slot bookkeeping; the decode-path twin lives on device
        # in self._state (and is only read back at drain time)
        self.slot_req: List[Optional[RequestState]] = [None] * ecfg.slots
        self._host_len = np.zeros(ecfg.slots, np.int32)  # conservative shadow
        self._samp: List[SamplingParams] = [SamplingParams()] * ecfg.slots
        self._sp_packed = samp_lib.pack(self._samp)
        self._state = _SlotState(
            last_tok=jnp.zeros((ecfg.slots, 1), jnp.int32),
            lengths=jnp.zeros((ecfg.slots,), jnp.int32),
            remaining=jnp.zeros((ecfg.slots,), jnp.int32),
            active=jnp.zeros((ecfg.slots,), bool),
            sample_seed=jnp.zeros((ecfg.slots,), jnp.int32),
            sample_step=jnp.zeros((ecfg.slots,), jnp.int32),
        )
        self._pending: List[_TickRecord] = []
        self._prefilling: List[int] = []     # slots mid-chunked-prefill,
        # admission order; chunk grants rotate round-robin across them
        self._prefill_rr = 0

        if ecfg.preempt_after_ticks < 1:
            raise ValueError("preempt_after_ticks must be >= 1, got "
                             f"{ecfg.preempt_after_ticks}")
        self.scheduler = Scheduler(
            ecfg.policy, ecfg.max_prefills_per_tick,
            prefill_token_budget=(self._prefill_budget if self.paged
                                  else None),
            metrics=self._tel,
            lookahead=ecfg.admission_lookahead,
            head_age_cap=ecfg.head_age_cap)
        # frontdoor hooks: called per delivered token / per retirement at
        # drain time (host code, never inside a trace); None = no-op
        self.token_sink: Optional[Callable[[int, int], None]] = None
        self.retire_sink: Optional[Callable[[int, str], None]] = None
        self._metrics_server: Optional[Any] = None
        # fault containment (docs/serving.md, Failure handling)
        self.faults: Optional[faults_lib.FaultPlan] = ecfg.faults
        # durability: the write-ahead request journal (serve/journal.py).
        # Appends happen only at host-code points (submit, drain) — the
        # journal can never add a jit trace or device sync.
        self.journal = ecfg.journal
        self._owns_journal = False   # recover() builds and owns its writer
        if self.journal is not None:
            # one epoch header per engine attach: replay counts restarts
            self.journal.begin_epoch({"reason": "attach"})
        if ecfg.audit_interval is not None and ecfg.audit_interval < 1:
            raise ValueError("audit_interval must be >= 1, got "
                             f"{ecfg.audit_interval}")
        self._audit_interval = ecfg.audit_interval
        self._last_audit_tick = 0
        self._health = HEALTHY
        self.health_reason = ""
        self.errors: deque = deque(maxlen=32)   # contained exceptions
        self._has_deadlines = False   # sticky: set by the first deadline
        # submit, so deadline-free serving never scans for expiry
        if ecfg.watchdog_recovery < 1:
            raise ValueError("watchdog_recovery must be >= 1, got "
                             f"{ecfg.watchdog_recovery}")
        # rolling window of per-tick device-step sync times; the watchdog
        # arms once the window has enough samples for a stable p99 and trips
        # on max(floor, watchdog_ticks * p99)
        self._tick_window: deque = deque(maxlen=128)
        self._watchdog_arm = 16
        self._watchdog_ok_streak = 0
        self.stats: Dict[str, Any] = {"ticks": 0, "greedy_ticks": 0,
                                      "decode_tokens": 0,
                                      "prefill_tokens": 0,
                                      "cached_prefix_tokens": 0}
        self._key = jax.random.PRNGKey(ecfg.seed)
        self._requests: Dict[int, Request] = {}
        self._finished_unpolled: List[RequestState] = []

        # the cache tree and slot state are dead after every call
        # (immediately reassigned), so donate them: XLA aliases input->output
        # buffers in place instead of copying the KV pool per decoded token
        decode_fn, prefill_fn, reset_fn, chunk_fn = (
            self._decode_fn, self._prefill_fn, self._reset_fn, self._chunk_fn)
        cache_sh = state_sh = None
        if mesh is not None:
            # activation-sharding constraints must be live while these trace
            from repro.serve import sharding as shard_lib
            decode_fn = shard_lib.with_shard_ctx(decode_fn, mesh, cfg)
            prefill_fn = shard_lib.with_shard_ctx(prefill_fn, mesh, cfg)
            chunk_fn = shard_lib.with_shard_ctx(chunk_fn, mesh, cfg)
            # the donated trees leave every program in the placement they
            # were built with, so each program has one input signature and
            # warmup() traces exactly the signatures serving calls
            self._state = shard_lib.place_slot_state(self._state, mesh)
            cache_sh = jax.tree.map(lambda x: x.sharding, self.caches)
            state_sh = jax.tree.map(lambda x: x.sharding, self._state)
        def on_trace(name):
            # per-fn compile events into the registry; _CountingJit._traces
            # stays the authoritative count for compile_count()
            if self._tel is None:
                return None
            return self._tel.jit_traces.labels(fn=name).inc

        # the bodies as jitted (shard ctx included), for kernel_names()
        self._step_fns = {"decode": decode_fn, "prefill_chunk": chunk_fn}
        self._decode = _CountingJit(
            decode_fn, "decode", donate_argnums=(1, 2),
            on_trace=on_trace("decode"),
            out_shardings=(None if cache_sh is None
                           else (cache_sh, state_sh, None, None, None)))
        self._prefill = _CountingJit(prefill_fn, "prefill",
                                     donate_argnums=(3,),
                                     on_trace=on_trace("prefill"),
                                     out_shardings=cache_sh)
        self._reset = _CountingJit(reset_fn, "reset_slot",
                                   donate_argnums=(0,),
                                   on_trace=on_trace("reset_slot"),
                                   out_shardings=cache_sh)
        # chunked-prefill chunk forward + the copy-on-write block copy
        # (partial-block prefix reuse); paged engines only
        self._chunk = _CountingJit(chunk_fn, "prefill_chunk",
                                   donate_argnums=(2,),
                                   on_trace=on_trace("prefill_chunk"),
                                   out_shardings=cache_sh)
        self._copy = _CountingJit(self._copy_fn, "cow_copy",
                                  donate_argnums=(0,),
                                  on_trace=on_trace("cow_copy"),
                                  out_shardings=cache_sh)
        # numeric quarantine: zero a possibly-poisoned pool block before it
        # returns to the allocator (paged only; warmed alongside cow_copy so
        # fault handling never adds a trace)
        self._scrub = _CountingJit(self._scrub_fn, "scrub_block",
                                   donate_argnums=(0,),
                                   on_trace=on_trace("scrub_block"),
                                   out_shardings=cache_sh)
        self._jits = (self._decode, self._prefill, self._reset, self._chunk,
                      self._copy, self._scrub)

        # static metric entries are computed once; metrics() is then a cheap
        # merge of running aggregates — no per-call recomputation (and no
        # side effects), so callers may poll it freely
        wbits = sorted(set(wq_lib.weight_bits_by_layer(
            self.cfg, self.precision).values()))
        self._static_metrics: Dict[str, Any] = {
            "backend": "paged" if self.paged else "dense",
            "telemetry": self.telemetry_enabled,
            "weight_bits": wbits[0] if len(wbits) == 1 else list(wbits),
            "weights_quantized": self._wq,
            "weight_bytes": wq_lib.packed_param_bytes(self.params),
        }
        if self.paged:
            bits_tree = kvc.kv_bits_by_layer(self.cfg, self.precision)
            bits_flat = sorted({b for grp in bits_tree for b in grp})
            self._static_metrics.update({
                "paged_impl": self.paged_impl,
                "kv_bits": (bits_flat[0] if len(bits_flat) == 1
                            else list(bits_flat)),
                "kv_quantized": self._kv_quant,
                "decode_buckets": list(self.decode_buckets),
                "total_blocks": self.allocator.num_blocks,
                "prefill_chunk": self.prefill_chunk,
                "prefill_token_budget": self._prefill_budget,
                "prefix_cache": self.radix is not None,
            })
        if mesh is not None:
            from repro.serve import sharding as shard_lib
            self._static_metrics["mesh"] = shard_lib.mesh_summary(mesh)
        if self._tel is not None and self.paged:
            self._tel.pool_blocks_total.set(self.allocator.num_blocks)
        if self._tel is not None:
            self._tel.health.set(_HEALTH_CODE[self._health])
        self._publish_gauges()

    # --- jitted bodies ---------------------------------------------------

    def _decode_fn(self, params, caches, state, block_table, sp, key):
        """Fused global decode step + sampling + termination (static shapes).

        EOS/max-token flags are computed here so the host never has to sync
        per tick to decide whether a slot finished; inactive slots decode
        masked garbage (writes land in the null block / stale rows) and
        their state is held frozen by `state.active`.
        """
        paged = (PagedState(block_table, state.lengths)
                 if block_table is not None else None)
        logits, caches = lm.decode_step(params, self.cfg, state.last_tok,
                                        caches, act=self._act, paged=paged,
                                        paged_impl=self.paged_impl,
                                        attn_quant=self._attn_quant)
        # per-slot keys from (request stream id, draws so far): sampling is a
        # pure function of the request and its progress, not of when the
        # scheduler happened to run it
        last = logits[:, -1]
        nxt = samp_lib.sample(
            last, samp_lib.for_active(sp, state.active),
            samp_lib.SlotKeys(key, state.sample_seed, state.sample_step))
        # numeric guardrail: per-slot finite-logits flag, reduced on device
        # (one (slots,) bool rides the existing drain sync — no extra host
        # round trip, no per-token check). Inactive/ghost slots decode
        # masked garbage that may legitimately be non-finite; they are
        # exempted here and their outputs are dropped at drain anyway.
        ok = ~state.active | jnp.all(jnp.isfinite(
            last.astype(jnp.float32)), axis=-1)
        act_i = state.active.astype(jnp.int32)
        remaining = state.remaining - act_i
        done = state.active & ((nxt == self.ecfg.eos_id) | (remaining <= 0))
        state = _SlotState(
            last_tok=jnp.where(state.active[:, None], nxt[:, None],
                               state.last_tok),
            lengths=state.lengths + act_i,
            remaining=remaining,
            active=state.active & ~done,
            sample_seed=state.sample_seed,
            sample_step=state.sample_step + 1,
        )
        return caches, state, nxt, done, ok

    def _prefill_fn(self, params, tokens, true_length, caches, slot,
                    encoder_frames):
        """One admitted prompt on the *dense* backend: run prefill_step on a
        fresh (1, bucket) cache and insert it as the slot's row. (Paged
        prompts go through _chunk_fn — the chunked-prefill state machine —
        and never call this.)"""
        pcaches = lm.init_caches(self.cfg, 1, tokens.shape[1],
                                 dtype=self.dtype)
        _, filled = lm.prefill_step(params, self.cfg, tokens, pcaches,
                                    true_length=true_length, act=self._act,
                                    encoder_frames=encoder_frames)

        def ins(big, small):
            start = (0, slot) + (0,) * (big.ndim - 2)
            return jax.lax.dynamic_update_slice(
                big, small.astype(big.dtype), start)

        return jax.tree.map(ins, caches, filled)

    def _chunk_fn(self, params, tokens, caches, table_row, p0, ctx):
        """One chunk of the chunked-prefill state machine: tokens (1, C) at
        absolute positions p0..p0+C-1, written through the slot's (bucket-
        sliced) table row and attending the already-resident prefix blocks —
        cached (pinned from the radix tree) and freshly computed blocks are
        indistinguishable here, which is what keeps cache-on and cache-off
        admissions bit-identical. `ctx` (the row's real context length) only
        steers quantized pools' scale exponents away from chunk padding —
        it is a pure function of the request, so the invariant holds."""
        st = PagedState(table_row, p0, ctx)
        _, caches = lm.prefill_step(params, self.cfg, tokens, caches,
                                    act=self._act, paged=st,
                                    paged_impl=self.paged_impl,
                                    attn_quant=self._attn_quant)
        return caches

    def _copy_fn(self, caches, src, dst):
        """Copy-on-write: duplicate a partially-matched cached block into a
        slot-private block before decode writes into it."""
        return kvc.copy_pool_block(caches, src, dst)

    def _scrub_fn(self, caches, blk):
        """Numeric quarantine: zero one pool block (quant pools: payload +
        EXP_EMPTY exponents) before it returns to the allocator — a
        quarantined slot's KV may hold NaN/Inf, and recycled-block bytes are
        still read by the attention gather before masking."""
        return kvc.scrub_pool_block(caches, blk)

    def _reset_fn(self, caches, slot):
        """Zero one slot's recurrent state / cache lengths (empty-context
        admission on the exact-length SSM path)."""
        def fix(c):
            if isinstance(c, (KVCache, MLACache)):
                return c._replace(length=c.length.at[:, slot].set(0))
            if isinstance(c, SSMState):
                return SSMState(c.conv.at[:, slot].set(0),
                                c.ssm.at[:, slot].set(0))
            return c

        return jax.tree.map(
            fix, caches, is_leaf=lambda c: isinstance(
                c, (KVCache, MLACache, SSMState, CrossKV)))

    # --- submission / results -------------------------------------------

    def submit(self, req: Request) -> int:
        plen = int(len(req.prompt))
        if plen < 1:
            raise ValueError("empty prompt")
        if req.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if plen + req.max_new_tokens > self.ecfg.max_seq:
            raise ValueError(
                f"prompt ({plen}) + max_new_tokens ({req.max_new_tokens}) "
                f"exceeds max_seq ({self.ecfg.max_seq})")
        if self.paged:
            need = kvc.blocks_for(plen + req.max_new_tokens,
                                  self.ecfg.page_size)
            if need > self.allocator.num_blocks - 1:
                raise ValueError("request exceeds total KV pool capacity")
        if self.cfg.encoder is not None and req.encoder_frames is None:
            raise ValueError("enc-dec arch requires encoder_frames")
        if req.rid in self._requests:
            raise ValueError(f"duplicate rid {req.rid}")

        if req.deadline_ms is not None and req.deadline_ms <= 0:
            raise ValueError(f"deadline_ms must be > 0, got "
                             f"{req.deadline_ms}")
        rs = RequestState(rid=req.rid,
                          prompt=np.asarray(req.prompt, np.int32),
                          max_new_tokens=int(req.max_new_tokens),
                          sampling=req.sampling,
                          encoder_frames=req.encoder_frames,
                          deadline_ms=req.deadline_ms)
        if req.deadline_ms is not None:
            self._has_deadlines = True
        req.out_tokens = rs.out_tokens          # live alias
        self._requests[req.rid] = req
        if self.journal is not None:
            # WAL ordering: the submission is durable before the engine
            # acts on it — a crash after this line recovers the request
            self.journal.record_submit(
                req.rid, rs.prompt, rs.max_new_tokens,
                sampling={"temperature": req.sampling.temperature,
                          "top_k": req.sampling.top_k,
                          "top_p": req.sampling.top_p},
                deadline_ms=req.deadline_ms)
        self.scheduler.submit(rs, self.stats["ticks"], time.perf_counter())
        self.trace.record(req.rid, "submit", prompt_len=plen,
                          max_new_tokens=int(req.max_new_tokens))
        self.trace.record(req.rid, "queued",
                          queue_depth=len(self.scheduler.waiting))
        return req.rid

    def poll(self) -> List[Request]:
        """Requests finished since the last poll, in completion order.

        Draining happens here: every pending decode tick's tokens and
        termination flags are pulled to host in one batch, slots/blocks are
        recycled, and finished requests become deliverable. Delivered
        requests are dropped from the engine's live table (their rid becomes
        reusable); lifecycle records stay on scheduler.finished for metrics.
        """
        self._drain()
        return self.reap()

    def reap(self) -> List[Request]:
        """Deliver already-drained finished requests WITHOUT forcing a
        drain — poll() is drain() + reap(). The async front door uses this
        with drain(keep=1) so delivery never blocks on the tick that was
        just dispatched to the device."""
        out = [self._requests.pop(rs.rid) for rs in self._finished_unpolled]
        self._finished_unpolled = []
        return out

    # --- fault containment ------------------------------------------------

    @property
    def health(self) -> str:
        """Current health state: HEALTHY / DEGRADED / DRAINING / HANDOFF."""
        return self._health

    def _set_health(self, state: str, reason: str) -> None:
        if state == self._health:
            return
        self._health = state
        self.health_reason = reason
        # rid -1: an engine-level event, not a request span
        self.trace.record(-1, "health", state=state, reason=reason)
        if self._tel is not None:
            self._tel.health.set(_HEALTH_CODE[state])

    def record_error(self, exc: BaseException,
                     rid: Optional[int] = None) -> str:
        """Keep a contained exception visible: its type and message go into
        metrics()["errors"] (the newest 32) and are returned for the trace
        event of whatever the containment retires or degrades."""
        text = f"{type(exc).__name__}: {exc}"
        self.errors.append({"rid": rid, "error": text})
        return text

    def mark_degraded(self, reason: str) -> None:
        """Degrade the engine (front-door tick-loop containment, operator
        action). In-flight work keeps running; the front door refuses new
        submits and /healthz turns 503 until recovery."""
        if self._health == HEALTHY:
            self._set_health(DEGRADED, reason)

    def mark_healthy(self, reason: str = "recovered") -> None:
        """Explicit recovery from DEGRADED (the watchdog also auto-recovers
        after `watchdog_recovery` in-threshold device steps). A DRAINING
        engine never recovers — close() is terminal."""
        if self._health == DEGRADED:
            self._watchdog_ok_streak = 0
            self._set_health(HEALTHY, reason)

    def _fault(self, site: str, rid: Optional[int] = None,
               tick: Optional[int] = None) -> Optional[faults_lib.FaultSpec]:
        """Fire one injection site against the attached FaultPlan. The
        production cost of a site is the `faults is None` check at its
        caller; this helper is only reached with a plan attached."""
        spec = self.faults.fire(
            site, rid=rid,
            tick=self.stats["ticks"] if tick is None else tick)
        if spec is not None and self._tel is not None:
            self._tel.faults_injected(site=site).inc()
        return spec

    def _retire_unslotted(self, rs: RequestState, reason: str,
                          now: float, tick: int) -> None:
        """Retire a request that holds no slot and no blocks (still in the
        waiting queue, or an admission that was aborted before reserving):
        close the span, count the reason, make it deliverable."""
        self.scheduler.retire(rs, tick, now, reason)
        self.trace.record(rs.rid, "finish", reason=reason,
                          tokens=len(rs.out_tokens), decode_s=0.0,
                          tpot_s=0.0)
        if self.journal is not None:
            self.journal.record_retire(rs.rid, reason)
        self._finished_unpolled.append(rs)
        if self.retire_sink is not None:
            self.retire_sink(rs.rid, reason)

    def _retire_anywhere(self, rid: int, reason: str) -> bool:
        """Retire a live request wherever it is in the lifecycle — the
        shared containment path behind deadlines and step-level fault
        recovery (cancel() is the user-facing twin). Resources are released
        exactly like cancel(): waiting requests just close their span;
        slotted requests free blocks, unpin radix chains, and go
        ghost-active. Returns False if the rid is not live."""
        now = time.perf_counter()
        tick = self.stats["ticks"]
        for rs in self.scheduler.waiting:
            if rs.rid == rid:
                self.scheduler.waiting.remove(rs)
                self._retire_unslotted(rs, reason, now, tick)
                return True
        for slot, rs in enumerate(self.slot_req):
            if rs is not None and rs.rid == rid:
                if slot in self._prefilling:
                    self._prefilling.remove(slot)
                self._retire(slot, rs, reason, now, tick)
                return True
        return False

    def _enforce_deadlines(self) -> int:
        """Retire every live request whose deadline has expired (reason
        "deadline"), at a tick boundary. Pending ticks are drained first so
        tokens generated before expiry are delivered and a request that
        actually finished in flight keeps its real finish reason — the
        deadline never rolls back completed work. Returns retirements."""
        if not self._has_deadlines:
            return 0

        def expired(rs: RequestState, now: float) -> bool:
            return (rs.deadline_ms is not None
                    and (now - rs.submit_time) * 1e3 >= rs.deadline_ms)

        now = time.perf_counter()
        hit = [rs for rs in self.scheduler.waiting if expired(rs, now)]
        hit += [rs for rs in self.slot_req
                if rs is not None and expired(rs, now)]
        if not hit:
            return 0
        self._drain()
        n = 0
        now = time.perf_counter()
        for rs in hit:
            # the drain may have retired it (EOS won the race) — re-check
            if rs.finish_tick < 0 and self._retire_anywhere(
                    rs.rid, "deadline"):
                n += 1
        return n

    def audit(self) -> Dict[str, Any]:
        """Invariant audit: cross-check allocator refcounts against slot
        reservations and radix pins, reclaim provably-leaked references,
        and refresh the leak gauge. Safe to run on a live engine (drains
        first so host bookkeeping is current).

        Ownership model (one refcount per owner): a block is owed one
        reference per live slot listing it in `blocks` or `cached_blocks`,
        plus one if a radix node holds it; a radix node is owed one pin per
        live slot listing it in `radix_nodes`. Any *excess* actual refcount
        or pin is a leak with no possible owner — freed / clamped here and
        reported. A *deficit* (owners exceed the refcount) cannot be fixed
        safely (freeing the other owner's reference would corrupt it) and
        is only reported. Returns the report dict; `leaked_after` == 0 is
        the bench-gated invariant."""
        if self._tel is not None:
            self._tel.audit_runs.inc()
        self._drain()
        report: Dict[str, Any] = {
            "reclaimed_blocks": 0, "reclaimed_refs": 0,
            "reclaimed_pins": 0, "mismatches": [],
            "leaked_before": 0, "leaked_after": 0,
        }
        if not self.paged:
            return report
        alloc = self.allocator
        expected: Counter = Counter()
        pin_owners: Counter = Counter()
        for rs in self.slot_req:
            if rs is None:
                continue
            expected.update(rs.blocks)
            expected.update(rs.cached_blocks)
            for node in rs.radix_nodes:
                pin_owners[id(node)] += 1
        nodes = self.radix.nodes() if self.radix is not None else []
        for node in nodes:
            expected[node.block] += 1
        live = alloc.live_block_ids()
        report["leaked_before"] = sum(
            1 for b in live if expected[b] == 0)
        # excess pins first: an unpinned-only node keeps its block (cache-
        # owned), so pin reclamation never cascades into block reclamation
        for node in nodes:
            owed = pin_owners[id(node)]
            if node.pins > owed:
                report["reclaimed_pins"] += node.pins - owed
                report["mismatches"].append(
                    f"node {node.tokens[:4]}...: pins {node.pins} > "
                    f"owners {owed} (clamped)")
                node.pins = owed
            elif node.pins < owed:
                report["mismatches"].append(
                    f"node {node.tokens[:4]}...: pins {node.pins} < "
                    f"owners {owed} (unfixable deficit)")
        for b in live:
            actual = alloc.refcount(b)
            owed = expected[b]
            if actual > owed:
                excess = actual - owed
                alloc.free([b] * excess)
                report["reclaimed_refs"] += excess
                if owed == 0:
                    report["reclaimed_blocks"] += 1
            elif actual < owed:
                report["mismatches"].append(
                    f"block {b}: refcount {actual} < owners {owed} "
                    "(unfixable deficit)")
        report["leaked_after"] = sum(
            1 for b in alloc.live_block_ids() if expected[b] == 0)
        self._publish_gauges()
        return report

    # --- admission -------------------------------------------------------

    def _blocks_needed(self, rs: RequestState) -> int:
        return kvc.blocks_for(rs.prompt_len + rs.max_new_tokens,
                              self.ecfg.page_size)

    def _match_prefix(self, rs: RequestState):
        """Longest usable cached prefix for `rs` under the chunk grid:
        (match, blocks, nodes, cached_tokens, cow_src). Pure — the engine
        commits the match (LRU bump + hit/miss accounting) only once the
        admission actually lands.

        Full coverage (the whole context cached — block-aligned, or via a
        copy-on-write partial block) uses every matched block; otherwise
        reuse rounds *down* to a chunk-grid multiple so the suffix chunks
        land on the same absolute grid positions — and therefore run the
        same compiled programs on the same inputs — as a cache-off
        admission. That rounding is what makes cache-on/cache-off token
        streams and pool contents bit-identical.
        """
        ctx = rs.prompt_len - 1
        if self.radix is None or ctx <= 0:
            return None, [], [], 0, None
        # memoized per request on the radix mutation clock: _can_admit and
        # _admit_paged (and blocked-head retries across quiet ticks) share
        # one trie walk instead of re-tupling the whole context each time
        memo = rs.match_memo
        if memo is not None and memo[0] == self.radix.clock:
            return memo[1]
        m = self.radix.match(rs.prompt[:ctx])
        if self._kv_quant and m.cow_src is not None:
            # quantized pools share one scale exponent per block, and a
            # donor block's exponent depends on *its* trailing positions —
            # copying it for a partial match would make the reused prefix's
            # dequantized values depend on the donor's suffix, breaking the
            # cache-on/off bit-exactness contract. Full-block reuse keeps it
            # (identical writes -> identical payload + exponent), so
            # partial-block COW is simply not taken at kv_bits < 16.
            m = dataclasses.replace(m, cow_src=None, cow_node=None,
                                    cow_tokens=0)
        if m.tokens_matched + m.cow_tokens >= ctx:
            out = (m, m.blocks, m.nodes, ctx, m.cow_src)
        else:
            used = ((m.tokens_matched // self.prefill_chunk)
                    * self.prefill_chunk)
            nb = used // self.ecfg.page_size
            out = (m, m.blocks[:nb], m.nodes[:nb], used, None)
        rs.match_memo = (self.radix.clock, out)
        return out

    def _can_admit(self, rs: RequestState) -> bool:
        if not self.paged:
            return True
        need = self._blocks_needed(rs)
        if self.radix is None:
            return self.allocator.can_alloc(need)
        _, blocks, _, _, _ = self._match_prefix(rs)
        if need - len(blocks) <= self.allocator.free_blocks:
            return True      # fits without eviction: skip the trie walk
        # cache-only blocks are evictable headroom, but the matched chain is
        # about to be pinned — never count it as both reused and evictable
        headroom = max(0, self.radix.evictable_blocks() - len(blocks))
        return need - len(blocks) <= self.allocator.free_blocks + headroom

    def _admit(self, rs: RequestState) -> bool:
        """Admit one picked request; False means the reservation no longer
        fits (same-tick over-commit) and the caller must requeue it."""
        slot = self.slot_req.index(None)
        ctx = rs.prompt_len - 1       # prompt[-1] is fed by the first decode
        if self.paged:
            return self._admit_paged(slot, rs, ctx)

        # dense backend: one-shot prefill at admission (bucketed, or exact
        # length for recurrent SSM state), then immediate activation
        bucket = (kvc.bucket_for(max(ctx, 1), self.buckets)
                  if self.bucketed else None)
        if self.bucketed:
            toks = np.zeros((1, bucket), np.int32)
            toks[0, :ctx] = rs.prompt[:ctx]
            tl = np.array([ctx], np.int32)
            ef = (rs.encoder_frames[None].astype(np.float32)
                  if rs.encoder_frames is not None else None)
            self.caches = self._prefill(self.params, toks, tl, self.caches,
                                        np.int32(slot), ef)
        elif ctx == 0:
            self.caches = self._reset(self.caches, np.int32(slot))
        else:
            # exact-length prefill: padding would corrupt recurrent SSM state
            toks = rs.prompt[None, :ctx].astype(np.int32)
            tl = np.array([ctx], np.int32)
            self.caches = self._prefill(self.params, toks, tl, self.caches,
                                        np.int32(slot), None)
        self.stats["prefill_tokens"] += ctx
        rs.computed_prefill_tokens = ctx
        rs.prefill_pos = rs.prefill_ctx = ctx
        self.trace.record(rs.rid, "admit", slot=slot,
                          cached_prefix_tokens=0, suffix_tokens=ctx,
                          blocks_reserved=0)
        if self._tel is not None:
            self._tel.requests_admitted.inc()
            self._tel.prefill_computed.inc(ctx)
        self._activate(slot, rs)
        return True

    def _admit_paged(self, slot: int, rs: RequestState, ctx: int) -> bool:
        """Reserve blocks, pin the longest cached prefix, and arm the
        chunk-grid suffix prefill. The decode-visible table row stays NULL
        until activation, so ghost decode writes keep landing in trash while
        the slot is still prefilling."""
        total = self._blocks_needed(rs)
        if (self.faults is not None
                and self._fault("alloc_exhausted", rid=rs.rid)):
            # injected pool exhaustion: containment is a structured
            # retirement ("resource_exhausted"), not the requeue-retry loop
            # a transient same-tick over-commit gets — nothing was reserved
            # yet, so undoing the admission marks releases everything
            self.scheduler.revert_admission(rs)
            self._retire_unslotted(rs, "resource_exhausted",
                                   time.perf_counter(), self.stats["ticks"])
            return True
        match, cached, nodes, cached_tokens, cow_src = self._match_prefix(rs)
        if cached:
            # pin + hold before any eviction runs: the matched chain must
            # survive the allocation below even under pool pressure
            self.radix.pin(nodes)
            self.allocator.incref(cached)
        need_new = total - len(cached)
        if self.radix is not None and not self.allocator.can_alloc(need_new):
            self.radix.evict(need_new)
        blocks = self.allocator.alloc(need_new)
        if blocks is None:
            # over-committed within a multi-admission tick: every pick's
            # headroom was evaluated against the same free/evictable set
            # before any admission landed. Undo the holds; step() requeues
            # the failures in arrival order and they retry next tick.
            if cached:
                self.allocator.free(cached)
                self.radix.unpin(nodes)
            return False
        if match is not None:
            # the admission is committed: now the hit/miss counts and the
            # LRU clock may move (requeued retries never get here twice)
            self.radix.commit(match)
        rs.blocks = blocks
        rs.cached_blocks = list(cached)
        rs.radix_nodes = nodes
        row = np.zeros(self._chunk_cols, np.int32)
        row[:len(cached)] = cached
        row[len(cached):total] = blocks
        rs.table_row = row
        if cow_src is not None:
            # partial-block divergence: decode writes position ctx into the
            # block holding the matched partial prefix — copy it into the
            # slot's first private block so the shared copy stays pristine
            self.caches = self._copy(self.caches, np.int32(cow_src),
                                     np.int32(row[len(cached)]))
        rs.slot = slot
        self.slot_req[slot] = rs
        rs.prefill_pos = cached_tokens
        rs.prefill_ctx = ctx
        # full coverage (block-aligned or COW) needs no chunks and may sit
        # off the grid; partial reuse is always rounded onto it
        rs.pending_chunks = ([] if cached_tokens >= ctx else
                             list(kvc.chunk_starts(cached_tokens, ctx,
                                                   self.prefill_chunk)))
        rs.match_memo = None
        rs.cached_prefix_tokens = cached_tokens
        self.stats["cached_prefix_tokens"] += cached_tokens
        self.trace.record(rs.rid, "admit", slot=slot,
                          cached_prefix_tokens=cached_tokens,
                          suffix_tokens=ctx - cached_tokens,
                          blocks_reserved=total)
        if self._tel is not None:
            self._tel.requests_admitted.inc()
            if cached_tokens:
                self._tel.prefill_cached.inc(cached_tokens)
        # incremental-publish cursor: suffix chunks extend the trie from the
        # end of the matched chain instead of re-walking from the root
        rs.published_blocks = len(cached)
        rs.radix_tail = nodes[-1] if nodes else None
        if not rs.pending_chunks:
            self._activate(slot, rs)
        else:
            self._prefilling.append(slot)
        return True

    def _activate(self, slot: int, rs: RequestState) -> None:
        """Prefill complete: make the slot decode-visible (install its block
        table row, arm the device slot state) and publish its full-block
        prompt prefix to the radix cache for future admissions."""
        ctx = rs.prefill_ctx
        if self.paged:
            self.block_table[slot] = rs.table_row
            # suffix-chunk blocks were published per chunk as they were
            # enqueued; fully-cached admissions have nothing new to insert
        rs.slot = slot
        self.slot_req[slot] = rs
        self._host_len[slot] = ctx
        self._samp[slot] = rs.sampling
        # packed sampler state is rebuilt here (activations) only — never in
        # the per-tick hot loop
        self._sp_packed = samp_lib.pack(self._samp)
        st = self._state
        self._state = _SlotState(
            last_tok=st.last_tok.at[slot, 0].set(int(rs.prompt[-1])),
            lengths=st.lengths.at[slot].set(ctx),
            remaining=st.remaining.at[slot].set(int(rs.max_new_tokens)),
            active=st.active.at[slot].set(True),
            sample_seed=st.sample_seed.at[slot].set(
                int(rs.rid) & 0x7FFFFFFF),
            # draws already made for this request: 0 on a fresh admission,
            # len(out_tokens) when resuming after preemption — the sampled
            # stream continues with exactly the keys it would have used
            sample_step=st.sample_step.at[slot].set(len(rs.out_tokens)),
        )
        self.trace.record(rs.rid, "activate", slot=slot, context_tokens=ctx)

    def _run_chunk(self, rs: RequestState) -> None:
        if (self.faults is not None
                and self._fault("chunk_error", rid=rs.rid)):
            # raised before any state moves, so containment in
            # _run_prefill_chunks sees a consistent request
            raise faults_lib.InjectedFault("chunk_error", rs.rid,
                                           self.stats["ticks"])
        p0 = rs.pending_chunks.pop(0)
        C = self.prefill_chunk
        W = kvc.chunk_table_width(p0, C, self.ecfg.page_size,
                                  self.chunk_buckets)
        toks = np.zeros((1, C), np.int32)
        n = min(rs.prefill_ctx - p0, C)
        toks[0, :n] = rs.prompt[p0:p0 + n]
        self.caches = self._chunk(self.params, toks, self.caches,
                                  rs.table_row[None, :W],
                                  np.array([p0], np.int32),
                                  np.array([rs.prefill_ctx], np.int32))
        rs.prefill_pos = p0 + C
        rs.computed_prefill_tokens += n
        self.stats["prefill_tokens"] += n
        self.trace.record(rs.rid, "prefill_chunk", p0=p0, tokens=n,
                          kind="computed")
        if self._tel is not None:
            self._tel.prefill_computed.inc(n)
        if self.radix is not None:
            # publish the newly completed full blocks immediately (not at
            # activation): a same-prefix request admitted one tick later can
            # already pin them — enqueue order makes the values visible to
            # any later reader via device data dependencies. The cursor
            # resumes from the last published node, so a long prompt walks
            # each trie level once, not once per chunk.
            bs = self.ecfg.page_size
            nfull = min(rs.prefill_pos, rs.prefill_ctx) // bs
            prev = rs.published_blocks
            if nfull > prev:
                tail, walked = self.radix.insert(
                    rs.prompt[prev * bs:nfull * bs],
                    list(rs.table_row[prev:nfull]), node=rs.radix_tail)
                # pin the extended chain: the resume cursor must survive
                # eviction until retirement unpins it
                self.radix.pin(walked)
                rs.radix_nodes.extend(walked)
                rs.radix_tail = tail
                rs.published_blocks = nfull

    def _run_prefill_chunks(self) -> int:
        """Advance mid-prefill slots on the absolute chunk grid, spending at
        most the scheduler's per-tick prefill token budget — the pacing that
        keeps one long prompt from stalling every live decode.

        Grants rotate round-robin across prefilling slots (one chunk per
        slot per pass, starting offset advancing each tick), so a 13-chunk
        prompt cannot head-of-line-block a 1-chunk prompt admitted behind
        it. Chunk order across slots is value-invisible: slots write
        disjoint blocks and shared cached blocks are read-only, so fairness
        here is pure scheduling — token streams stay bit-identical.
        Returns the number of chunks run."""
        if not self._prefilling:
            return 0
        budget = self.scheduler.prefill_token_budget
        C = self.prefill_chunk
        start = self._prefill_rr % len(self._prefilling)
        self._prefill_rr += 1
        order = self._prefilling[start:] + self._prefilling[:start]
        ran = 0
        progressed = True
        while budget >= C and progressed:
            progressed = False
            for slot in order:
                if budget < C:
                    break
                rs = self.slot_req[slot]
                if rs is None or not rs.pending_chunks:
                    # None: retired mid-pass by chunk containment below
                    continue
                try:
                    self._run_chunk(rs)
                except Exception as e:
                    # chunk-level fault containment: one failed chunk costs
                    # one request ("internal_error"), never the engine —
                    # _retire frees its blocks and unpins its published
                    # chain; co-prefilling slots keep their grants. The
                    # exception (a compile error, say) is kept, not dropped
                    self._retire(slot, rs, "internal_error",
                                 time.perf_counter(), self.stats["ticks"],
                                 error=self.record_error(e, rs.rid))
                    continue
                budget -= C
                ran += 1
                progressed = True
        still: List[int] = []
        for slot in self._prefilling:
            rs = self.slot_req[slot]
            if rs is None:
                continue        # retired by chunk containment this tick
            if not rs.pending_chunks:
                self._activate(slot, rs)
            else:
                still.append(slot)
        self._prefilling = still
        return ran

    def _retire(self, slot: int, rs: RequestState, reason: str,
                now: float, tick: int, error: Optional[str] = None) -> None:
        self.scheduler.retire(rs, tick, now, reason)
        decode_s = (now - rs.first_token_time
                    if rs.first_token_time is not None else 0.0)
        self.trace.record(rs.rid, "finish", reason=reason,
                          tokens=len(rs.out_tokens), decode_s=decode_s,
                          tpot_s=rs.tpot or 0.0,
                          **({"error": error} if error else {}))
        self.slot_req[slot] = None
        self._host_len[slot] = 0
        if self.paged:
            # leak-injection sites: model a retire path that forgets its
            # cleanup. The bookkeeping lists are cleared either way (the
            # leak is invisible to per-slot accounting — that is the point);
            # the leaked refcounts/pins are what audit() must find and
            # reclaim via the ownership cross-check.
            leak_blocks = (self.faults is not None
                           and self._fault("block_leak", rid=rs.rid))
            leak_pins = (self.faults is not None
                         and self._fault("radix_pin_leak", rid=rs.rid))
            if not leak_blocks:
                self.allocator.free(rs.blocks)
            rs.blocks = []
            if rs.cached_blocks:
                # drop the slot's hold on shared prefix blocks (the cache's
                # own reference keeps them warm) and unpin the chain
                if not leak_pins:
                    self.allocator.free(rs.cached_blocks)
                rs.cached_blocks = []
            if rs.radix_nodes:
                if not leak_pins:
                    self.radix.unpin(rs.radix_nodes)
                rs.radix_nodes = []
            self.block_table[slot] = kvc.NULL_BLOCK
        if self.journal is not None:
            self.journal.record_retire(rs.rid, reason)
        self._finished_unpolled.append(rs)
        if self.retire_sink is not None:
            self.retire_sink(rs.rid, reason)

    # --- preemption -------------------------------------------------------

    def _preempt_slot(self, slot: int) -> None:
        """Evict one activated decode slot under KV-pool pressure: free its
        blocks, unpin its radix chain, and requeue the request at the front
        of the waiting queue (scheduler.preempt) for bit-exact recompute.

        Resume is exact by construction: the generated-so-far tokens fold
        into the prompt, so re-admission recomputes the full context through
        the absolute-grid chunked prefill (the same compiled programs on the
        same inputs as if the context had been prefilled fresh — the
        cache-on/off invariant), and _activate re-arms sample_step at
        len(out_tokens) so a sampled stream continues with exactly the keys
        it would have drawn uninterrupted. Prefill-computed full blocks the
        slot already published stay in the radix cache (unpinned ->
        evictable headroom now, cheap re-match at resume); decode-written
        blocks are dropped and recomputed — publishing them would hand
        decode-written K/V to the prefill path and break its bit-exactness
        contract.

        The device slot state is left untouched ("ghost-active", the same
        mechanism as undrained finishes): the NULLed table row sends its
        decode writes to the trash block, the remaining countdown bounds the
        ghost ticks, and _activate fully re-arms the state on reuse — so
        preemption adds no device ops and no new jit traces."""
        rs = self.slot_req[slot]
        freed = len(rs.blocks) + len(rs.cached_blocks)
        self.trace.record(rs.rid, "preempt", slot=slot,
                          tokens_generated=len(rs.out_tokens),
                          blocks_freed=freed)
        self._release_slot_resources(slot, rs)
        new = rs.out_tokens[rs.folded_tokens:]
        if new:
            # tokens generated since the last fold become context; the
            # drained done flag guarantees budget remains (a spent budget
            # retires at drain, and preemption only runs against a drained
            # pending buffer)
            assert len(new) < rs.max_new_tokens
            rs.prompt = np.concatenate(
                [rs.prompt, np.asarray(new, np.int32)])
            rs.max_new_tokens -= len(new)
            rs.folded_tokens = len(rs.out_tokens)
        self.scheduler.preempt(rs, self.stats["ticks"])

    def _release_slot_resources(self, slot: int, rs: RequestState) -> None:
        """Release a slotted request's pool holds (blocks, cached prefix
        references, radix pins) and make its device slot ghost-active —
        NULLed table row sends decode writes to trash, the remaining
        countdown bounds the ghost ticks, and _activate fully re-arms the
        state on reuse. Shared by preemption and handoff extraction; adds
        no device ops and no jit traces. The request's delivered tokens,
        sampling state, and fold bookkeeping are untouched."""
        self.slot_req[slot] = None
        self._host_len[slot] = 0
        if self.paged:
            self.allocator.free(rs.blocks)
            rs.blocks = []
            if rs.cached_blocks:
                self.allocator.free(rs.cached_blocks)
                rs.cached_blocks = []
            if rs.radix_nodes:
                self.radix.unpin(rs.radix_nodes)
                rs.radix_nodes = []
            self.block_table[slot] = kvc.NULL_BLOCK
        rs.slot = -1
        rs.table_row = None
        rs.prefill_pos = rs.prefill_ctx = 0
        rs.pending_chunks = []
        rs.match_memo = None
        rs.cached_prefix_tokens = 0
        rs.published_blocks = 0
        rs.radix_tail = None

    def _maybe_preempt(self) -> int:
        """Admit-or-preempt: when the blocked queue head has waited
        `preempt_after_ticks` (since submit, or since its own last
        preemption), evict last-admitted decode slots — LIFO, least progress
        lost — until the head's reservation fits. Returns slots preempted.

        Must run against a drained pending buffer (out_tokens current, no
        in-flight ticks to discard). Victims are restricted to requests
        that *arrived after* the head — preemption is the enforcement arm
        of arrival-order fairness (it reclaims capacity the lookahead
        handed to opportunistic later arrivals), and because "may preempt"
        is then a strict order, preemption cycles (two requests evicting
        each other forever) cannot exist. The head is held out of the queue
        while victims requeue so it stays in front of them: the freed
        blocks must admit *it*, not hand the pool straight back to a
        requeued victim. Mid-prefill slots are never victims (their
        computed blocks are shared-publishable work in flight); a what-if
        gate skips the whole storm when even preempting every victim could
        not admit the head (e.g. surviving pins keep the pool occupied) —
        then the head waits for natural retirements exactly as without
        preemption."""
        sched = self.scheduler
        head = sched.waiting[0]
        if self._can_admit(head):
            return 0
        if head.wait_age(self.stats["ticks"]) < self.ecfg.preempt_after_ticks:
            return 0
        victims = sorted(
            (s for s, r in enumerate(self.slot_req)
             if r is not None and s not in self._prefilling
             and r.arrival_seq > head.arrival_seq),
            key=lambda s: (self.slot_req[s].admit_tick, s))
        if not victims:
            return 0
        # what-if headroom across all victims: directly freed private
        # blocks (no cache reference) + cache blocks that become evictable
        # once every victim chain is unpinned, minus the head's own matched
        # chain (about to be pinned — never both reused and evictable)
        _, matched, _, _, _ = self._match_prefix(head)
        chains: List[Any] = []
        direct = 0
        for s in victims:
            rs = self.slot_req[s]
            chains.extend(rs.radix_nodes)
            published_own = max(0, rs.published_blocks
                                - len(rs.cached_blocks))
            direct += len(rs.blocks) - published_own
        headroom = 0
        if self.radix is not None:
            headroom = max(0, self.radix.evictable_after_unpin(chains)
                           - len(matched))
        if (self._blocks_needed(head) - len(matched)
                > self.allocator.free_blocks + direct + headroom):
            return 0
        sched.waiting.popleft()
        n = 0
        while victims and not self._can_admit(head):
            self._preempt_slot(victims.pop())
            n += 1
        sched.waiting.appendleft(head)
        return n

    # --- cancellation -----------------------------------------------------

    def cancel(self, rid: int) -> bool:
        """Cancel a live request, releasing its resources wherever it is in
        the lifecycle — waiting in the queue, mid-chunked-prefill, or
        mid-decode. Returns True if it was cancelled; False if it is unknown
        or already finished (in-flight ticks are drained first, so a request
        whose stream just completed keeps its tokens — cancellation never
        rolls back delivered output). The cancelled request is retired with
        reason "cancelled" and is still returned by poll() with whatever
        tokens it produced."""
        req = self._requests.get(rid)
        if req is None:
            return False
        self._drain()
        now = time.perf_counter()
        tick = self.stats["ticks"]
        for rs in self.scheduler.waiting:
            if rs.rid == rid:
                # never admitted: no slot, no blocks — just close the span
                self.scheduler.waiting.remove(rs)
                self._retire_unslotted(rs, "cancelled", now, tick)
                return True
        for slot, rs in enumerate(self.slot_req):
            if rs is not None and rs.rid == rid:
                if slot in self._prefilling:
                    # mid-prefill: the slot was never decode-visible (table
                    # row still NULL); _retire frees blocks + unpins the
                    # published chain
                    self._prefilling.remove(slot)
                # mid-decode: the device slot goes ghost-active exactly like
                # preemption — trash writes, bounded by the remaining
                # countdown, fully re-armed by the next _activate
                self._retire(slot, rs, "cancelled", now, tick)
                return True
        return False    # finished since the caller last polled

    # --- durability: snapshot / restore / recovery / handoff --------------

    def begin_draining(self, reason: str = "drain") -> None:
        """Stop admitting new work: slotted requests run to completion,
        waiting requests stay queued (preserved for a final snapshot).
        DRAINING is terminal — used by the launcher's signal handlers and
        as the handoff source's end state; close() still performs the
        actual shutdown."""
        self._set_health(DRAINING, reason)

    def _live_records(self) -> List[dict]:
        """Every live request (waiting or slotted, including mid-prefill)
        as a durable record (RequestState.to_record: original submission +
        delivered stream, folds undone), in arrival order — the one
        extraction snapshot(), recover() cross-checks, and handoff() all
        build on."""
        recs = [rs.to_record() for rs in self.scheduler.waiting]
        recs += [rs.to_record() for rs in self.slot_req if rs is not None]
        recs.sort(key=lambda r: r["arrival_seq"])
        return recs

    def _validate_readmit(self, records: List[dict]) -> None:
        """Check every record could be admitted on THIS engine without
        touching any state: no collision with a live rid (or a duplicate
        within the batch), and prompt + original budget fits max_seq.
        handoff() runs this on the target BEFORE the source releases
        anything, so a doomed handoff fails atomically with the source
        intact; _readmit shares it so the error surfaces before any record
        of the batch has been journaled or queued."""
        seen = set()
        for rec in records:
            rid = int(rec["rid"])
            if rid in self._requests or rid in seen:
                raise ValueError(f"readmit of live rid {rid}")
            seen.add(rid)
            plen = len(rec["prompt"])
            budget = int(rec["max_new_tokens"])
            if plen + budget > self.ecfg.max_seq:
                raise ValueError(
                    f"rid {rid}: prompt ({plen}) + max_new_tokens "
                    f"({budget}) exceeds this engine's max_seq "
                    f"({self.ecfg.max_seq})")

    def _readmit(self, records: List[dict],
                 journal_known_rids=frozenset()) -> int:
        """Re-admit durable request records through normal admission: each
        becomes a fresh waiting RequestState with its delivered tokens
        folded into the prompt (the preemption resume mechanism), so
        chunked prefill recomputes the full context bit-exactly and
        _activate re-arms sample_step at len(out_tokens) — greedy and
        sampled streams continue exactly where they stopped.

        Journaling: records whose rid is not in `journal_known_rids` are
        written to the attached journal (submit + every delivered token)
        so a fresh journal is a self-contained ledger; rids already live
        in the journal (recovery replays the same file, handoff moves it)
        are not re-journaled — a second submit for a live rid is, by
        design, replay corruption.

        A record whose budget is spent or whose last delivered token is
        EOS had its retire record torn off the journal tail by the crash:
        it is retired immediately (repairing the journal) instead of being
        queued. Deadlines carry over as the RESIDUAL budget (the record's
        deadline_elapsed_ms is subtracted; an already-expired request
        retires with reason "deadline") — a request nearly out of deadline
        at the crash or handoff never gets its clock restarted. Returns
        the number of records processed."""
        self._validate_readmit(records)
        now = time.perf_counter()
        tick = self.stats["ticks"]
        n = 0
        for rec in sorted(records, key=lambda r: r.get("arrival_seq", 0)):
            rid = int(rec["rid"])
            prompt = np.asarray(rec["prompt"], np.int32)
            budget = int(rec["max_new_tokens"])
            delivered = [int(t) for t in rec.get("delivered") or ()]
            sd = rec.get("sampling") or {}
            sp = SamplingParams(
                temperature=float(sd.get("temperature", 0.0)),
                top_k=int(sd.get("top_k", 0)),
                top_p=float(sd.get("top_p", 1.0)))
            deadline_ms = rec.get("deadline_ms")
            elapsed_ms = rec.get("deadline_elapsed_ms")
            if deadline_ms is not None and elapsed_ms:
                # residual deadline: time already consumed before the
                # snapshot/handoff/crash (downtime included) stays charged
                deadline_ms = float(deadline_ms) - float(elapsed_ms)
            if (self.journal is not None
                    and rid not in journal_known_rids):
                self.journal.record_submit(rid, prompt, budget,
                                           sampling=dict(sd) or None,
                                           deadline_ms=deadline_ms)
                for tok in delivered:
                    self.journal.record_token(rid, tok)
            rs = RequestState(rid=rid, prompt=prompt,
                              max_new_tokens=budget, sampling=sp,
                              deadline_ms=deadline_ms)
            rs.out_tokens.extend(delivered)
            remaining = budget - len(delivered)
            if delivered:
                # the fold: delivered tokens become context to recompute
                rs.prompt = np.concatenate(
                    [prompt, np.asarray(delivered, np.int32)])
                rs.max_new_tokens = remaining
                rs.folded_tokens = len(delivered)
            req = Request(rid=rid, prompt=prompt, max_new_tokens=budget,
                          sampling=sp, deadline_ms=deadline_ms)
            req.out_tokens = rs.out_tokens          # live alias
            if deadline_ms is not None:
                self._has_deadlines = True
            self._requests[rid] = req
            self.scheduler.submit(rs, tick, now)
            self.trace.record(rid, "submit", prompt_len=len(prompt),
                              max_new_tokens=budget)
            self.trace.record(rid, "restore",
                              delivered_tokens=len(delivered))
            if self._tel is not None:
                self._tel.restored_requests.inc()
            n += 1
            if remaining <= 0 or (delivered
                                  and delivered[-1] == self.ecfg.eos_id):
                # its retirement was lost with the journal tail — finish it
                reason = ("eos" if delivered
                          and delivered[-1] == self.ecfg.eos_id
                          else "max_tokens")
                self.scheduler.waiting.remove(rs)
                self._retire_unslotted(rs, reason, now, tick)
                continue
            if deadline_ms is not None and deadline_ms <= 0:
                # the residual ran out while the request was down or in
                # transit — same reason _enforce_deadlines would assign at
                # the next tick, without a pointless prefill first
                self.scheduler.waiting.remove(rs)
                self._retire_unslotted(rs, "deadline", now, tick)
                continue
            self.trace.record(rid, "queued",
                              queue_depth=len(self.scheduler.waiting))
        return n

    def snapshot(self, ckpt_dir, step: Optional[int] = None,
                 keep: int = 3):
        """Write a durable engine snapshot through the ckpt manifest format
        (staged dir + MANIFEST.json-last atomic commit): the EngineConfig,
        every live request record (scheduler queue and slot states — prompt,
        delivered/folded tokens, sampling), and the radix-cache pin summary.
        KV pools are deliberately NOT persisted: restore re-admits every
        request through absolute-grid chunked prefill, which recomputes
        pool contents bit-exactly — persisting them would add gigabytes per
        snapshot to save work recovery already does for free, exactly.
        Returns the committed checkpoint path; `step` defaults to the
        engine tick."""
        self._drain()
        if self.journal is not None:
            self.journal.sync()
        records = self._live_records()
        ecfg_dict, skipped = _ecfg_to_dict(self.ecfg)
        payload = {
            "format": 1,
            "tick": self.stats["ticks"],
            "engine_config": ecfg_dict,
            "non_serializable": skipped,
            "requests": records,
            "radix": (self.radix.pin_summary()
                      if self.radix is not None else None),
        }
        blob = np.frombuffer(json.dumps(payload).encode(), np.uint8)
        from repro.ckpt import checkpoint as ckpt
        path = ckpt.save(ckpt_dir,
                         self.stats["ticks"] if step is None else int(step),
                         {"snapshot": blob}, keep=keep,
                         extra={"kind": "serve_snapshot",
                                "tick": self.stats["ticks"],
                                "live_requests": len(records)})
        if self._tel is not None:
            self._tel.snapshots.inc()
        return path

    @staticmethod
    def _load_snapshot(ckpt_dir, step: Optional[int]) -> dict:
        from repro.ckpt import checkpoint as ckpt
        if step is None:
            step = ckpt.latest_step(ckpt_dir)
            if step is None:
                raise ValueError(f"no committed snapshot under {ckpt_dir}")
        blob = ckpt.load_flat(ckpt_dir, int(step))["snapshot"]
        return json.loads(blob.tobytes().decode())

    @classmethod
    def restore(cls, cfg: ModelConfig, params, ckpt_dir, *,
                step: Optional[int] = None, dtype=jnp.float32, mesh=None,
                overrides: Optional[Dict[str, Any]] = None,
                journal=None) -> "ServeEngine":
        """Build a fresh engine from a snapshot() checkpoint and re-admit
        every captured request. `overrides` patches EngineConfig fields
        (including the non-serializable ones the snapshot could not carry);
        `journal` attaches a write-ahead journal to the restored engine.
        ecfg.seed must survive the round trip unchanged for sampled streams
        to resume bit-exactly — it does, as a plain serialized field."""
        payload = cls._load_snapshot(ckpt_dir, step)
        ecfg = _ecfg_from_dict(payload["engine_config"], overrides)
        if journal is not None:
            ecfg = dataclasses.replace(ecfg, journal=journal)
        eng = cls(cfg, params, ecfg, dtype=dtype, mesh=mesh)
        known = frozenset()
        if journal is not None:
            # resuming onto an existing journal: rids already live in it
            # must not be re-journaled (and a fresh journal knows none)
            from repro.serve import journal as journal_lib
            known = frozenset(journal_lib.replay(journal.path).live.keys())
        eng._readmit(payload["requests"], journal_known_rids=known)
        return eng

    @classmethod
    def recover(cls, cfg: ModelConfig, params, journal_path, *,
                ecfg: Optional[EngineConfig] = None, snapshot_dir=None,
                snapshot_step: Optional[int] = None, dtype=jnp.float32,
                mesh=None, overrides: Optional[Dict[str, Any]] = None,
                fsync_every: int = 16) -> "ServeEngine":
        """Crash recovery: replay the journal, build a fresh engine, and
        resume every request that was live at the kill — each stream
        continues with exactly its undelivered suffix (bit-identical to an
        uninterrupted run, greedy and sampled), never a duplicate or
        dropped token, because only drain-delivered tokens were journaled
        and the fold recomputes everything else.

        The engine config comes from `ecfg` or from a snapshot under
        `snapshot_dir` (the launcher writes one on clean shutdown; either
        source must preserve the original seed). The same journal file is
        reopened for appending — recovery adds a new epoch header, so one
        file spans every crash/recover cycle and replay stays idempotent.
        The recovered engine owns the journal writer (close() closes it)."""
        from repro.serve import journal as journal_lib
        state = journal_lib.replay(journal_path)
        if ecfg is None:
            if snapshot_dir is None:
                raise ValueError("recover() needs ecfg or snapshot_dir "
                                 "for the engine config")
            payload = cls._load_snapshot(snapshot_dir, snapshot_step)
            ecfg = _ecfg_from_dict(payload["engine_config"], overrides)
        jr = journal_lib.RequestJournal(journal_path,
                                        fsync_every=fsync_every)
        eng = cls(cfg, params, dataclasses.replace(ecfg, journal=jr),
                  dtype=dtype, mesh=mesh)
        eng._owns_journal = True
        now_wall = time.time()
        records = [{"rid": lr.rid, "prompt": lr.prompt,
                    "max_new_tokens": lr.max_new_tokens,
                    "sampling": lr.sampling, "deadline_ms": lr.deadline_ms,
                    # wall-clock elapsed since the journaled submit: the
                    # deadline keeps ticking through the outage, so readmit
                    # resumes with the residual budget, never a fresh one
                    "deadline_elapsed_ms": (
                        max(0.0, (now_wall - lr.submit_wall_time_s) * 1e3)
                        if (lr.deadline_ms is not None
                            and lr.submit_wall_time_s is not None)
                        else None),
                    "delivered": lr.delivered, "arrival_seq": i}
                   for i, lr in enumerate(state.live.values())]
        eng._readmit(records,
                     journal_known_rids=frozenset(state.live.keys()))
        return eng

    def handoff(self, target: "ServeEngine") -> Dict[str, Any]:
        """Live handoff: drain pending ticks, extract every live request,
        release this engine's pool holds, and re-admit them on `target` —
        which may run a different config (kv_bits, mesh, slot count, pool
        size). Zero-downtime reconfiguration: streams continue under the
        same rids (the async front door rebinds its sinks), bit-exactly by
        the preemption-fold construction — which is why eos_id and seed
        must match (the engine seed is folded into every per-request
        sampling key). Atomic on failure: every record is validated
        against the target (max_seq fit, live-rid collisions) before the
        source releases anything, so a refused handoff raises with the
        source untouched and still serving.

        This engine passes through the HANDOFF health state (exported on
        the gauge and /healthz, which turns 503) and ends DRAINING
        (terminal). If this engine holds the journal and `target` has
        none, the journal moves with the requests and a handoff epoch is
        appended — one ledger spans both engines' lifetimes."""
        if target is self:
            raise ValueError("handoff target must be a different engine")
        if target._health == DRAINING:
            raise ValueError("handoff target is draining/closed")
        if int(target.ecfg.eos_id) != int(self.ecfg.eos_id):
            raise ValueError("handoff target must keep eos_id")
        if int(target.ecfg.seed) != int(self.ecfg.seed):
            raise ValueError("handoff target must keep seed: sampled "
                             "resume folds it into every per-request key")
        self._drain()
        records = self._live_records()
        # every record must be admissible on the target (max_seq fit, no
        # live-rid collision) BEFORE this engine releases anything — a
        # doomed handoff must fail here, atomically, with the source still
        # RUNNING and every request intact, not mid-release with requests
        # split across two engines
        target._validate_readmit(records)
        self._set_health(HANDOFF, "handoff")
        for slot, rs in enumerate(self.slot_req):
            if rs is None:
                continue
            if slot in self._prefilling:
                self._prefilling.remove(slot)
            self._release_slot_resources(slot, rs)
        self.scheduler.waiting.clear()
        for rec in records:
            # closes the span on this recorder (the request is no longer
            # ours); the target opens a fresh one on readmission
            self.trace.record(rec["rid"], "handoff",
                              tokens_generated=len(rec["delivered"]))
            self._requests.pop(rec["rid"], None)
        known = frozenset()
        if self.journal is not None and target.journal is None:
            target.journal = self.journal
            target._owns_journal = self._owns_journal
            self.journal = None
            self._owns_journal = False
            target.journal.begin_epoch({"reason": "handoff"})
            known = frozenset(rec["rid"] for rec in records)
        target._readmit(records, journal_known_rids=known)
        if self._tel is not None:
            self._tel.handoffs.inc()
        self._set_health(DRAINING, "handoff_complete")
        self._publish_gauges()
        return {"transferred": len(records),
                "source_tick": self.stats["ticks"],
                "target_tick": target.stats["ticks"]}

    # --- decode tick ------------------------------------------------------

    def _decode_bucket(self, active: List[int]) -> int:
        """Smallest decode block bucket covering every live context (+1 for
        the token being written this tick). `_host_len` is a conservative
        shadow — it keeps counting for device-finished-but-undrained slots,
        which can only round the bucket up, never under-cover."""
        need = max(kvc.blocks_for(int(self._host_len[s]) + 1,
                                  self.ecfg.page_size) for s in active)
        return kvc.bucket_for(min(need, self.blocks_per_slot),
                              self.decode_buckets)

    def step(self) -> int:
        """Admissions + one enqueued decode tick; returns the number of live
        slots advanced. Sampled tokens and termination flags stay on device
        until the next drain (poll(), admission pressure, or the pending
        cap) — the hot loop never blocks on a host sync per token.

        Fault containment: per-request deadlines are enforced first (tick
        boundaries are the deadline grid), and an InjectedFault escaping
        the tick body is contained here — its target request retires with
        reason "internal_error" (an untargeted fault degrades the engine
        instead), so a step-level failure costs one request, never the
        process. Real exceptions still propagate: the front door's tick
        loop is the containment layer for those (it degrades the engine
        and keeps draining in-flight streams)."""
        if (self.faults is not None
                and self._fault("process_crash") is not None):
            # simulated hard process death at a tick boundary: escapes
            # every containment layer by design (recovery is journal
            # replay in a fresh engine — ServeEngine.recover — not an
            # except path in the dying one)
            raise faults_lib.ProcessCrash(self.stats["ticks"])
        if self._has_deadlines:
            self._enforce_deadlines()
        try:
            with self.span("step"):
                n = self._step_impl()
        except faults_lib.InjectedFault as e:
            if e.rid is not None and self._retire_anywhere(
                    e.rid, "internal_error"):
                # containment IS schedule progress (a retirement happened):
                # returning 0 here would make run()'s dead-queue bail
                # misread one contained tick as a permanently stuck head
                return 1
            self.mark_degraded(f"injected:{e.site}")
            return 1
        if (self._audit_interval is not None
                and self.stats["ticks"] - self._last_audit_tick
                >= self._audit_interval):
            self._last_audit_tick = self.stats["ticks"]
            self.audit()
        return n

    def _step_impl(self) -> int:
        # the tick phases are spans around host code the tick already runs:
        # no block_until_ready, no extra device round trips. The device-step
        # wait itself is spanned in _drain, at the host sync that already
        # exists there.
        if self.faults is not None:
            spec = self._fault("step_error")
            if spec is not None:
                # fired before any state moves this tick, so the containment
                # in step() operates on a consistent engine
                raise faults_lib.InjectedFault("step_error", spec.rid,
                                               self.stats["ticks"])
        admit = bool(self.scheduler.waiting) and self._health != DRAINING
        if admit:
            # admission decisions need an up-to-date view of free slots.
            # (A DRAINING engine stops admitting: queued requests wait —
            # preserved for the final snapshot — while slotted ones finish.)
            self._drain()
        with self.span("schedule"):
            free = self.slot_req.count(None) if admit else 0
            if free and self.paged and self.ecfg.preemption:
                # head blocked on blocks (not slots): evict last-admitted
                # decode slots so it admits instead of stalling the queue
                if self._maybe_preempt():
                    free = self.slot_req.count(None)
            if free:
                not_admitted = [
                    rs for rs in self.scheduler.pick(
                        free, self.stats["ticks"], self._can_admit)
                    if not self._admit(rs)]
                # requeue failures back-to-front so appendleft restores
                # arrival order at the queue head
                for rs in reversed(not_admitted):
                    self.scheduler.requeue_front(rs)

            if self.paged:
                # chunked prefill interleaves with decode under the budget;
                # slots still mid-prefill are excluded from the decode batch
                self._run_prefill_chunks()

            active = [s for s, r in enumerate(self.slot_req)
                      if r is not None and not r.pending_chunks]
        if not active:
            return 0

        # dispatch = host cost of enqueueing the async decode jit; the
        # device's own execution time surfaces as _drain's sync
        with self.span("dispatch"):
            bt = (self.block_table[:, :self._decode_bucket(active)]
                  if self.paged else None)
            key = self._key    # per-slot keys are derived in the decode jit
            self.caches, self._state, nxt, done, ok = self._decode(
                self.params, self.caches, self._state, bt, self._sp_packed,
                key)
            self._pending.append(_TickRecord(
                self.stats["ticks"], tuple(active), nxt, done, ok))
            self._host_len[active] += 1
            self.stats["ticks"] += 1
            # the host's active set holds the device's, so this counts no
            # more ticks than took the sampler's argmax path
            greedy = bool(self._sp_packed.greedy[active].all())
            self.stats["greedy_ticks"] += greedy
        if self._tel is not None:
            self._tel.ticks.inc()
            if greedy:
                self._tel.greedy_ticks.inc()
        if len(self._pending) >= self.ecfg.max_pending_ticks:
            self._drain()
        return len(active)

    def drain(self, keep: int = 0) -> None:
        """Deliver pending decode ticks to host, leaving the newest `keep`
        enqueued. `keep=1` is the overlap knob the async front door uses:
        after step() enqueues tick N+1, drain(keep=1) syncs only ticks
        <= N — work the device has already finished (it is executing N+1) —
        so token delivery proceeds while the device computes, instead of
        blocking on the tick that was just dispatched."""
        self._drain(keep)

    def _drain(self, keep: int = 0) -> None:
        """Deliver every pending decode tick (all but the newest `keep`):
        one host sync per drained batch instead of one per token. Ticks are
        replayed in order so retirement and slot recycling land exactly
        where the per-tick loop would have put them (a slot freed at tick t
        is admissible at tick t+1 for any caller that polls between steps)."""
        if len(self._pending) <= keep:
            return
        with self.span("drain"):
            self._deliver(keep)

    def _deliver(self, keep: int) -> None:
        if keep:
            pending = self._pending[:-keep]
            self._pending = self._pending[-keep:]
        else:
            pending, self._pending = self._pending, []
        delivered = 0
        for rec in pending:
            # the np.asarray host syncs are the one place the engine already
            # waits on the device: the device-step phase adds no sync
            with self.span("device_step"):
                s0 = time.perf_counter()
                if self.faults is not None:
                    spec = self._fault("slow_step", tick=rec.tick)
                    if spec is not None:
                        # a slow/hung device step: the stall lands inside
                        # the sync bracket, exactly where a real one would,
                        # so the watchdog observes it the same way
                        time.sleep(spec.delay_s)
                toks = np.asarray(rec.tokens)
                done = np.asarray(rec.done)
                oks = np.asarray(rec.ok)
                now = time.perf_counter()
            self._watchdog(now - s0)
            for slot in rec.slots:
                rs = self.slot_req[slot]
                if rs is None:
                    # ghost tick: the slot finished at an earlier (buffered)
                    # tick; its masked decode output is dropped
                    continue
                if (not oks[slot]
                        or (self.faults is not None
                            and self._fault("nan_logits", rid=rs.rid,
                                            tick=rec.tick))):
                    self._quarantine(slot, rs, now, rec.tick)
                    continue
                tok = int(toks[slot])
                rs.out_tokens.append(tok)
                if self.journal is not None:
                    # WAL ordering: the token is durable before any client
                    # can observe it, so recovery can never drop a token a
                    # client saw — and tokens still in the pending device
                    # buffer are never journaled, so it never replays one a
                    # client didn't
                    self.journal.record_token(rs.rid, tok)
                if self.token_sink is not None:
                    try:
                        if (self.faults is not None
                                and self._fault("sink_error", rid=rs.rid,
                                                tick=rec.tick)):
                            raise faults_lib.InjectedFault(
                                "sink_error", rs.rid, rec.tick)
                        self.token_sink(rs.rid, tok)
                    except Exception:
                        # sink containment: a failing consumer costs its
                        # own request ("sink_error"), never the engine or
                        # its co-batched streams. The token stays on
                        # out_tokens — delivery to the sink failed, the
                        # generation didn't.
                        self._retire(slot, rs, "sink_error", now, rec.tick)
                        continue
                if rs.first_token_time is None:
                    rs.first_token_time = now
                    self.trace.record(rs.rid, "first_token",
                                      ttft_s=now - rs.submit_time)
                self.stats["decode_tokens"] += 1
                delivered += 1
                if done[slot]:
                    reason = ("eos" if tok == self.ecfg.eos_id
                              else "max_tokens")
                    self._retire(slot, rs, reason, now, rec.tick)
        if self._tel is not None:
            if delivered:
                self._tel.decode_tokens.inc(delivered)
            self._publish_gauges()

    def _publish_gauges(self) -> None:
        """Refresh point-in-time gauges (slot/pool occupancy, sharing,
        refcount leaks, radix size) and mirror the prefix-cache lifetime
        counters into the registry. Called at drain boundaries — the same
        cadence slots and blocks actually change at — never per token.
        Pure host arithmetic over the allocator/radix bookkeeping."""
        t = self._tel
        if t is None:
            return
        t.slots_active.set(sum(r is not None for r in self.slot_req))
        if not self.paged:
            return
        alloc = self.allocator
        t.pool_blocks_free.set(alloc.free_blocks)
        t.pool_blocks_live.set(alloc.live_blocks)
        t.pool_blocks_shared.set(alloc.shared_blocks)
        # leak detection: every live block must be reachable from a slot's
        # reservation (suffix blocks + pinned cached prefix) or from a radix
        # node (cache-owned reference). A block nobody can account for means
        # a refcount was taken and never released.
        reachable = set()
        for rs in self.slot_req:
            if rs is not None:
                reachable.update(rs.blocks)
                reachable.update(rs.cached_blocks)
        if self.radix is not None:
            reachable.update(self.radix.block_ids())
            t.radix_nodes.set(self.radix.num_nodes())
            # the radix cache keeps its own lifetime counts; mirror them
            # (monotone, so set == sync) instead of double-counting events
            t.prefix_hits.set(self.radix.hits)
            t.prefix_misses.set(self.radix.misses)
            t.prefix_evictions.set(self.radix.evictions)
        leaked = [b for b in alloc.live_block_ids() if b not in reachable]
        t.pool_blocks_leaked.set(len(leaked))

    def _watchdog(self, step_s: float) -> None:
        """Tick watchdog: one observed device-step sync exceeding
        max(watchdog_floor_s, watchdog_ticks x rolling-p99) degrades the
        engine to DEGRADED instead of letting a hung device wedge the
        whole process silently. Recovery is automatic after
        `watchdog_recovery` consecutive in-threshold steps. Breaching
        samples stay out of the rolling window, so a burst of hangs cannot
        inflate the baseline and mask the next one."""
        mult = self.ecfg.watchdog_ticks
        if mult is None:
            return
        win = self._tick_window
        if len(win) >= self._watchdog_arm:
            thresh = max(self.ecfg.watchdog_floor_s,
                         mult * float(np.percentile(np.asarray(win), 99)))
            if step_s > thresh:
                self._watchdog_ok_streak = 0
                self.mark_degraded("watchdog")
                return
            if self._health == DEGRADED and self.health_reason == "watchdog":
                self._watchdog_ok_streak += 1
                if self._watchdog_ok_streak >= self.ecfg.watchdog_recovery:
                    self.mark_healthy("watchdog_recovered")
        win.append(step_s)

    def _quarantine(self, slot: int, rs: RequestState, now: float,
                    tick: int) -> None:
        """Numeric quarantine: this slot's decode logits went non-finite.
        Only the poisoned slot retires (reason "numeric_error"); co-batched
        slots in the same tick record stream on bit-identically — per-slot
        rows never mix in the decode math, so their logits are untouched by
        construction. The slot's exclusively-owned blocks (refcount 1 —
        exactly the ones its decode/prefill wrote that nobody shares) are
        scrubbed before _retire returns them to the allocator: recycled
        bytes are still read by the attention gather before masking, and
        NaN survives a `0 *` mask. Shared blocks were read-only for this
        slot and stay untouched."""
        if self.paged:
            for b in rs.blocks:
                if self.allocator.refcount(b) == 1:
                    self.caches = self._scrub(self.caches, np.int32(b))
        self._retire(slot, rs, "numeric_error", now, tick)

    # --- warmup -----------------------------------------------------------

    def warmup(self, prefill: bool = True) -> int:
        """Trace the decode jit for every decode bucket (and the prefill jit
        for every prefill bucket) with inert inputs, so serving never
        compiles again. Idle-slot decode writes land in the null block /
        stale rows exactly as during normal ghost ticks; trash prefills
        target the null block row (paged) or a to-be-overwritten slot row
        (dense). Returns the warm compile count."""
        assert all(r is None for r in self.slot_req) and not self._pending, \
            "warmup() requires an idle engine"
        buckets = self.decode_buckets if self.paged else (None,)
        for i, nb in enumerate(buckets):
            bt = self.block_table[:, :nb] if self.paged else None
            key = jax.random.fold_in(self._key, np.uint32(2**31 + i))
            self.caches, self._state, _, _, _ = self._decode(
                self.params, self.caches, self._state, bt, self._sp_packed,
                key)
        if prefill and self.paged:
            # chunked prefill: one trace per chunk-table bucket, plus the
            # copy-on-write block copy and the quarantine scrub (so fault
            # handling never compiles) — all against the null/trash block
            toks = np.zeros((1, self.prefill_chunk), np.int32)
            p0 = np.zeros(1, np.int32)
            for w in self.chunk_widths:
                row = np.full((1, w), kvc.NULL_BLOCK, np.int32)
                self.caches = self._chunk(self.params, toks, self.caches,
                                          row, p0, np.zeros(1, np.int32))
            self.caches = self._copy(self.caches, np.int32(kvc.NULL_BLOCK),
                                     np.int32(kvc.NULL_BLOCK))
            self.caches = self._scrub(self.caches, np.int32(kvc.NULL_BLOCK))
        elif prefill and self.bucketed:
            ef = (np.zeros((1, self.cfg.encoder.num_frames, self.cfg.d_model),
                           np.float32) if self.cfg.encoder is not None
                  else None)
            for b in self.buckets:
                toks = np.zeros((1, b), np.int32)
                tl = np.array([1], np.int32)
                self.caches = self._prefill(self.params, toks, tl,
                                            self.caches, np.int32(0), ef)
        return self.compile_count()

    # --- synchronous driver ----------------------------------------------

    def run(self, requests: List[Request],
            max_ticks: int = 100000) -> List[Request]:
        """Serve `requests` to completion; returns them in completion order
        (each Request's out_tokens is also filled in place)."""
        for req in requests:
            self.submit(req)
        completed: List[Request] = []
        ticks = 0
        while ((self.scheduler.waiting or any(r is not None
                                              for r in self.slot_req))
               and ticks < max_ticks):
            made_progress = self.step() > 0 or not self.scheduler.waiting
            completed.extend(self.poll())
            ticks += 1
            if not made_progress and not any(r is not None
                                             for r in self.slot_req):
                break    # queue head can never be admitted — bail, don't spin
        return completed

    # --- introspection ---------------------------------------------------

    def compile_count(self) -> int:
        """Total distinct jit traces — must not grow after warmup."""
        return sum(j.compiles for j in self._jits)

    def decode_cost(self, bucket: Optional[int] = None) -> Dict[str, float]:
        """Roofline terms of one decode tick at a given decode bucket, from
        the trip-count-aware HLO analyzer (roofline/hlo.py).

        `gather_bytes` is the paged KV read traffic (the dense-view gather)
        — the quantity that must scale with live context, never with pool
        capacity. `bytes` is the raw instruction-boundary proxy; it includes
        full-pool-shaped scatter *outputs* that donation aliases in place at
        runtime, so it overstates pool-size sensitivity (see docs/perf.md)."""
        from repro.roofline.hlo import analyze_hlo
        if self.paged:
            bucket = bucket or self.decode_buckets[-1]
            bt = jax.ShapeDtypeStruct((self.ecfg.slots, bucket), jnp.int32)
        else:
            bt = None
        shapes = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
            (self.params, self.caches, self._state, bt,
             samp_lib.pack(self._samp), self._key))
        hlo = (jax.jit(self._decode_fn)
               .lower(*shapes).compile().as_text())
        t = analyze_hlo(hlo)
        return {"flops": t.flops, "bytes": t.bytes,
                "dot_bytes": t.dot_bytes,
                "gather_bytes": t.bytes_by_op.get("gather", 0.0),
                # model-bytes/step: what the parameter tree streams per tick
                # as stored (packed payloads + exponent planes at
                # weight_bits < 16); weight_bytes is the host-side leaf sum,
                # param_bytes the HLO entry-parameter cross-check (it also
                # includes caches/state — the dtype split isolates the
                # packed planes)
                "weight_bytes": float(
                    wq_lib.packed_param_bytes(self.params)),
                "param_bytes": t.param_bytes,
                "param_bytes_by_dtype": dict(t.param_bytes_by_dtype)}

    def kernel_names(self) -> Dict[str, List[str]]:
        """Pallas kernels compiled into the decode step (largest bucket)
        and, on the paged backend, the chunked-prefill step, read from
        their lowering: nothing runs and nothing is traced into the
        engine's counted jits.  An interpreted kernel lowers to plain HLO
        and never shows up here, so on a TPU this names the kernels that
        really run."""
        def names(fn, *args) -> List[str]:
            text = jax.jit(fn).lower(*args).as_text()
            return sorted(set(re.findall(r"kernel_name\W+(\w+)", text)))

        bt = (self.block_table[:, :self.decode_buckets[-1]] if self.paged
              else None)
        out = {"decode": names(self._step_fns["decode"], self.params,
                               self.caches, self._state, bt,
                               self._sp_packed, self._key)}
        if self.paged:
            out["prefill_chunk"] = names(
                self._step_fns["prefill_chunk"], self.params,
                np.zeros((1, self.prefill_chunk), np.int32), self.caches,
                np.zeros((1, self.chunk_widths[0]), np.int32),
                np.zeros(1, np.int32), np.zeros(1, np.int32))
        return out

    def metrics(self) -> Dict[str, Any]:
        """Snapshot of the engine's serving metrics (merged over the
        scheduler's lifecycle aggregates). Side-effect-free and cheap: the
        config-derived entries were precomputed at construction
        (`_static_metrics`), the scheduler snapshot is O(1) histogram reads,
        and the dynamic entries below are dict lookups over host
        bookkeeping — no device sync, no jit, no per-request walk. The key
        set is a stable schema (docs/observability.md); keys are added, not
        renamed."""
        m = dict(self.scheduler.metrics())
        m.update(self.stats)
        m.update(self._static_metrics)
        m["compiles"] = self.compile_count()
        m["compiles_by_fn"] = {j.name: j.compiles for j in self._jits}
        m["health"] = self._health
        m["errors"] = list(self.errors)
        m["faults_injected"] = (dict(self.faults.injected)
                                if self.faults is not None else {})
        # prefix-cache counters are always present (zero when disabled) so
        # dashboards/launchers can report them unconditionally
        cached = self.stats["cached_prefix_tokens"]
        computed = self.stats["prefill_tokens"]
        m["cached_prefix_tokens"] = cached
        m["prefix_hit_rate"] = cached / max(cached + computed, 1)
        m["evictions"] = self.radix.evictions if self.radix else 0
        if self.paged:
            m["free_blocks"] = self.allocator.free_blocks
            if self.radix is not None:
                m["prefix_cache_nodes"] = self.radix.num_nodes()
                m["prefix_cache_hits"] = self.radix.hits
                m["prefix_cache_misses"] = self.radix.misses
        return m

    def prometheus_text(self) -> str:
        """Prometheus text exposition of the metrics registry (empty string
        with telemetry off — scrapers see a valid, if blank, page)."""
        if self.registry is None:
            return ""
        self._publish_gauges()      # gauges current as of the scrape
        return self.registry.to_prometheus_text()

    def export_trace(self, path) -> int:
        """Write the lifecycle-trace ring buffer as JSONL (wall-clock epoch
        header + one event per line, schema in serve/trace.py); returns the
        number of lines."""
        return self.trace.export_jsonl(path)

    # --- lifecycle --------------------------------------------------------

    def serve_metrics(self, port: int = 0):
        """Start an HTTP metrics endpoint for this engine's registry and
        *own* it: close() stops the socket and joins the serving thread, so
        embedders that manage the engine (or use it as a context manager)
        cannot leak the listener. Returns the server (`.port` carries the
        bound port when 0 was requested); idempotent — a second call returns
        the already-running server."""
        if self.registry is None:
            raise ValueError("serve_metrics() requires telemetry=True")
        if self._metrics_server is None:
            self._metrics_server = tel.start_metrics_server(
                self.registry, port, health_cb=lambda: self._health)
        return self._metrics_server

    def close(self) -> None:
        """Release host-side resources: enter DRAINING, deliver pending
        ticks (so no generated tokens are stranded on device) and stop the
        owned metrics endpoint. Idempotent, and exception-safe: even when
        the final drain raises (e.g. an injected fault or a poisoned
        device buffer), the metrics server is stopped, its thread joined,
        and its port released before the exception propagates. The engine
        remains usable for introspection (metrics(), export_trace())
        afterwards; DRAINING is terminal — a closed engine never reports
        healthy again."""
        try:
            self._set_health(DRAINING, "close")
            self._drain()
        finally:
            if self.journal is not None:
                self.journal.sync()
                if self._owns_journal:
                    self.journal.close()
            server, self._metrics_server = self._metrics_server, None
            if server is not None:
                server.stop()

    def __enter__(self) -> "ServeEngine":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False
