"""Serving throughput / latency benchmark under a synthetic arrival trace.

Drives the continuous-batching engine with Poisson request arrivals (requests
are submitted when the engine's decode tick passes their arrival tick) and
reports tokens/sec and time-to-first-token, for greedy and sampled decoding,
with float activations and with GRAU-quantized (QAT surrogate) activations —
the paper's serving story is that the GRAU unit makes the quantized column
cheap in hardware, and this bench gives the apples-to-apples software oracle.

The `decode_scaling` section is the paged-attention acceptance measurement:
at a large `blocks_per_slot` (long slot capacity, short live requests) it
serves the same trace through

  * `dense_gather_full`  — the pre-PR decode path: every tick gathers each
    slot's *entire* block-table row into a dense view (decode cost follows
    pool capacity), and
  * `paged_bucketed`     — the decode-bucket path (Pallas kernel on TPU,
    bucketed gather on host CPU): decode cost follows live tokens,

and reports tokens/sec for both plus per-step gathered bytes from the
trip-count-aware HLO cost analysis (engine.decode_cost).

The `kv_quant` section is the quantized-KV acceptance measurement: the same
trace through 16/8/4-bit paged pools (one PrecisionPolicy end to end) with
tokens/sec, per-step gathered bytes, and a teacher-forced logit-error probe
vs the 16-bit pools — the gather_bytes ratios and logit-error ceilings are
the CI gates.

    PYTHONPATH=src python benchmarks/serving_bench.py          # BENCH_serving.json
    PYTHONPATH=src python benchmarks/serving_bench.py --mesh 1x4
      (adds a sharded section: tokens/sec on a 1-device engine vs the same
       trace on a (data x model) mesh over forced host CPU devices)
    PYTHONPATH=src python benchmarks/serving_bench.py --quick  # CI smoke
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np

import jax

from repro.launch.mesh import ensure_host_devices, parse_mesh_spec
from repro.launch.runtime import use_repo_dirs
from repro.launch.serve import model_config
from repro.models import lm
from repro.models.config import GRAUConfig
from repro.serve.engine import EngineConfig, Request, ServeEngine
from repro.serve.sampling import SamplingParams
from repro.serve.sharding import init_params
from repro.serve.telemetry import percentiles


def synth_trace(n: int, mean_interarrival_ticks: float, vocab: int,
                max_new: int, seed: int, max_prompt: int = 24):
    """Poisson arrivals: (arrival_tick, prompt, max_new) per request."""
    rng = np.random.default_rng(seed)
    gaps = rng.exponential(mean_interarrival_ticks, size=n)
    arrivals = np.floor(np.cumsum(gaps)).astype(int)
    return [(int(a),
             rng.integers(2, vocab, size=int(rng.integers(4, max_prompt))),
             max_new)
            for a in arrivals]


def synth_shared_prefix_trace(n: int, mean_interarrival_ticks: float,
                              vocab: int, max_new: int, seed: int, *,
                              prefix_len: int = 96, n_prefixes: int = 4,
                              tail_lo: int = 4, tail_hi: int = 32):
    """Poisson arrivals where every prompt is one of `n_prefixes` shared
    system prompts plus a unique tail — the traffic shape prefix caching
    targets. Returns (trace, overlap_frac)."""
    rng = np.random.default_rng(seed)
    prefixes = [rng.integers(2, vocab, size=prefix_len)
                for _ in range(n_prefixes)]
    gaps = rng.exponential(mean_interarrival_ticks, size=n)
    arrivals = np.floor(np.cumsum(gaps)).astype(int)
    trace, total, shared = [], 0, 0
    for a in arrivals:
        pre = prefixes[int(rng.integers(0, n_prefixes))]
        tail = rng.integers(2, vocab,
                            size=int(rng.integers(tail_lo, tail_hi)))
        prompt = np.concatenate([pre, tail])
        total += len(prompt)
        shared += len(pre)
        trace.append((int(a), prompt, max_new))
    return trace, shared / total


def run_trace(engine: ServeEngine, trace, sampling: SamplingParams,
              max_ticks: int = 100000):
    """Submit requests as their arrival tick passes; drain to completion."""
    pending = [(a, Request(rid=i, prompt=p, max_new_tokens=m,
                           sampling=sampling))
               for i, (a, p, m) in enumerate(trace)]
    n_finished_before = len(engine.scheduler.finished)   # exclude warmup
    t0 = time.perf_counter()
    ticks = 0
    done = []
    while (pending or engine.scheduler.waiting
           or any(r is not None for r in engine.slot_req)):
        while pending and pending[0][0] <= ticks:
            engine.submit(pending.pop(0)[1])
        engine.step()
        done.extend(engine.poll())
        ticks += 1
        if ticks >= max_ticks:
            raise RuntimeError("trace did not drain")
    wall = time.perf_counter() - t0
    gen_tokens = sum(len(r.out_tokens or []) for r in done)
    ttfts = [rs.ttft
             for rs in list(engine.scheduler.finished)[n_finished_before:]
             if rs.ttft is not None]
    # the shared exact implementation (serve/telemetry.py) — the scheduler's
    # live snapshot uses the histogram estimate; reports use this
    p50, p90, p99 = percentiles(ttfts, (50, 90, 99))
    return {
        "wall_s": wall,
        "generated_tokens": gen_tokens,
        "tokens_per_s": gen_tokens / wall if wall > 0 else 0.0,
        "ttft_mean_s": float(np.mean(ttfts)),
        "ttft_p50_s": p50,
        "ttft_p90_s": p90,
        "ttft_p99_s": p99,
        "ticks": ticks,
        "compiles": engine.compile_count(),
        "backend": "paged" if engine.paged else "dense",
    }


def bench_decode_scaling(cfg, params, args):
    """Pre-PR full-table gather vs bucketed decode at large blocks_per_slot.

    The trace is deliberately long (hundreds of decode ticks) and each
    variant is timed `--scaling-reps` times with the median reported: the
    per-tick wall cost on host CPU is small enough that a single short
    window would be dominated by scheduler noise, which the CI regression
    gate must not be.
    """
    trace = synth_trace(args.scaling_requests, 1.0, cfg.vocab_size,
                        max(args.max_new, 16), args.seed)
    base = dict(slots=max(args.slots, 8), max_seq=args.scaling_max_seq,
                page_size=16, seed=args.seed)
    blocks_per_slot = -(-args.scaling_max_seq // 16)
    # the largest context the trace can reach decides which decode bucket
    # the bucketed engine actually runs — report decode_cost for that one
    max_ctx = max(len(p) + m for _, p, m in trace)
    live_blocks = -(-(max_ctx + 1) // 16)
    variants = {
        # pre-PR cost model: one decode signature whose block table always
        # spans the whole slot capacity
        "dense_gather_full": EngineConfig(
            decode_buckets=(blocks_per_slot,), paged_impl="gather", **base),
        # the shipped path (auto impl: Pallas kernel on TPU, bucketed
        # gather on host CPU)
        "paged_bucketed": EngineConfig(**base),
    }
    out = {"blocks_per_slot": blocks_per_slot,
           "max_seq": args.scaling_max_seq, "slots": base["slots"]}
    for name, ecfg in variants.items():
        reps = []
        for _ in range(args.scaling_reps):
            engine = ServeEngine(cfg, params, ecfg)
            engine.warmup()
            reps.append(run_trace(engine, trace, SamplingParams()))
        stats = sorted(reps, key=lambda s: s["tokens_per_s"])[len(reps) // 2]
        stats["tokens_per_s_reps"] = [r["tokens_per_s"] for r in reps]
        from repro.serve import kv_cache as kvc
        bucket = kvc.bucket_for(min(live_blocks, blocks_per_slot),
                                engine.decode_buckets)
        cost = engine.decode_cost(bucket if name == "paged_bucketed"
                                  else blocks_per_slot)
        stats["decode_cost_per_step"] = cost
        stats["paged_impl"] = engine.paged_impl
        out[name] = stats
        print(f"decode_scaling/{name}: {stats['tokens_per_s']:.1f} tok/s "
              f"[{engine.paged_impl}], gathered {cost['gather_bytes']:.0f} "
              "B/step", flush=True)
    out["speedup"] = (out["paged_bucketed"]["tokens_per_s"]
                      / max(out["dense_gather_full"]["tokens_per_s"], 1e-9))
    out["gather_bytes_ratio"] = (
        out["dense_gather_full"]["decode_cost_per_step"]["gather_bytes"]
        / max(out["paged_bucketed"]["decode_cost_per_step"]["gather_bytes"],
              1e-9))
    print(f"decode_scaling: {out['speedup']:.2f}x tokens/sec, "
          f"{out['gather_bytes_ratio']:.1f}x fewer gathered bytes/step",
          flush=True)
    return out


def bench_prefix_caching(cfg, params, args):
    """Shared-prefix trace through cache-off vs cache-on engines.

    Both engines run the identical chunk-grid prefill state machine (so the
    comparison isolates *reuse*, and token streams stay bit-identical); the
    section reports prefix hit rate, prefill tokens avoided, and TTFT
    p50/p99 improvement — the admission-latency win of not re-computing the
    shared system prompt. Each variant is timed `--prefix-reps` times with
    the median kept (same rationale as decode_scaling: the CI gate must not
    be scheduler noise).
    """
    trace, overlap = synth_shared_prefix_trace(
        args.prefix_requests, args.interarrival, cfg.vocab_size,
        max(args.max_new, 8), args.seed, prefix_len=args.prefix_len)
    base = dict(slots=max(args.slots, 4), max_seq=256, page_size=16,
                prefill_chunk=32, seed=args.seed)
    out = {"prefix_len": args.prefix_len, "overlap_frac": overlap,
           "requests": args.prefix_requests, "prefill_chunk": 32,
           "slots": base["slots"]}
    tokens = {}
    for name, on in (("cache_off", False), ("cache_on", True)):
        reps = []
        for _ in range(args.prefix_reps):
            engine = ServeEngine(cfg, params,
                                 EngineConfig(prefix_cache=on, **base))
            warm = engine.warmup()
            stats = run_trace(engine, trace, SamplingParams())
            stats["recompiles_after_warmup"] = (engine.compile_count()
                                                - warm)
            m = engine.metrics()
            stats["prefill_tokens"] = m["prefill_tokens"]
            stats["cached_prefix_tokens"] = m["cached_prefix_tokens"]
            stats["prefix_hit_rate"] = m["prefix_hit_rate"]
            stats["evictions"] = m["evictions"]
            stats["prefill_tokens_per_request"] = \
                m["prefill_tokens_per_request"]
            reps.append(stats)
            toks = {rs.rid: tuple(rs.out_tokens)
                    for rs in engine.scheduler.finished}
        tokens[name] = toks
        out[name] = sorted(reps, key=lambda s: s["ttft_p50_s"])[len(reps) // 2]
        print(f"prefix_caching/{name}: TTFT p50 "
              f"{out[name]['ttft_p50_s'] * 1e3:.1f} ms, p99 "
              f"{out[name]['ttft_p99_s'] * 1e3:.1f} ms, "
              f"{out[name]['prefill_tokens']} prefill tokens computed, "
              f"hit rate {out[name]['prefix_hit_rate']:.2f} "
              f"[{out[name]['recompiles_after_warmup']} recompiles]",
              flush=True)
    # reuse must be invisible in the streams: bit-identical tokens — checked
    # in float mode (the timed runs above) and in GRAU mode (one short pass)
    out["tokens_bit_identical"] = tokens["cache_on"] == tokens["cache_off"]
    grau_cfg = cfg.replace(grau=GRAUConfig())
    gparams, _ = lm.init_lm(grau_cfg, jax.random.PRNGKey(0),
                            dtype=jax.numpy.float32)
    gtoks = {}
    for on in (False, True):
        engine = ServeEngine(grau_cfg, gparams,
                             EngineConfig(prefix_cache=on, **base))
        run_trace(engine, trace[:12], SamplingParams())
        gtoks[on] = {rs.rid: tuple(rs.out_tokens)
                     for rs in engine.scheduler.finished}
    out["tokens_bit_identical_grau"] = gtoks[True] == gtoks[False]
    out["ttft_p50_improvement"] = (out["cache_off"]["ttft_p50_s"]
                                   / max(out["cache_on"]["ttft_p50_s"], 1e-9))
    out["ttft_p99_improvement"] = (out["cache_off"]["ttft_p99_s"]
                                   / max(out["cache_on"]["ttft_p99_s"], 1e-9))
    out["prefill_tokens_avoided_frac"] = 1.0 - (
        out["cache_on"]["prefill_tokens"]
        / max(out["cache_off"]["prefill_tokens"], 1))
    print(f"prefix_caching: {out['ttft_p50_improvement']:.2f}x TTFT p50, "
          f"{out['ttft_p99_improvement']:.2f}x p99, "
          f"{out['prefill_tokens_avoided_frac'] * 100:.0f}% prefill tokens "
          f"avoided at {overlap * 100:.0f}% overlap, tokens bit-identical: "
          f"{out['tokens_bit_identical']}", flush=True)
    return out


def kv_logit_probe(cfg, params, kv_bits: int, *, total: int = 64,
                   prefill: int = 48, page: int = 16, seed: int = 0):
    """Teacher-forced logits through the paged pipeline at one KV precision.

    One fixed token sequence runs the exact serving datapath — chunked
    prefill through a block table, then per-token decode writes + reads —
    and the logits at every decode position come back.  Every precision sees
    *identical* token inputs (teacher forcing), so the difference between a
    quantized run and the 16-bit run is purely KV storage error: the
    kv_quant section's logit-error-vs-bf16 column and its regression ceiling.
    """
    import jax.numpy as jnp

    from repro.nn.attention import PagedState
    from repro.quant.policy import kv_policy
    from repro.serve import kv_cache as kvc

    rng = np.random.default_rng(seed)
    tokens = rng.integers(2, cfg.vocab_size, size=total).astype(np.int32)
    nblocks = -(-total // page)
    caches = kvc.init_paged_caches(
        cfg, nblocks + 1, page, dtype=jnp.float32,
        policy=kv_policy(kv_bits) if kv_bits != 16 else None)
    row = np.arange(1, nblocks + 1, dtype=np.int32)[None]
    logits_out = []
    for p0 in range(0, prefill, page):
        chunk = tokens[None, p0:p0 + page]
        st = PagedState(jnp.asarray(row), jnp.asarray([p0], np.int32))
        last, caches = lm.prefill_step(params, cfg, jnp.asarray(chunk),
                                       caches, paged=st, paged_impl="gather")
    logits_out.append(np.asarray(last, np.float32))
    for pos in range(prefill, total):
        # engine semantics: token at absolute position `pos` is fed with
        # length=pos — its K/V lands at position pos, attention spans pos+1
        st = PagedState(jnp.asarray(row), jnp.asarray([pos], np.int32))
        lg, caches = lm.decode_step(params, cfg,
                                    jnp.asarray(tokens[None, pos:pos + 1]),
                                    caches, paged=st, paged_impl="gather")
        logits_out.append(np.asarray(lg[:, -1], np.float32))
    return np.concatenate(logits_out, axis=0)     # (1 + decode_steps, vocab)


def bench_kv_quant(cfg, params, args):
    """Quantized-KV serving: 16/8/4-bit pools on one identical trace.

    Reports, per kv_bits: tokens/sec on the Poisson trace (same schedule at
    every precision — quantization changes values, never shapes or
    programs), per-decode-step gathered bytes from the compiled HLO
    (engine.decode_cost — the packed pools must shrink this), recompiles
    after warmup, and teacher-forced max-logit error vs the 16-bit pools.
    The gather-bytes ratios and logit-error ceilings are the CI gates: on
    the host-CPU runner the 16-bit reference gathers at f32 width (XLA CPU
    widens half-precision pools before gathering), which is also what bf16
    pools lower to there.
    """
    trace = synth_trace(args.kv_requests, args.interarrival, cfg.vocab_size,
                        max(args.max_new, 8), args.seed)
    base = dict(slots=max(args.slots, 4), max_seq=128, page_size=16,
                seed=args.seed)
    out = {"requests": args.kv_requests, "slots": base["slots"],
           "max_seq": base["max_seq"]}
    logits = {}
    for name, bits in (("kv16", 16), ("kv8", 8), ("kv4", 4)):
        reps = []
        for _ in range(args.kv_reps):
            engine = ServeEngine(
                cfg, params,
                EngineConfig(kv_bits=bits if bits != 16 else None, **base))
            warm = engine.warmup()
            stats = run_trace(engine, trace, SamplingParams())
            stats["recompiles_after_warmup"] = (engine.compile_count()
                                                - warm)
            reps.append(stats)
        stats = sorted(reps, key=lambda s: s["tokens_per_s"])[len(reps) // 2]
        stats["tokens_per_s_reps"] = [r["tokens_per_s"] for r in reps]
        cost = engine.decode_cost(engine.decode_buckets[-1])
        stats["gather_bytes_per_step"] = cost["gather_bytes"]
        stats["kv_bits"] = bits
        logits[name] = kv_logit_probe(cfg, params, bits, seed=args.seed)
        stats["max_logit_error_vs_16"] = float(
            np.max(np.abs(logits[name] - logits["kv16"])))
        stats["top1_agreement_vs_16"] = float(np.mean(
            logits[name].argmax(-1) == logits["kv16"].argmax(-1)))
        out[name] = stats
        print(f"kv_quant/{name}: {stats['tokens_per_s']:.1f} tok/s, "
              f"gathered {stats['gather_bytes_per_step']:.0f} B/step, "
              f"max logit err {stats['max_logit_error_vs_16']:.4f}, "
              f"top-1 agree {stats['top1_agreement_vs_16']:.2f} "
              f"[{stats['recompiles_after_warmup']} recompiles]",
              flush=True)
    out["gather_bytes_ratio_int8"] = (out["kv16"]["gather_bytes_per_step"]
                                      / out["kv8"]["gather_bytes_per_step"])
    out["gather_bytes_ratio_int4"] = (out["kv16"]["gather_bytes_per_step"]
                                      / out["kv4"]["gather_bytes_per_step"])
    print(f"kv_quant: {out['gather_bytes_ratio_int8']:.2f}x fewer gathered "
          f"B/step at int8, {out['gather_bytes_ratio_int4']:.2f}x at int4",
          flush=True)
    return out


def bench_weight_quant(cfg, params, args):
    """Weight-only quantized serving: 16/8/4-bit matmul weights, one trace.

    Reports, per weight_bits: tokens/sec on the same Poisson trace (packing
    changes leaf types once at construction, never programs — the recompile
    column must stay zero), resident weight bytes from the packed tree
    (engine.decode_cost's ``weight_bytes`` — the floor-gated shrink ratios),
    the compiled step's parameter bytes by dtype (the f32 -> s8 shift is the
    model-bytes/step roofline term), and teacher-forced logit error / top-1
    agreement vs the raw-f32 engine.  The probe reuses kv_logit_probe with a
    pre-packed tree: identical datapath, so the delta is purely weight
    storage error.  Closes with the full composition — int4 weights + int4
    KV pools + GRAU attention activations — which must complete with bounded
    error against its own f32 reference: the fully shift-based decode
    datapath.
    """
    from repro.quant import weights as wq_lib
    from repro.quant.policy import weight_policy

    trace = synth_trace(args.wq_requests, args.interarrival, cfg.vocab_size,
                        max(args.max_new, 8), args.seed)
    base = dict(slots=max(args.slots, 4), max_seq=128, page_size=16,
                seed=args.seed)
    out = {"requests": args.wq_requests, "slots": base["slots"],
           "max_seq": base["max_seq"]}

    def probe(pcfg, p, bits, kv_bits=16):
        packed = (p if bits == 16
                  else wq_lib.pack_params(p, pcfg, weight_policy(bits)))
        return kv_logit_probe(pcfg, packed, kv_bits, seed=args.seed)

    logits = {}
    for name, bits in (("wq16", 16), ("wq8", 8), ("wq4", 4)):
        reps = []
        for _ in range(args.wq_reps):
            engine = ServeEngine(
                cfg, params,
                EngineConfig(weight_bits=bits if bits != 16 else None,
                             **base))
            warm = engine.warmup()
            stats = run_trace(engine, trace, SamplingParams())
            stats["recompiles_after_warmup"] = (engine.compile_count()
                                                - warm)
            reps.append(stats)
        stats = sorted(reps, key=lambda s: s["tokens_per_s"])[len(reps) // 2]
        stats["tokens_per_s_reps"] = [r["tokens_per_s"] for r in reps]
        cost = engine.decode_cost(engine.decode_buckets[-1])
        stats["weight_bytes"] = cost["weight_bytes"]
        stats["param_bytes_by_dtype"] = cost["param_bytes_by_dtype"]
        stats["weight_bits"] = bits
        logits[name] = probe(cfg, params, bits)
        stats["max_logit_error_vs_16"] = float(
            np.max(np.abs(logits[name] - logits["wq16"])))
        stats["top1_agreement_vs_16"] = float(np.mean(
            logits[name].argmax(-1) == logits["wq16"].argmax(-1)))
        out[name] = stats
        print(f"weight_quant/{name}: {stats['tokens_per_s']:.1f} tok/s, "
              f"weights {stats['weight_bytes']:.0f} B resident, "
              f"max logit err {stats['max_logit_error_vs_16']:.4f}, "
              f"top-1 agree {stats['top1_agreement_vs_16']:.2f} "
              f"[{stats['recompiles_after_warmup']} recompiles]",
              flush=True)
    out["weight_bytes_ratio_int8"] = (out["wq16"]["weight_bytes"]
                                      / out["wq8"]["weight_bytes"])
    out["weight_bytes_ratio_int4"] = (out["wq16"]["weight_bytes"]
                                      / out["wq4"]["weight_bytes"])
    print(f"weight_quant: {out['weight_bytes_ratio_int8']:.2f}x fewer "
          f"resident weight bytes at int8, "
          f"{out['weight_bytes_ratio_int4']:.2f}x at int4", flush=True)

    # composition: every matmul weight a shifted int4, every KV read a
    # shifted int4, every attention activation through the GRAU PWLF — the
    # paper's multiplier-free arithmetic on the whole decode datapath at
    # once.  Gated on completing the trace with zero recompiles and bounded
    # teacher-forced error vs the same GRAU model served in raw f32.
    gcfg = cfg.replace(grau=GRAUConfig())
    gparams, _ = lm.init_lm(gcfg, jax.random.PRNGKey(0),
                            dtype=jax.numpy.float32)
    engine = ServeEngine(gcfg, gparams,
                         EngineConfig(weight_bits=4, kv_bits=4, **base))
    warm = engine.warmup()
    stats = run_trace(engine, trace, SamplingParams())
    stats["recompiles_after_warmup"] = engine.compile_count() - warm
    ref = probe(gcfg, gparams, 16)
    comp = probe(gcfg, gparams, 4, kv_bits=4)
    stats["max_logit_error_vs_16"] = float(np.max(np.abs(comp - ref)))
    stats["top1_agreement_vs_16"] = float(np.mean(
        comp.argmax(-1) == ref.argmax(-1)))
    out["composition_wq4_kv4_grau"] = stats
    print(f"weight_quant/composition wq4+kv4+grau: "
          f"{stats['tokens_per_s']:.1f} tok/s, "
          f"max logit err {stats['max_logit_error_vs_16']:.4f}, "
          f"top-1 agree {stats['top1_agreement_vs_16']:.2f} "
          f"[{stats['recompiles_after_warmup']} recompiles]", flush=True)
    return out


def synth_overload_trace(n: int, mean_interarrival_ticks: float, vocab: int,
                         max_new: int, seed: int, *, big_every: int = 6,
                         big_prompt: int = 60, max_prompt: int = 16):
    """Poisson arrivals where every `big_every`-th request carries a long
    prompt — the head-of-line shape: a big reservation blocks while smalls
    stream past it, so a tight pool exercises lookahead admission and then
    KV-pressure preemption once the big head ages."""
    rng = np.random.default_rng(seed)
    gaps = rng.exponential(mean_interarrival_ticks, size=n)
    arrivals = np.floor(np.cumsum(gaps)).astype(int)
    trace = []
    for i, a in enumerate(arrivals):
        size = (big_prompt if i % big_every == big_every - 1
                else int(rng.integers(4, max_prompt)))
        trace.append((int(a), rng.integers(2, vocab, size=size), max_new))
    return trace


def _run_overload_trace(engine: ServeEngine, trace,
                        sampling: SamplingParams, max_ticks: int = 100000):
    """run_trace plus overload accounting: admission-refusal errors are
    counted (the contract is zero — overload control is backpressure and
    preemption, never refusal), and per-request streams/preempt counts come
    back for the bit-identity checks."""
    pending = [(a, Request(rid=i, prompt=p, max_new_tokens=m,
                           sampling=sampling))
               for i, (a, p, m) in enumerate(trace)]
    n_before = len(engine.scheduler.finished)
    errors = 0
    t0 = time.perf_counter()
    ticks = 0
    done = []
    while (pending or engine.scheduler.waiting
           or any(r is not None for r in engine.slot_req)):
        while pending and pending[0][0] <= ticks:
            try:
                engine.submit(pending.pop(0)[1])
            except ValueError:
                errors += 1
        engine.step()
        done.extend(engine.poll())
        ticks += 1
        if ticks >= max_ticks:
            raise RuntimeError("overload trace did not drain")
    wall = time.perf_counter() - t0
    finished = list(engine.scheduler.finished)[n_before:]
    gen_tokens = sum(len(r.out_tokens or []) for r in done)
    ttfts = [rs.ttft for rs in finished if rs.ttft is not None]
    tpots = [rs.tpot for rs in finished if rs.tpot is not None]
    m = engine.metrics()
    ttft_p50, ttft_p99 = percentiles(ttfts, (50, 99))
    tpot_p50, tpot_p99 = percentiles(tpots, (50, 99))
    stats = {
        "wall_s": wall,
        "ticks": ticks,
        "completed": len(done),
        "generated_tokens": gen_tokens,
        "goodput_tokens_per_s": gen_tokens / wall if wall > 0 else 0.0,
        "ttft_p50_s": ttft_p50,
        "ttft_p99_s": ttft_p99,
        "tpot_p50_s": tpot_p50,
        "tpot_p99_s": tpot_p99,
        "admission_errors": errors,
        "preempted": m["preempted"],
        "hol_skips": m["hol_skips"],
        "compiles": engine.compile_count(),
    }
    streams = {rs.rid: tuple(rs.out_tokens) for rs in finished}
    preempt_counts = {rs.rid: rs.preempt_count for rs in finished}
    return stats, streams, preempt_counts


def bench_overload(cfg, params, args):
    """Overload sweep: Poisson arrivals at 1.0/1.5/2.0x estimated capacity
    through a deliberately tight KV pool (big every-6th prompts need most
    of it), preemption on — plus a preemption-off run at 1.5x for the
    control comparison.

    The contracts this section gates: past capacity the engine preempts
    instead of refusing admission (preempted > 0, admission_errors == 0 at
    every rate), goodput holds a floor, and preemption is stream-invisible
    — greedy token streams of never-preempted requests are bit-identical
    to the non-preempting engine's, and preempted requests reproduce their
    uninterrupted streams exactly (fold + chunk-grid recompute + resumed
    sample_step). Keys use `p` for the decimal point (r1p5x = 1.5x) so the
    check_regression dotted paths stay unambiguous.
    """
    slots = max(args.slots, 4)
    max_new = max(args.max_new, 8)
    # capacity estimate: each retired request occupies one slot for about
    # max_new decode ticks, so `slots` requests retire per ~max_new ticks
    capacity_interarrival = max_new / slots
    ecfg = dict(slots=slots, max_seq=128, page_size=16,
                num_blocks=args.overload_blocks, prefill_chunk=32,
                preempt_after_ticks=4, seed=args.seed)
    out = {"requests": args.overload_requests, "slots": slots,
           "num_blocks": args.overload_blocks,
           "capacity_interarrival_ticks": capacity_interarrival}
    runs = {}
    for label, rate, preempt in (("r1x", 1.0, True), ("r1p5x", 1.5, True),
                                 ("r2x", 2.0, True),
                                 ("r1p5x_no_preempt", 1.5, False)):
        trace = synth_overload_trace(
            args.overload_requests, capacity_interarrival / rate,
            cfg.vocab_size, max_new, args.seed)
        engine = ServeEngine(cfg, params,
                             EngineConfig(preemption=preempt, **ecfg))
        warm = engine.warmup()
        stats, streams, pc = _run_overload_trace(engine, trace,
                                                 SamplingParams())
        stats["recompiles_after_warmup"] = engine.compile_count() - warm
        stats["rate_x_capacity"] = rate
        runs[label] = (streams, pc)
        out[label] = stats
        print(f"overload/{label}: goodput "
              f"{stats['goodput_tokens_per_s']:.1f} tok/s, TTFT p99 "
              f"{stats['ttft_p99_s'] * 1e3:.1f} ms, TPOT p99 "
              f"{(stats['tpot_p99_s'] or 0) * 1e3:.1f} ms, "
              f"preempted {stats['preempted']}, hol_skips "
              f"{stats['hol_skips']}, admission errors "
              f"{stats['admission_errors']} "
              f"[{stats['recompiles_after_warmup']} recompiles]",
              flush=True)
    on_streams, on_pc = runs["r1p5x"]
    off_streams, _ = runs["r1p5x_no_preempt"]
    never = {rid for rid, n in on_pc.items() if n == 0}
    out["tokens_bit_identical_never_preempted"] = all(
        on_streams[rid] == off_streams.get(rid) for rid in never)
    out["tokens_bit_identical_all"] = on_streams == off_streams
    out["preempted_requests_r1p5x"] = sum(1 for n in on_pc.values() if n)
    out["admission_errors_total"] = sum(
        out[k]["admission_errors"]
        for k in ("r1x", "r1p5x", "r2x", "r1p5x_no_preempt"))
    out["goodput_ratio_r1p5x"] = (
        out["r1p5x"]["goodput_tokens_per_s"]
        / max(out["r1p5x_no_preempt"]["goodput_tokens_per_s"], 1e-9))
    print(f"overload: preempted {out['r1p5x']['preempted']} at 1.5x "
          f"({out['preempted_requests_r1p5x']} requests), goodput ratio "
          f"vs no-preempt {out['goodput_ratio_r1p5x']:.2f}, bit-identical "
          f"never-preempted {out['tokens_bit_identical_never_preempted']}, "
          f"all {out['tokens_bit_identical_all']}", flush=True)
    return out


def bench_telemetry(cfg, params, args):
    """Telemetry overhead: one identical trace through telemetry-on vs -off
    engines (paged backend with prefix cache on, so every publish site —
    spans, counters, gauges, tick phases — is actually exercised).

    The contract this section gates: telemetry is host-side bookkeeping
    only, so turning it on must (a) leave token streams bit-identical,
    (b) leave the warm compile count unchanged and cause zero recompiles
    (no new jit traces), and (c) cost <= 5% decode throughput —
    `overhead_ratio` (on/off, medians over `--telemetry-reps`) is the
    check_regression hard floor at 0.95. The section also smoke-exports
    both surfaces: Prometheus text size and (with --trace-out) the
    lifecycle-trace JSONL artifact CI uploads.
    """
    trace = synth_trace(args.telemetry_requests, 1.0, cfg.vocab_size,
                        max(args.max_new, 8), args.seed)
    base = dict(slots=max(args.slots, 4), max_seq=128, page_size=16,
                prefix_cache=True, prefill_chunk=32, seed=args.seed)
    out = {"requests": args.telemetry_requests, "slots": base["slots"],
           "reps": args.telemetry_reps}
    tokens = {}
    for name, on in (("telemetry_off", False), ("telemetry_on", True)):
        reps = []
        for _ in range(args.telemetry_reps):
            engine = ServeEngine(cfg, params,
                                 EngineConfig(telemetry=on, **base))
            warm = engine.warmup()
            stats = run_trace(engine, trace, SamplingParams())
            stats["warm_compiles"] = warm
            stats["recompiles_after_warmup"] = (engine.compile_count()
                                                - warm)
            reps.append(stats)
        tokens[name] = {rs.rid: tuple(rs.out_tokens)
                        for rs in engine.scheduler.finished}
        med = sorted(reps, key=lambda s: s["tokens_per_s"])[len(reps) // 2]
        med["tokens_per_s_reps"] = [r["tokens_per_s"] for r in reps]
        out[name] = med
        print(f"telemetry/{name}: {med['tokens_per_s']:.1f} tok/s "
              f"[warm={med['warm_compiles']}, "
              f"{med['recompiles_after_warmup']} recompiles]", flush=True)
    out["tokens_bit_identical"] = (tokens["telemetry_on"]
                                   == tokens["telemetry_off"])
    out["warm_compiles_equal"] = (out["telemetry_on"]["warm_compiles"]
                                  == out["telemetry_off"]["warm_compiles"])
    out["overhead_ratio"] = (out["telemetry_on"]["tokens_per_s"]
                             / max(out["telemetry_off"]["tokens_per_s"],
                                   1e-9))
    # export-surface smoke on the last telemetry-on engine: a scrape and a
    # trace dump must both be non-trivially populated after real traffic
    prom = engine.prometheus_text()
    out["prometheus_bytes"] = len(prom)
    out["prometheus_families"] = sum(
        1 for line in prom.splitlines() if line.startswith("# TYPE"))
    snap = engine.registry.snapshot()
    out["decode_tokens_counted"] = snap["serve_decode_tokens_total"]
    out["pool_blocks_leaked"] = snap["serve_kv_pool_blocks_leaked"]
    if args.trace_out:
        out["trace_events_written"] = engine.export_trace(args.trace_out)
        print(f"telemetry: wrote {out['trace_events_written']} trace events "
              f"to {args.trace_out}", flush=True)
    print(f"telemetry: overhead_ratio={out['overhead_ratio']:.3f} "
          f"(on/off tok/s), bit-identical={out['tokens_bit_identical']}, "
          f"warm-compiles-equal={out['warm_compiles_equal']}", flush=True)
    return out


def _faults_requests(cfg, args, *, shared, with_tails=True):
    """Deterministic mixed batch: every prompt is `shared` plus a per-rid
    tail, so the fault-matrix target (rid 1) holds real radix pins when the
    shared prefix is already published. Rebuilt per call — fault runs and
    the fault-free baseline must see bit-identical inputs."""
    reqs = []
    for i in range(args.faults_requests):
        tail = np.random.default_rng(args.seed * 1000 + i).integers(
            2, cfg.vocab_size, size=6).astype(np.int32)
        prompt = np.concatenate([shared, tail]) if with_tails else shared
        reqs.append(Request(rid=i, prompt=prompt.copy(),
                            max_new_tokens=max(args.max_new, 8),
                            sampling=SamplingParams()))
    return reqs


def bench_faults(cfg, params, args):
    """Fault containment: the deterministic injection matrix from
    serve/faults.fault_matrix, one engine per site, against a fault-free
    baseline on the identical workload.

    The contracts this section gates: every injected fault retires exactly
    its target request with a structured reason (never a hang, never an
    unhandled exception), every *unaffected* stream is bit-identical to the
    fault-free run, `engine.audit()` reclaims injected pin/block leaks and
    leaves zero leaked blocks, and no containment path compiles a new jit
    trace. Plus the two degradation demos — deadline_ms retiring an expired
    request with reason "deadline", and the tick watchdog degrading on an
    injected slow step then auto-recovering — and a seeded chaos run whose
    lifecycle trace is the CI artifact (--faults-trace-out).
    """
    from repro.serve import faults as faults_lib

    target = 1
    shared = np.random.default_rng(args.seed + 17).integers(
        2, cfg.vocab_size, size=32).astype(np.int32)
    base = dict(slots=max(args.slots, 4), max_seq=128, page_size=16,
                prefix_cache=True, prefill_chunk=32, seed=args.seed)
    sink = lambda rid, tok: None    # noqa: E731 — sink_error needs a sink

    def run_batch(plan, publish):
        engine = ServeEngine(cfg, params,
                             EngineConfig(faults=plan, **base))
        warm = engine.warmup()
        engine.token_sink = sink
        if publish:
            # publish the shared prefix first so batch targets hold pins
            engine.run([Request(rid=100, prompt=shared.copy(),
                                max_new_tokens=4)])
        engine.run(_faults_requests(cfg, args, shared=shared))
        fin = {rs.rid: rs.finish_reason for rs in engine.scheduler.finished
               if rs.rid != 100}
        streams = {rs.rid: tuple(rs.out_tokens)
                   for rs in engine.scheduler.finished if rs.rid != 100}
        recompiles = engine.compile_count() - warm
        return engine, fin, streams, recompiles

    baselines = {}
    for publish in (False, True):
        engine, fin, streams, rec = run_batch(None, publish)
        baselines[publish] = streams
        engine.close()

    out = {"target_rid": target, "requests": args.faults_requests,
           "sites": {}}
    leak_sites = ("radix_pin_leak", "block_leak")
    for site, plan, reason in faults_lib.fault_matrix(target):
        if site == "process_crash":
            # deliberate: a process crash is not containable by design —
            # the recovery section (bench_recovery) exercises it end to
            # end via journal replay in a fresh engine
            continue
        publish = site in leak_sites
        engine, fin, streams, recompiles = run_batch(plan, publish)
        rep = engine.audit()
        rep2 = engine.audit()
        others_ok = all(streams.get(rid) == toks
                        for rid, toks in baselines[publish].items()
                        if rid != target)
        s = {
            "retire_reason": fin.get(target),
            "reason_ok": (True if reason is None
                          else fin.get(target) == reason),
            "streams_bit_identical": others_ok,
            "injected": plan.injected.get(site, 0),
            "reclaimed_refs": rep["reclaimed_refs"],
            "reclaimed_pins": rep["reclaimed_pins"],
            "reclaimed_second_audit": (rep2["reclaimed_refs"]
                                       + rep2["reclaimed_pins"]),
            "leaked_after": rep["leaked_after"],
            "recompiles_after_warmup": recompiles,
            "health": engine.health,
        }
        out["sites"][site] = s
        engine.close()
        print(f"faults/{site}: reason={s['retire_reason']!r} "
              f"(ok={s['reason_ok']}), streams bit-identical="
              f"{s['streams_bit_identical']}, reclaimed "
              f"{s['reclaimed_refs']}r/{s['reclaimed_pins']}p, leaked "
              f"{s['leaked_after']} [{recompiles} recompiles]", flush=True)

    sites = out["sites"]
    out["reasons_structured_all"] = all(s["reason_ok"]
                                        for s in sites.values())
    out["streams_bit_identical_all"] = all(s["streams_bit_identical"]
                                           for s in sites.values())
    out["all_sites_injected"] = all(s["injected"] >= 1
                                    for s in sites.values())
    out["leak_reclaim_ok"] = all(
        sites[ls]["reclaimed_refs"] + sites[ls]["reclaimed_pins"] > 0
        and sites[ls]["reclaimed_second_audit"] == 0 for ls in leak_sites)
    out["leaked_after_max"] = max(s["leaked_after"] for s in sites.values())
    out["recompiles_total"] = sum(s["recompiles_after_warmup"]
                                  for s in sites.values())

    # deadline: an expired budget retires with reason "deadline" at the
    # next tick boundary, waiting or decoding alike
    engine = ServeEngine(cfg, params, EngineConfig(**base))
    engine.warmup()
    engine.submit(Request(rid=0, prompt=np.array([5, 6, 7], np.int32),
                          max_new_tokens=8, deadline_ms=0.001))
    time.sleep(0.005)
    fin = {}
    for _ in range(20):
        engine.step()
        engine.poll()
        fin = {rs.rid: rs.finish_reason for rs in engine.scheduler.finished}
        if 0 in fin:
            break
    out["deadline"] = {"finish_reason": fin.get(0),
                       "ok": fin.get(0) == "deadline"}
    engine.close()
    print(f"faults/deadline: reason={fin.get(0)!r}", flush=True)

    # watchdog: injected slow steps (after the rolling window arms with
    # clean samples) degrade the engine; in-threshold traffic recovers it
    plan = faults_lib.FaultPlan()
    spec = plan.arm("slow_step", once=False, delay_s=0.2, nth=24)
    engine = ServeEngine(cfg, params,
                         EngineConfig(slots=2, max_seq=128, page_size=16,
                                      faults=plan, watchdog_ticks=2.0,
                                      watchdog_floor_s=0.0,
                                      watchdog_recovery=4, seed=args.seed))
    engine.warmup()
    wd = {"degraded": False, "recovered": False, "ticks": 0}
    rid = 0

    def feed(n=2):
        nonlocal rid
        for _ in range(n):
            engine.submit(Request(
                rid=rid,
                prompt=np.random.default_rng(rid).integers(
                    2, cfg.vocab_size, size=6),
                max_new_tokens=48))
            rid += 1

    feed()
    for _ in range(400):
        if not (engine.scheduler.waiting
                or any(r is not None for r in engine.slot_req)):
            feed()
        engine.step()
        engine.poll()
        wd["ticks"] += 1
        if not wd["degraded"] and engine.health == "degraded":
            wd["degraded"] = True
            spec.once = True        # disarm: fired once-specs are spent
        elif wd["degraded"] and engine.health == "healthy":
            wd["recovered"] = True
            break
    engine.close()
    out["watchdog"] = wd
    print(f"faults/watchdog: degraded={wd['degraded']}, "
          f"recovered={wd['recovered']} after {wd['ticks']} ticks",
          flush=True)

    # seeded chaos run: reproducible random plan over the mixed batch; the
    # engine must retire every request with a structured reason and audit
    # clean; the lifecycle trace is the CI chaos artifact
    plan = faults_lib.FaultPlan.seeded(
        args.seed, rids=tuple(range(args.faults_requests)), n=4)
    engine = ServeEngine(cfg, params, EngineConfig(faults=plan, **base))
    engine.warmup()
    engine.token_sink = sink
    reqs = _faults_requests(cfg, args, shared=shared)
    engine.run(reqs)
    fin = {rs.rid: rs.finish_reason for rs in engine.scheduler.finished}
    rep = engine.audit()
    out["chaos"] = {
        "injected": dict(plan.injected),
        "all_retired": all(r.rid in fin and bool(fin[r.rid])
                           for r in reqs),
        "retired_by_reason": {
            r: sum(1 for v in fin.values() if v == r)
            for r in sorted(set(fin.values()))},
        "leaked_after": rep["leaked_after"],
        "health": engine.health,
    }
    if args.faults_trace_out:
        out["chaos"]["trace_events_written"] = engine.export_trace(
            args.faults_trace_out)
        print(f"faults/chaos: wrote "
              f"{out['chaos']['trace_events_written']} trace events to "
              f"{args.faults_trace_out}", flush=True)
    engine.close()
    print(f"faults/chaos: injected={out['chaos']['injected']}, "
          f"all_retired={out['chaos']['all_retired']}, leaked "
          f"{out['chaos']['leaked_after']}", flush=True)
    print(f"faults: reasons_structured={out['reasons_structured_all']}, "
          f"streams_bit_identical={out['streams_bit_identical_all']}, "
          f"leak_reclaim_ok={out['leak_reclaim_ok']}, recompiles "
          f"{out['recompiles_total']}", flush=True)
    return out


def _recovery_requests(cfg, args):
    """Deterministic mixed workload (even rids greedy, odd rids sampled) —
    rebuilt per call so every run in the section sees identical inputs."""
    rng = np.random.default_rng(args.seed + 31)
    reqs = []
    for i in range(args.recovery_requests):
        reqs.append(Request(
            rid=i,
            prompt=rng.integers(2, cfg.vocab_size,
                                size=int(rng.integers(4, 14))),
            max_new_tokens=args.max_new,
            sampling=SamplingParams(temperature=0.8 if i % 2 else 0.0,
                                    top_k=50 if i % 2 else 0)))
    return reqs


def _journal_client_streams(path):
    """The client-visible stream per rid, straight from the journal bytes:
    token records in file order across every epoch. Duplicated or dropped
    tokens in recovery would show up here — nowhere to hide."""
    streams = {}
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                continue                     # torn tail
            if rec["kind"] == "submit":
                streams[rec["rid"]] = []     # rid reuse opens fresh
            elif rec["kind"] == "token":
                streams[rec["rid"]].append(rec["tok"])
    return streams


def bench_recovery(cfg, params, args):
    """Durability: crash-at-tick-N journal recovery, snapshot/restore, and
    live handoff, every gate exact.

    The contracts this section gates: after a process kill at each sampled
    tick index, journal replay + ``ServeEngine.recover`` resumes every
    in-flight request so the concatenated client-visible streams (read back
    from the journal itself) are bit-identical to an uninterrupted run —
    greedy and sampled, zero duplicated and zero dropped tokens; replay is
    idempotent across the multi-epoch file; the recovered engine compiles
    nothing after warmup (static-shape invariant holds through recovery);
    a snapshot()/restore() round trip finishes mid-flight streams
    bit-identically; and a live handoff (same config, and to a different
    kv_bits config) finishes every transferred request.

    ``--recovery-journal-out`` / ``--recovery-snapshot-dir`` keep the last
    crash's journal and the mid-flight snapshot as CI artifacts.
    """
    import shutil
    import tempfile

    from repro.serve import faults as faults_lib
    from repro.serve import journal as journal_lib

    base = dict(slots=max(2, args.slots // 2), max_seq=128,
                seed=args.seed)
    workdir = tempfile.mkdtemp(prefix="recovery_bench_")

    def drive(engine, reqs):
        for r in reqs:
            engine.submit(r)
        try:
            while (engine.scheduler.waiting
                   or any(s is not None for s in engine.slot_req)):
                engine.step()
                engine.poll()
        except faults_lib.ProcessCrash:
            return False
        engine.poll()
        return True

    # --- reference: the uninterrupted ground truth -----------------------
    ref_eng = ServeEngine(cfg, params, EngineConfig(**base))
    ref_eng.warmup()
    drive(ref_eng, _recovery_requests(cfg, args))
    ref = {rs.rid: list(rs.out_tokens) for rs in ref_eng.scheduler.finished}
    ref_ticks = ref_eng.stats["ticks"]
    ref_eng.close()

    ks = sorted(set(
        max(1, round(ref_ticks * (i + 1) / (args.recovery_crash_ticks + 1)))
        for i in range(args.recovery_crash_ticks)))
    out = {"requests": args.recovery_requests, "reference_ticks": ref_ticks,
           "crash_ticks": ks, "crashes": {}}
    dup_total = drop_total = rec_recompiles = 0
    greedy_ok = sampled_ok = replay_ok = True
    last_journal = None

    for k in ks:
        jpath = f"{workdir}/crash_{k}.journal"
        plan = faults_lib.FaultPlan()
        plan.arm("process_crash", tick=k)
        eng = ServeEngine(cfg, params, EngineConfig(
            journal=journal_lib.RequestJournal(jpath), faults=plan,
            **base))
        eng._owns_journal = True
        finished_clean = drive(eng, _recovery_requests(cfg, args))
        if finished_clean:                    # k past the end: no kill
            eng.close()
            continue
        state = journal_lib.replay(jpath)
        del eng                               # simulated death: no close()

        eng2 = ServeEngine.recover(cfg, params, jpath,
                                   ecfg=EngineConfig(**base))
        warm = eng2.warmup()
        drive(eng2, [])
        recompiles = eng2.compile_count() - warm
        eng2.close()
        rec_recompiles += recompiles

        final = journal_lib.replay(jpath)
        idem = final == journal_lib.replay(jpath) and not final.live
        replay_ok &= idem
        streams = _journal_client_streams(jpath)
        dup = drop = 0
        identical = True
        for rid, want in ref.items():
            got = streams.get(rid, [])
            if got != want:
                identical = False
                dup += max(0, len(got) - len(want))
                drop += max(0, len(want) - len(got))
                if rid % 2:
                    sampled_ok = False
                else:
                    greedy_ok = False
        dup_total += dup
        drop_total += drop
        out["crashes"][str(k)] = {
            "live_at_kill": len(state.live),
            "epochs": final.epochs,
            "bit_identical": identical,
            "duplicated_tokens": dup,
            "dropped_tokens": drop,
            "replay_idempotent": idem,
            "recovered_recompiles": recompiles,
        }
        last_journal = jpath
        print(f"recovery: kill@tick {k}: live={len(state.live)}, "
              f"bit_identical={identical}, recompiles={recompiles}",
              flush=True)

    # --- snapshot round trip ---------------------------------------------
    snapdir = args.recovery_snapshot_dir or f"{workdir}/snapshot"
    eng = ServeEngine(cfg, params, EngineConfig(**base))
    reqs = _recovery_requests(cfg, args)
    for r in reqs:
        eng.submit(r)
    for _ in range(max(1, ref_ticks // 2)):
        eng.step()
    eng.poll()
    path = eng.snapshot(snapdir)
    manifest = json.load(open(f"{path}/MANIFEST.json"))
    pre = {rs.rid: list(rs.out_tokens) for rs in eng.scheduler.finished}
    eng.close()
    eng3 = ServeEngine.restore(cfg, params, snapdir)
    restored_n = len(eng3._requests)
    drive(eng3, [])
    post = {rs.rid: list(rs.out_tokens) for rs in eng3.scheduler.finished}
    eng3.close()
    snap_streams = dict(pre)
    snap_streams.update(post)
    snap_ok = snap_streams == ref
    out["snapshot"] = {
        "restored_requests": restored_n,
        "manifest_kind_ok": manifest["extra"]["kind"] == "serve_snapshot",
        "roundtrip_bit_identical": snap_ok,
    }
    print(f"recovery: snapshot roundtrip restored={restored_n}, "
          f"bit_identical={snap_ok}", flush=True)

    # --- live handoff: same config, then a reconfiguring target ----------
    hand = {}
    for label, tgt_over in (("same_config", {}),
                            ("diff_config", {"kv_bits": 8})):
        src = ServeEngine(cfg, params, EngineConfig(**base))
        reqs = _recovery_requests(cfg, args)
        for r in reqs:
            src.submit(r)
        for _ in range(max(1, ref_ticks // 2)):
            src.step()
        src.poll()
        pre = {rs.rid: list(rs.out_tokens)
               for rs in src.scheduler.finished}
        live = set(src._requests.keys())
        tgt = ServeEngine(cfg, params, EngineConfig(**{**base, **tgt_over}))
        summary = src.handoff(tgt)
        drive(tgt, [])
        post = {rs.rid: list(rs.out_tokens)
                for rs in tgt.scheduler.finished}
        failed = len(live - set(post.keys()))
        full = dict(pre)
        full.update(post)
        hand[label] = {
            "transferred": summary["transferred"],
            "failed_in_flight": failed,
            "streams_bit_identical": full == ref,
        }
        src.close()
        tgt.close()
        print(f"recovery: handoff {label}: "
              f"transferred={summary['transferred']}, failed={failed}, "
              f"bit_identical={full == ref}", flush=True)
    out["handoff"] = {
        "transferred": hand["same_config"]["transferred"],
        "failed_in_flight": hand["same_config"]["failed_in_flight"],
        "streams_bit_identical": hand["same_config"]
                                     ["streams_bit_identical"],
        "diff_config_failed_in_flight": hand["diff_config"]
                                            ["failed_in_flight"],
    }

    out["streams_bit_identical_greedy"] = greedy_ok
    out["streams_bit_identical_sampled"] = sampled_ok
    out["duplicated_tokens_total"] = dup_total
    out["dropped_tokens_total"] = drop_total
    out["replay_idempotent_all"] = replay_ok
    out["recovered_recompiles_total"] = rec_recompiles

    if args.recovery_journal_out and last_journal is not None:
        shutil.copyfile(last_journal, args.recovery_journal_out)
        print(f"wrote {args.recovery_journal_out}")
    print(f"recovery: greedy_identical={greedy_ok}, "
          f"sampled_identical={sampled_ok}, dup={dup_total}, "
          f"dropped={drop_total}, replay_idempotent={replay_ok}, "
          f"recompiles={rec_recompiles}", flush=True)
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-3b")
    ap.add_argument("--published", action="store_true",
                    help="the published config (default: its smoke variant)")
    ap.add_argument("--layers", type=int, default=None,
                    help="cut the depth to the first N layers")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--interarrival", type=float, default=2.0,
                    help="mean request inter-arrival time in decode ticks")
    ap.add_argument("--scaling-max-seq", type=int, default=2048,
                    help="slot capacity for the decode_scaling section")
    ap.add_argument("--scaling-requests", type=int, default=48)
    ap.add_argument("--scaling-reps", type=int, default=3,
                    help="repetitions per decode_scaling variant (median)")
    ap.add_argument("--prefix-requests", type=int, default=32,
                    help="requests in the shared-prefix (prefix_caching) "
                         "section")
    ap.add_argument("--prefix-len", type=int, default=96,
                    help="shared system-prompt length for prefix_caching")
    ap.add_argument("--prefix-reps", type=int, default=3,
                    help="repetitions per prefix_caching variant (median)")
    ap.add_argument("--kv-requests", type=int, default=16,
                    help="requests in the quantized-KV (kv_quant) section")
    ap.add_argument("--kv-reps", type=int, default=3,
                    help="repetitions per kv_quant variant (median)")
    ap.add_argument("--wq-requests", type=int, default=16,
                    help="requests in the quantized-weight (weight_quant) "
                         "section")
    ap.add_argument("--wq-reps", type=int, default=3,
                    help="repetitions per weight_quant variant (median)")
    ap.add_argument("--telemetry-requests", type=int, default=24,
                    help="requests in the telemetry-overhead section")
    ap.add_argument("--telemetry-reps", type=int, default=3,
                    help="repetitions per telemetry variant (median)")
    ap.add_argument("--overload-requests", type=int, default=36,
                    help="requests per rate in the overload section")
    ap.add_argument("--overload-blocks", type=int, default=10,
                    help="KV pool size (blocks) for the overload section; "
                         "deliberately tight so big prompts block the head")
    ap.add_argument("--trace-out", default=None,
                    help="write the telemetry section's lifecycle-trace "
                         "JSONL here (the CI artifact)")
    ap.add_argument("--faults-requests", type=int, default=4,
                    help="requests in the fault-containment batch")
    ap.add_argument("--faults-trace-out", default=None,
                    help="write the chaos run's lifecycle-trace JSONL here "
                         "(the CI chaos artifact)")
    ap.add_argument("--recovery-requests", type=int, default=6,
                    help="requests in the durability (recovery) section")
    ap.add_argument("--recovery-crash-ticks", type=int, default=4,
                    help="number of kill points sampled across the "
                         "reference run's tick range")
    ap.add_argument("--recovery-journal-out", default=None,
                    help="keep the last crash's multi-epoch journal here "
                         "(the CI durability artifact)")
    ap.add_argument("--recovery-snapshot-dir", default=None,
                    help="write the mid-flight engine snapshot here "
                         "(the CI durability artifact)")
    ap.add_argument("--sections", default="all",
                    help="comma list of sections to run: runs,decode_scaling,"
                         "prefix,kv_quant,weight_quant,telemetry,overload,"
                         "faults,recovery (default all)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke sizes: fewer requests, smaller capacity")
    ap.add_argument("--mesh", default=None,
                    help="also benchmark sharded serving on a 'M' or 'DxM' "
                         "mesh (forces host devices on CPU) vs 1 device")
    ap.add_argument("--out", default="BENCH_serving.json",
                    help="write the JSON report here")
    args = ap.parse_args()
    if args.quick:
        # shrink the float/grau trace, NOT the scaling section's slot
        # capacity or tick count: the decode_scaling ratio only separates
        # cleanly from scheduler noise with a long trace at large
        # blocks_per_slot
        args.requests = 6
        args.scaling_requests = 32
        args.kv_requests = 12
        args.kv_reps = 2
        args.wq_requests = 12
        args.wq_reps = 2
        args.overload_requests = 24
        args.recovery_requests = 4
        args.recovery_crash_ticks = 2
    for name in ("requests", "scaling_requests", "scaling_reps",
                 "prefix_requests", "prefix_reps", "kv_requests", "kv_reps",
                 "wq_requests", "wq_reps",
                 "telemetry_requests", "telemetry_reps",
                 "overload_requests", "overload_blocks", "faults_requests",
                 "recovery_requests", "recovery_crash_ticks"):
        if getattr(args, name) < 1:
            ap.error(f"--{name.replace('_', '-')} must be >= 1")
    if args.faults_requests < 2:
        ap.error("--faults-requests must be >= 2 (the fault matrix targets "
                 "rid 1)")
    sections = (("runs", "decode_scaling", "prefix", "kv_quant",
                 "weight_quant", "telemetry", "overload", "faults",
                 "recovery")
                if args.sections == "all"
                else tuple(s.strip() for s in args.sections.split(",") if s))

    mesh_shape = parse_mesh_spec(args.mesh) if args.mesh else None
    if mesh_shape:
        ensure_host_devices(mesh_shape[0] * mesh_shape[1])
    use_repo_dirs()

    base_cfg = model_config(args.arch, args.published, args.layers)
    report = {
        "arch": base_cfg.name,
        "slots": args.slots,
        "requests": args.requests,
        "mean_interarrival_ticks": args.interarrival,
        "runs": {},
    }
    trace = synth_trace(args.requests, args.interarrival,
                        base_cfg.vocab_size, args.max_new, args.seed)
    samplers = {
        "greedy": SamplingParams(),
        "sampled": SamplingParams(temperature=0.8, top_k=50, top_p=0.95),
    }

    if "runs" in sections:
        for act_name, cfg in (("float", base_cfg),
                              ("grau", base_cfg.replace(grau=GRAUConfig()))):
            params = init_params(cfg, jax.random.PRNGKey(0))
            for samp_name, sampling in samplers.items():
                engine = ServeEngine(
                    cfg, params,
                    EngineConfig(slots=args.slots, max_seq=args.max_seq,
                                 seed=args.seed))
                warm_compiles = engine.warmup()

                stats = run_trace(engine, trace, sampling)
                stats["recompiles_after_warmup"] = (engine.compile_count()
                                                    - warm_compiles)
                report["runs"][f"{act_name}/{samp_name}"] = stats
                print(f"{act_name}/{samp_name}: "
                      f"{stats['tokens_per_s']:.1f} tok/s, "
                      f"TTFT p50 {stats['ttft_p50_s'] * 1e3:.1f} ms, "
                      f"p99 {stats['ttft_p99_s'] * 1e3:.1f} ms "
                      f"[{stats['backend']}, "
                      f"{stats['recompiles_after_warmup']} recompiles]",
                      flush=True)

    params = init_params(base_cfg, jax.random.PRNGKey(0))
    if "decode_scaling" in sections:
        report["decode_scaling"] = bench_decode_scaling(base_cfg, params,
                                                        args)
    if "prefix" in sections:
        report["prefix_caching"] = bench_prefix_caching(base_cfg, params,
                                                        args)
    if "kv_quant" in sections:
        report["kv_quant"] = bench_kv_quant(base_cfg, params, args)
    if "weight_quant" in sections:
        report["weight_quant"] = bench_weight_quant(base_cfg, params, args)
    if "telemetry" in sections:
        report["telemetry"] = bench_telemetry(base_cfg, params, args)
    if "overload" in sections:
        report["overload"] = bench_overload(base_cfg, params, args)
    if "faults" in sections:
        report["faults"] = bench_faults(base_cfg, params, args)
    if "recovery" in sections:
        report["recovery"] = bench_recovery(base_cfg, params, args)

    if mesh_shape:
        # sharded vs single-device: same float/greedy trace, so the delta is
        # purely the mesh (on forced host CPU devices expect overhead, not
        # speedup — the point is the apples-to-apples wiring and the report
        # format, which carries over unchanged to real accelerators)
        from repro.launch.mesh import make_serve_mesh
        report["mesh_comparison"] = {}
        meshes = {"1 device": None,
                  f"{mesh_shape[0]}x{mesh_shape[1]} mesh":
                      make_serve_mesh(*mesh_shape)}
        for label, mesh in meshes.items():
            engine = ServeEngine(
                base_cfg, params,
                EngineConfig(slots=args.slots, max_seq=args.max_seq,
                             seed=args.seed),
                mesh=mesh)
            warm_compiles = engine.warmup()
            stats = run_trace(engine, trace, SamplingParams())
            stats["recompiles_after_warmup"] = (engine.compile_count()
                                                - warm_compiles)
            report["mesh_comparison"][label] = stats
            print(f"mesh {label}: {stats['tokens_per_s']:.1f} tok/s, "
                  f"TTFT p50 {stats['ttft_p50_s'] * 1e3:.1f} ms "
                  f"[{stats['recompiles_after_warmup']} recompiles]",
                  flush=True)

    payload = json.dumps(report, indent=2)
    if args.out:
        with open(args.out, "w") as f:
            f.write(payload)
        print(f"wrote {args.out}")
    else:
        print(payload)


if __name__ == "__main__":
    main()
