"""Serving launcher: continuous-batching decode over a slot pool, with the
paged KV cache on pageable archs, optional mesh sharding, and
scheduler/engine metrics reporting.

Example:
  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-3b --requests 6
  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-3b \
      --temperature 0.8 --top-p 0.9 --policy prefill
  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-3b --mesh 1x4
    (on CPU, forces 4 host devices automatically; see docs/sharding.md)
  PYTHONPATH=src python -m repro.launch.serve --arch glm4-9b --published \
      --layers 4 --max-seq 1024
    (on a TPU: published widths, depth cut to the first 4 of 40 layers —
     one pipeline stage's share; random weights from a fixed seed)
  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-3b \
      --prefix-cache --shared-prefix 48 --prefill-chunk 32
    (radix-tree shared-prefix KV reuse + chunked prefill; --shared-prefix
     makes the demo requests share a synthetic system prompt so the cache
     has something to hit)
  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-3b \
      --journal /tmp/serve.journal --snapshot-dir /tmp/serve-snap
    (durable serving: write-ahead request journal + final snapshot;
     SIGINT/SIGTERM drain in-flight streams and snapshot instead of dying
     mid-tick; add --resume to recover the journaled requests after a
     crash — see docs/serving.md, Durability and recovery)
"""
from __future__ import annotations

import argparse
import json
import signal
import sys
from typing import Callable, List, Optional

import numpy as np


def serve_requests(engine, requests, stop: Optional[Callable[[], int]] = None
                   ) -> List:
    """Submit `requests` and tick `engine` until every one has retired;
    returns them in completion order.  The launcher's serving loop —
    chip_smoke.py drives the same one.  `stop()` (optional) says how many
    shutdown signals have arrived: the first drains in-flight streams
    (queued requests stay queued for a snapshot), the second stops now."""
    for req in requests:
        engine.submit(req)
    done = []
    draining = False
    ticks = 0
    while (engine.scheduler.waiting
           or any(r is not None for r in engine.slot_req)):
        signals = stop() if stop is not None else 0
        if signals and not draining:
            engine.begin_draining("signal")
            draining = True
            print("draining: finishing in-flight streams "
                  "(signal again to stop now)")
        if signals > 1:
            break
        if draining and all(r is None for r in engine.slot_req):
            break       # in-flight done; queued wait in the snapshot
        made_progress = (engine.step() > 0
                         or not engine.scheduler.waiting)
        done.extend(engine.poll())
        ticks += 1
        if ticks >= 100000:
            break
        if not made_progress and not any(r is not None
                                         for r in engine.slot_req):
            break       # queue head can never admit — bail, don't spin
    done.extend(engine.poll())
    return done


def serving_failures(engine) -> List[str]:
    """What makes a serving run fail: requests that fault containment
    retired as internal_error (with the exceptions the engine kept), and an
    engine left degraded.  Empty when the run is sound."""
    from repro.serve.engine import DEGRADED
    out = []
    bad = [rs.rid for rs in engine.scheduler.finished
           if rs.finish_reason == "internal_error"]
    if bad:
        errors = sorted({e["error"] for e in engine.errors})
        out.append(f"{len(bad)} request(s) retired as internal_error "
                   f"(rids {bad[:8]}): {errors}")
    if engine.health == DEGRADED:
        out.append(f"engine degraded: {engine.health_reason}")
    return out


def model_config(arch: str, published: bool, layers: Optional[int]):
    """The config `--published` / `--layers` select, announced with its
    widths and depth cut (the launcher and the serving bench share it)."""
    from repro.configs.archs import get_config
    cfg = get_config(arch, smoke=not published, layers=layers)
    full = get_config(arch, smoke=not published)
    print(f"model: {cfg.name} ({'published' if published else 'smoke'}"
          f" widths: d_model={cfg.d_model}, heads={cfg.num_heads}/"
          f"{cfg.num_kv_heads} kv, d_ff={cfg.d_ff}, vocab={cfg.vocab_size});"
          f" layers {cfg.num_layers} of {full.num_layers}", flush=True)
    return cfg


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-3b")
    ap.add_argument("--published", action="store_true",
                    help="serve the published config (default: its smoke "
                         "variant, toy widths for the CPU)")
    ap.add_argument("--layers", type=int, default=None,
                    help="cut the depth to the first N layers (widths stay "
                         "whole): one pipeline stage's share of the model")
    ap.add_argument("--mesh", default=None,
                    help="serve sharded: 'M' (tensor-parallel) or 'DxM'")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-seq", type=int, default=256)
    ap.add_argument("--backend", choices=["auto", "paged", "dense"],
                    default="auto")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--kv-bits", type=int, choices=[16, 8, 4], default=16,
                    help="KV-cache precision: 16 = float pools, 8/4 = packed "
                         "int pools with per-block power-of-two scale "
                         "exponents (paged backend only)")
    ap.add_argument("--weight-bits", type=int, choices=[16, 8, 4], default=16,
                    help="serving-weight precision: 16 = raw f32 params, "
                         "8/4 = matmul weights packed once at startup into "
                         "power-of-two-scaled int planes (quant/weights.py); "
                         "composes with --kv-bits")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="radix-tree shared-prefix KV reuse (paged only)")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="chunked-prefill grid step (page-size multiple; "
                         "default auto)")
    ap.add_argument("--prefill-budget", type=int, default=None,
                    help="max prefill tokens per decode tick "
                         "(default: one chunk)")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="prepend a shared synthetic system prompt of this "
                         "many tokens to every request (prefix-cache demo)")
    ap.add_argument("--policy", choices=["fcfs", "prefill"], default="fcfs")
    ap.add_argument("--num-blocks", type=int, default=None,
                    help="KV pool size in blocks (default: no "
                         "oversubscription); small pools exercise "
                         "preemption under load")
    ap.add_argument("--no-preemption", action="store_true",
                    help="disable KV-pressure preemption (a blocked request "
                         "then waits for natural retirements)")
    ap.add_argument("--preempt-after-ticks", type=int, default=8,
                    help="ticks a blocked queue head must wait before it "
                         "may evict later-arrival decode slots")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request wall-clock budget: a request past it "
                         "retires with reason 'deadline' at the next tick "
                         "boundary, keeping tokens generated before expiry "
                         "(docs/serving.md, Failure handling)")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--top-p", type=float, default=1.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--no-telemetry", action="store_true",
                    help="disable the metrics registry + lifecycle traces "
                         "(telemetry is on by default; overhead is gated "
                         "<= 5%% by benchmarks/serving_bench.py)")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="serve Prometheus /metrics (and /metrics.json) on "
                         "this port while running; 0 picks an ephemeral port")
    ap.add_argument("--trace-out", default=None,
                    help="write per-request lifecycle traces as JSONL here "
                         "on exit (schema: docs/observability.md)")
    ap.add_argument("--journal", default=None,
                    help="write-ahead request journal (append-only JSONL "
                         "of submits / delivered tokens / retires); after "
                         "a crash, --resume replays it and finishes every "
                         "in-flight request bit-exactly")
    ap.add_argument("--snapshot-dir", default=None,
                    help="write a final engine snapshot (config + live "
                         "request records, ckpt manifest format) here on "
                         "shutdown — including signal-driven shutdown")
    ap.add_argument("--resume", action="store_true",
                    help="recover from --journal instead of submitting "
                         "synthetic requests (engine flags must match the "
                         "original run — in particular --seed)")
    ap.add_argument("--audit-interval", type=int, default=None,
                    help="run the engine invariant audit automatically "
                         "every N ticks (default: on demand only)")
    args = ap.parse_args()
    if args.resume and not args.journal:
        ap.error("--resume requires --journal")

    from repro.launch.mesh import ensure_host_devices, parse_mesh_spec
    from repro.launch.runtime import use_repo_dirs
    mesh_shape = parse_mesh_spec(args.mesh) if args.mesh else None
    if mesh_shape:
        ensure_host_devices(mesh_shape[0] * mesh_shape[1])
    use_repo_dirs()

    import jax

    from repro.launch.mesh import make_serve_mesh
    from repro.serve.engine import EngineConfig, Request, ServeEngine
    from repro.serve.sampling import SamplingParams
    from repro.serve.sharding import init_params

    mesh = make_serve_mesh(*mesh_shape) if mesh_shape else None
    cfg = model_config(args.arch, args.published, args.layers)
    # built straight into the serving placement: under a mesh no device
    # ever holds the whole tree
    params = init_params(cfg, jax.random.PRNGKey(0), mesh=mesh)
    paged = None if args.backend == "auto" else (args.backend == "paged")
    telemetry = not args.no_telemetry
    if args.metrics_port is not None and not telemetry:
        ap.error("--metrics-port requires telemetry (drop --no-telemetry)")
    if args.trace_out is not None and not telemetry:
        ap.error("--trace-out requires telemetry (drop --no-telemetry)")
    ecfg = EngineConfig(slots=args.slots, max_seq=args.max_seq, paged=paged,
                        page_size=args.page_size, policy=args.policy,
                        num_blocks=args.num_blocks,
                        kv_bits=args.kv_bits if args.kv_bits != 16 else None,
                        weight_bits=(args.weight_bits
                                     if args.weight_bits != 16 else None),
                        prefix_cache=args.prefix_cache,
                        prefill_chunk=args.prefill_chunk,
                        prefill_token_budget=args.prefill_budget,
                        preemption=not args.no_preemption,
                        preempt_after_ticks=args.preempt_after_ticks,
                        telemetry=telemetry,
                        audit_interval=args.audit_interval,
                        seed=args.seed)
    if args.resume:
        # crash recovery: replay the journal and resume every request that
        # was live at the kill with exactly its undelivered suffix
        engine = ServeEngine.recover(cfg, params, args.journal, ecfg=ecfg,
                                     mesh=mesh)
        print(f"resumed {len(engine.scheduler.waiting)} live requests "
              f"from {args.journal}")
    else:
        if args.journal:
            import dataclasses

            from repro.serve.journal import RequestJournal
            ecfg = dataclasses.replace(ecfg,
                                       journal=RequestJournal(args.journal))
        engine = ServeEngine(cfg, params, ecfg, mesh=mesh)
        if args.journal:
            engine._owns_journal = True   # launcher hands over the writer

    if args.metrics_port is not None:
        # engine-owned endpoint: engine.close() (the finally below) stops
        # the socket and joins the serving thread, so the launcher cannot
        # leak the listener however it exits
        server = engine.serve_metrics(args.metrics_port)
        print(f"metrics: http://{server.server_address[0]}:"
              f"{server.server_address[1]}/metrics")

    if engine.paged:
        # startup memory table: the paper's LUT-cost table's memory sibling —
        # KV bytes/slot and decode gather bytes/step from one cost model
        # (core/hwcost.kv_cache_cost), at the serving precision and its
        # neighbors so the --kv-bits tradeoff is visible before traffic hits
        from repro.core.hwcost import kv_cache_cost
        num_layers = sum(len(period) * repeats
                         for period, repeats in cfg.groups)
        print(f"kv cache @ page_size={args.page_size}, "
              f"max_seq={args.max_seq}, slots={args.slots}:")
        for bits in (16, 8, 4):
            r = kv_cache_cost(num_layers=num_layers,
                              kv_heads=cfg.kv_heads_phys,
                              head_dim=cfg.head_dim,
                              block_size=args.page_size, kv_bits=bits,
                              slots=args.slots, max_seq=args.max_seq)
            mark = " <- serving" if bits == args.kv_bits else ""
            print(f"  kv_bits={bits:2d}: {r.bytes_per_slot / 1e6:8.3f} MB/slot, "
                  f"pool {r.pool_bytes / 1e6:8.3f} MB, "
                  f"gather {r.gather_bytes_per_step / 1e3:8.1f} KB/step"
                  f"{mark}")

    # startup weight table: the other half of the serving memory budget —
    # packable matmul bytes at each --weight-bits setting from the same
    # analytic cost model family (core/hwcost.weight_cost). Decode streams
    # every weight per token, so total bytes IS the model-bytes/step term.
    from repro.core.hwcost import weight_cost
    wq_layers = sum(sum(1 for spec in period
                        if spec.kind == "attn" and spec.mlp == "dense")
                    * repeats for period, repeats in cfg.groups)
    print(f"serving weights (attn+dense-mlp layers={wq_layers}):")
    for bits in (16, 8, 4):
        w = weight_cost(num_layers=wq_layers, d_model=cfg.d_model,
                        num_heads=cfg.num_heads, kv_heads=cfg.kv_heads_phys,
                        head_dim=cfg.head_dim, d_ff=cfg.d_ff,
                        gated=cfg.gated_mlp, vocab_size=cfg.vocab_size,
                        tied=cfg.tie_embeddings, weight_bits=bits)
        mark = " <- serving" if bits == args.weight_bits else ""
        print(f"  weight_bits={bits:2d}: {w.total_bytes / 1e6:8.3f} MB total "
              f"(layers {w.layer_bytes / 1e6:8.3f} MB, "
              f"embed {w.embed_bytes / 1e6:8.3f} MB, "
              f"scales {w.scale_bytes / 1e3:7.1f} KB)"
              f"{mark}")

    sampling = SamplingParams(temperature=args.temperature,
                              top_k=args.top_k, top_p=args.top_p)
    rng = np.random.default_rng(args.seed)
    enc = (np.zeros((cfg.encoder.num_frames, cfg.d_model), np.float32)
           if cfg.encoder is not None else None)
    shared = (rng.integers(2, cfg.vocab_size, size=args.shared_prefix)
              if args.shared_prefix else np.zeros(0, np.int64))
    reqs = ([] if args.resume else
            [Request(rid=i,
                     prompt=np.concatenate(
                         [shared,
                          rng.integers(2, cfg.vocab_size,
                                       size=int(rng.integers(4, 12)))]),
                     max_new_tokens=args.max_new, sampling=sampling,
                     encoder_frames=enc, deadline_ms=args.deadline_ms)
             for i in range(args.requests)])

    # graceful shutdown: the first SIGINT/SIGTERM transitions the engine to
    # DRAINING (in-flight streams finish; queued requests stay put for the
    # final snapshot/journal), the second breaks out of the serve loop
    # immediately. Either way the engine snapshots and closes instead of
    # dying mid-tick.
    signals = {"count": 0}

    def _on_signal(signum, frame):
        signals["count"] += 1

    for sig in (signal.SIGINT, signal.SIGTERM):
        signal.signal(sig, _on_signal)

    try:
        done = serve_requests(engine, reqs, stop=lambda: signals["count"])
        for r in done:
            print(f"req {r.rid}: prompt={len(r.prompt)} toks -> "
                  f"generated {len(r.out_tokens or [])}: "
                  f"{(r.out_tokens or [])[:8]}...")
        m = engine.metrics()
        print(f"prefix cache: hit_rate={m['prefix_hit_rate']:.2f} "
              f"cached_prefix_tokens={m['cached_prefix_tokens']} "
              f"evictions={m['evictions']}")
        print(f"preemption: preempted={m['preempted']} "
              f"hol_skips={m['hol_skips']}")
        print(json.dumps(m, indent=2, default=str))
        if args.trace_out:
            n = engine.export_trace(args.trace_out)
            print(f"wrote {n} trace events to {args.trace_out}")
        if args.snapshot_dir:
            path = engine.snapshot(args.snapshot_dir)
            live = len(engine.scheduler.waiting)
            print(f"snapshot: {path} ({live} undelivered requests "
                  "captured)")
        failures = serving_failures(engine)
    finally:
        engine.close()
    for f in failures:
        print(f"FAILED: {f}", file=sys.stderr)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
