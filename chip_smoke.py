"""Chip smoke run: the serving engine's normal path on a TPU, at published
widths, checked end to end.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # four chips: the sharded phase only

Model: glm4-9b at its published widths (d_model 4096, 32 query heads and
2 KV heads of 128, d_ff 13696, vocab 151552) with random weights from a
fixed seed.  One chip serves the first 4 of its 40 layers: one stage of a
10-stage pipeline deployment (about 8.3 GB of float32 parameters).

One chip, in order (each phase prints one line; the first failure exits 1):
  1. device   platform, kind and count; anything but a TPU fails
  2. kernels  the GRAU unit and the fused GEMM + GRAU kernel at
              64x4096x13696 bit-exact against the integer oracles of
              kernels/ref.py; the paged decode kernel against the gather
              path at float and 4-bit KV, per slot, and the same kernel
              given one token too few, which the check must catch
  3. serve    8 greedy requests (prompts of 64-512 tokens, 32 new tokens
              each) through launch/serve.serve_requests on the float
              engine: every request gets all its tokens, no request is
              retired as internal_error, the engine stays healthy, nothing
              compiles after warmup(), and the paged kernel is what runs
  4. serve    the same traffic on the shift-based datapath (int4 weights
              through matmul_wq, int4 KV pools), with the same checks
--four-chips instead runs glm4-9b at all 40 layers on a (1, 4) mesh, with
parameters built straight into their shards, after comparing prefill
logits of the 4-layer cut on chip 0 alone against the same cut on the mesh.

TTFT, tokens/s and peak memory are printed as information, not claims.
The last line of a passing run is one JSON object:
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
Everything runs in this one process: a chip belongs to one process.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))
from repro.launch.runtime import use_repo_dirs  # noqa: E402

ARCH = "glm4-9b"
STAGE_LAYERS = 4          # one of 10 pipeline stages of the 40-layer model
GRAU_DIMS = (64, 4096, 13696)
# Paged kernel vs gather path, per slot: max |diff| relative to the slot's
# largest |output|.  The chip's default float32 matmul precision rounds
# operands to bfloat16 (2^-9 relative) in the gather path's einsums and in
# the kernel's dots alike; on a v5e the two differ by at most 0.29% of a
# slot's largest output.  An off-by-one mask (one token short, planted in
# every run below) moves each slot by at least 1.0% (int4 KV, the
# 1024-token slot) and by up to 83% at 17 tokens.  0.5% separates the two;
# the limit is relative because a long slot's output, a softmax mean over
# hundreds of N(0, 1) values, is ~5x smaller than a short slot's.
KERNEL_VS_GATHER_TOL = 5e-3
# Prefill logits of the same weights on one chip and on a (1, 4) mesh:
# both use the default precision, and only the order in which the shards'
# partial sums are added differs — 1e-2 of the largest logit is far above
# that and far below any placement error.
MESH_VS_CHIP_TOL = 1e-2


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------

def phase_device(cache_dir: str, need: int):
    import jax
    devs = jax.devices()
    d = devs[0]
    print(f"device: platform={d.platform} kind={d.device_kind} "
          f"count={len(devs)} compile_cache={cache_dir}", flush=True)
    check(d.platform == "tpu", f"no TPU: JAX runs on {d.platform!r}")
    check(len(devs) >= need, f"needs {need} chips, found {len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def _paged_case(rng, kv_bits: int):
    """One decode batch at glm4-9b widths: 4 slots over a 1024-block pool
    (block 16), fragmented tables, ragged lengths."""
    import jax.numpy as jnp
    slots, h, kvh, d, bs, nb, nblk = 4, 32, 2, 128, 16, 1024, 64
    lengths = np.array([1024, 513, 64, 17], np.int32)
    table = np.zeros((slots, nblk), np.int32)
    free = rng.permutation(np.arange(1, nb))
    for s, n in enumerate(lengths):
        k = -(-int(n) // bs)
        table[s, :k], free = free[:k], free[k:]
    q = jnp.asarray(rng.normal(size=(slots, h, d)), jnp.float32)
    if kv_bits == 16:
        pools = [jnp.asarray(rng.normal(size=(nb, kvh, bs, d)), jnp.float32)
                 for _ in range(2)]
        exps = [None, None]
    else:   # any byte is a valid pair of int4 nibbles
        pools = [jnp.asarray(rng.integers(-128, 128, (nb, kvh, bs, d // 2)),
                             jnp.int8) for _ in range(2)]
        exps = [jnp.asarray(rng.integers(-5, -2, (nb, kvh)), jnp.int8)
                for _ in range(2)]
    return q, pools, exps, jnp.asarray(table), jnp.asarray(lengths)


def _fmt(errs) -> str:
    return "[" + ", ".join(f"{x:.3e}" for x in errs) + "]"


def _slot_errors(got, want) -> np.ndarray:
    """Per slot: max |got - want| over heads and dims, relative to the
    slot's largest |want|."""
    got, want = np.asarray(got), np.asarray(want)
    axes = tuple(range(1, want.ndim))
    return (np.max(np.abs(got - want), axis=axes)
            / np.max(np.abs(want), axis=axes))


def phase_kernels(rng) -> None:
    import jax.numpy as jnp
    from repro.core.build import build_grau
    from repro.core.folding import fold
    from repro.kernels import ops, ref
    from repro.kernels.paged_attention import paged_attention

    spec = build_grau(fold("silu", s_in=2**-10, s_out=2**-4, out_bits=8),
                      mac_range=(-30000, 30000), segments=6,
                      num_exponents=8, mode="apot", bias_mode="lsq").spec
    m, k, n = GRAU_DIMS
    mac = jnp.asarray(rng.integers(-70000, 70000, (m, n)), jnp.int32)
    grau_exact = bool(np.array_equal(np.asarray(ops.grau(mac, spec)),
                                     np.asarray(ref.grau_ref(mac, spec))))
    x = jnp.asarray(rng.integers(-16, 17, (m, k)), jnp.int8)
    w = jnp.asarray(rng.integers(-16, 17, (k, n)), jnp.int8)
    mm_exact = bool(np.array_equal(
        np.asarray(ops.matmul_grau(x, w, spec)),
        np.asarray(ref.matmul_grau_ref(x, w, spec))))
    errs, planted = {}, {}
    for bits in (16, 4):
        q, (kp, vp), (ke, ve), bt, ln = _paged_case(rng, bits)

        def kernel(lengths):
            return paged_attention(q, kp, vp, bt, lengths, k_exp=ke,
                                   v_exp=ve, kv_bits=bits)

        want = ref.paged_attention_ref(q, kp, vp, bt, ln, k_exp=ke,
                                       v_exp=ve, kv_bits=bits)
        errs[bits] = _slot_errors(kernel(ln), want)
        # the planted fault: the kernel reads one token short in every slot
        planted[bits] = _slot_errors(kernel(ln - 1), want)
    print(f"kernels: grau {m}x{n} bit_exact={grau_exact}, matmul_grau "
          f"{m}x{k}x{n} bit_exact={mm_exact}, paged decode vs gather per "
          f"slot max |diff| / max |out| kv16={_fmt(errs[16])} "
          f"kv4={_fmt(errs[4])} (tol {KERNEL_VS_GATHER_TOL}), planted "
          f"off-by-one kv16={_fmt(planted[16])} kv4={_fmt(planted[4])} "
          "(each must exceed tol)", flush=True)
    check(grau_exact, "ops.grau differs from kernels/ref.grau_ref")
    check(mm_exact, "ops.matmul_grau differs from kernels/ref."
          "matmul_grau_ref")
    for bits in errs:
        check(np.all(errs[bits] <= KERNEL_VS_GATHER_TOL),
              f"paged decode kernel (kv_bits={bits}) differs from the "
              f"gather path by {_fmt(errs[bits])} of each slot's output")
        check(np.all(planted[bits] > KERNEL_VS_GATHER_TOL),
              f"an off-by-one mask (kv_bits={bits}) stays within the "
              f"tolerance: {_fmt(planted[bits])}")


def _prompts(rng, vocab: int, n: int, lo: int, hi: int):
    return [rng.integers(2, vocab, size=int(rng.integers(lo, hi + 1)))
            for _ in range(n)]


def _requests(prompts, max_new: int):
    from repro.serve.engine import Request
    return [Request(rid=i, prompt=p, max_new_tokens=max_new)
            for i, p in enumerate(prompts)]


def _s(x) -> str:
    return "n/a" if x is None else f"{x:.4f}"


def serve_phase(label: str, cfg, make_params, ecfg, reqs, *, mesh=None,
                impl: str, kernels: tuple) -> None:
    """Build the engine on the tree `make_params()` returns, warm it, serve
    `reqs` through the launcher's loop and hold the run to the checks every
    serving phase shares.  Only the engine holds the tree, so a quantized
    engine frees the float tree once it has packed it."""
    import jax
    from repro.launch.serve import serve_requests, serving_failures
    from repro.serve.engine import HEALTHY, ServeEngine

    devices = jax.devices()[:len(mesh.devices.flat) if mesh else 1]
    engine = ServeEngine(cfg, make_params(), ecfg, mesh=mesh)
    try:
        t0 = time.perf_counter()
        warm = engine.warmup()
        warm_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        done = serve_requests(engine, reqs)
        wall = time.perf_counter() - t0
        recompiles = engine.compile_count() - warm
        full = sum(len(r.out_tokens or []) == r.max_new_tokens for r in done)
        internal = sum(rs.finish_reason == "internal_error"
                       for rs in engine.scheduler.finished)
        found = engine.kernel_names()
        health = engine.health
        failures = serving_failures(engine)
        ttft = engine.scheduler.ttft_percentiles((50, 90))
        paged_impl = engine.paged_impl
        # what the serving engine holds: weights as served, pools, state
        in_use = [(d.memory_stats() or {}).get("bytes_in_use")
                  for d in devices]
    finally:
        engine.close()
    # the engine's jits close over it: collect the cycle so the next
    # phase starts with this engine's buffers freed
    del engine
    gc.collect()
    tokens = sum(len(r.out_tokens or []) for r in done)
    peak = [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in devices]
    print(f"serve[{label}]: {full}/{len(reqs)} requests finished with "
          f"max_new_tokens, internal_error={internal}, health={health}, "
          f"recompiles_after_warmup={recompiles}, paged_impl={paged_impl}, "
          f"kernels={found}; information: warmup {warm} programs in "
          f"{warm_s:.1f}s, TTFT p50/p90 {_s(ttft[0])}/{_s(ttft[1])}s, "
          f"{tokens / wall:.1f} tokens/s over {wall:.2f}s, bytes_in_use "
          f"while serving={in_use}, peak_bytes_in_use since start={peak}",
          flush=True)
    check(not failures, f"serve[{label}]: {failures}")
    check(len(done) == len(reqs) and full == len(reqs),
          f"serve[{label}]: {full}/{len(reqs)} requests got all their "
          "tokens")
    check(health == HEALTHY, f"serve[{label}]: engine {health}")
    check(recompiles == 0, f"serve[{label}]: {recompiles} compiles after "
          "warmup()")
    check(paged_impl == impl,
          f"serve[{label}]: paged_impl={paged_impl}, want {impl}")
    for step, names in found.items():
        check(set(kernels) <= set(names),
              f"serve[{label}]: {step} step runs kernels {names}, want "
              f"{list(kernels)} compiled in")


def phase_serve_one_chip(rng) -> None:
    import jax
    from repro.configs.archs import get_config
    from repro.serve.engine import EngineConfig
    from repro.serve.sharding import init_params

    cfg = get_config(ARCH, layers=STAGE_LAYERS)

    def make_params():
        return init_params(cfg, jax.random.PRNGKey(0))

    base = dict(slots=4, max_seq=1024, page_size=16, prefill_chunk=256,
                # the traffic's contexts span 4..34 blocks
                decode_buckets=(4, 8, 16, 32, 64),
                eos_id=-1)   # random weights: every request runs to length
    prompts = _prompts(rng, cfg.vocab_size, 8, 64, 512)
    serve_phase("f32", cfg, make_params, EngineConfig(**base),
                _requests(prompts, 32), impl="kernel",
                kernels=("paged_attention",))
    serve_phase("w4kv4", cfg, make_params,
                EngineConfig(**base, weight_bits=4, kv_bits=4),
                _requests(prompts, 32), impl="kernel",
                kernels=("paged_attention", "matmul_wq"))


def phase_four_chips(rng) -> None:
    import jax
    import jax.numpy as jnp
    from repro.configs.archs import get_config
    from repro.launch.mesh import make_serve_mesh
    from repro.models import lm
    from repro.serve import sharding as shard_lib
    from repro.serve.engine import EngineConfig

    mesh = make_serve_mesh(1, 4)
    # the 4-layer cut on chip 0 alone vs on the mesh, in prefill logits
    cfg = get_config(ARCH, layers=STAGE_LAYERS)
    b, ctx = 2, 128
    toks = jnp.asarray(rng.integers(2, cfg.vocab_size, (b, ctx)), jnp.int32)
    tl = jnp.full((b,), ctx, jnp.int32)

    def prefill(p, t, c):
        return lm.prefill_step(p, cfg, t, c, true_length=tl)[0]

    params = shard_lib.init_params(cfg, jax.random.PRNGKey(0))
    chip = np.asarray(jax.jit(prefill)(
        params, toks, lm.init_caches(cfg, b, ctx, dtype=jnp.float32)))
    sharded = shard_lib.place_params(params, cfg, mesh)
    del params
    caches = shard_lib.place_dense_caches(
        lm.init_caches(cfg, b, ctx, dtype=jnp.float32), cfg, mesh, b)
    meshed = np.asarray(jax.jit(shard_lib.with_shard_ctx(prefill, mesh, cfg))(
        sharded, toks, caches))
    del sharded, caches
    err = float(np.max(np.abs(meshed - chip)))
    scale = float(np.max(np.abs(chip)))
    same_top = float(np.mean(meshed.argmax(-1) == chip.argmax(-1)))
    print(f"mesh vs chip: {cfg.name} {cfg.num_layers} layers, prefill "
          f"logits ({b}x{ctx} tokens) max |diff| {err:.3e} of max |logit| "
          f"{scale:.3e} (tol {MESH_VS_CHIP_TOL} of it), argmax agreement "
          f"{same_top:.2f}", flush=True)
    check(err <= MESH_VS_CHIP_TOL * scale,
          f"mesh logits differ from one chip by {err:.3e}")

    cfg = get_config(ARCH)
    t0 = time.perf_counter()
    params = shard_lib.init_params(cfg, jax.random.PRNGKey(0), mesh=mesh)
    jax.block_until_ready(params)
    in_use = [(dev.memory_stats() or {}).get("bytes_in_use")
              for dev in jax.devices()]
    print(f"init: {cfg.name} {cfg.num_layers} layers built sharded on "
          f"{shard_lib.mesh_summary(mesh)} in "
          f"{time.perf_counter() - t0:.1f}s, bytes_in_use per chip "
          f"{in_use}", flush=True)
    # the engine serves this very tree (already in its shards)
    serve_phase("f32 mesh 1x4", cfg, lambda: params,
                EngineConfig(slots=4, max_seq=1024, page_size=16,
                             prefill_chunk=256, decode_buckets=(64,),
                             eos_id=-1),
                _requests(_prompts(rng, cfg.vocab_size, 4, 64, 256), 16),
                mesh=mesh,
                impl="gather", kernels=())


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded 40-layer phase and its "
                         "comparison, on four chips")
    args = ap.parse_args()
    cache_dir = use_repo_dirs()
    rng = np.random.default_rng(0)
    try:
        device = phase_device(cache_dir, 4 if args.four_chips else 1)
        if args.four_chips:
            phase_four_chips(rng)
        else:
            phase_kernels(rng)
            phase_serve_one_chip(rng)
    except SmokeFailure as e:
        print(f"FAILED: {e}", flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
