"""Kernel micro-bench: GRAU epilogue fusion + paged-attention decode traffic
accounting, with wall time and bit-exactness checks.

On this CPU container the Pallas kernels run in interpret mode, so wall time
is NOT a TPU number; the TPU-relevant outputs are the HBM-traffic models:

  * fused int8 GEMM + GRAU epilogue vs the unfused (matmul -> int32 out ->
    requant pass) baseline — the quantity the §Perf memory-roofline claims
    use; and
  * the paged-attention decode kernel's per-step KV bytes at the live-block
    bucket vs the pre-PR full-capacity gather — the decode-path scaling law
    (live tokens, not pool size) that benchmarks/serving_bench.py measures
    end-to-end.

``PYTHONPATH=src python benchmarks/kernel_bench.py``  writes BENCH_kernels.json.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.build import build_grau
from repro.core.folding import fold
from repro.kernels import ops
from repro.kernels.paged_attention import decode_grid, paged_attention
from repro.kernels.ref import (grau_ref, matmul_grau_ref, matmul_wq_ref,
                               paged_attention_ref)
from repro.quant import weights as wq_lib


def traffic_model(m, k, n):
    """Bytes to/from HBM for fused vs unfused MAC->quant path."""
    fused = m * k + k * n + m * n                # int8 in, int8 out
    unfused = (m * k + k * n + 4 * m * n         # GEMM writes int32
               + 4 * m * n + m * n)              # requant reads int32, writes int8
    return fused, unfused


def paged_traffic_model(slots, kvh, h, d, block_size, live_blocks,
                        full_blocks, dtype_bytes=4):
    """Per-decode-step KV HBM reads: block-table-driven kernel (live blocks
    only, each block fetched once per kv head) vs the pre-PR full-capacity
    gather (every mapped-or-not table column, materialized densely)."""
    per_block = block_size * d * 2 * dtype_bytes          # k + v
    qo = slots * h * d * 2 * dtype_bytes                  # q in, out written
    live = slots * kvh * live_blocks * per_block + qo
    full = slots * kvh * full_blocks * per_block + qo
    return live, full


def _time(f, reps=3):
    jax.block_until_ready(f())
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(f())
    return (time.perf_counter() - t0) / reps * 1e6


def _grau_spec():
    return build_grau(fold("silu", s_in=2**-10, s_out=2**-4, out_bits=8),
                      mac_range=(-30000, 30000), segments=6, num_exponents=8,
                      mode="apot", bias_mode="lsq").spec


def bench_matmul_grau(quick: bool):
    rows = []
    spec = _grau_spec()
    shapes = [(256, 512, 256)] if quick else [(256, 512, 256), (512, 1024, 512)]
    for m, k, n in shapes:
        key = jax.random.PRNGKey(0)
        x = jax.random.randint(key, (m, k), -128, 128, dtype=jnp.int8)
        w = jax.random.randint(key, (k, n), -128, 128, dtype=jnp.int8)

        us_fused = _time(lambda: ops.matmul_grau(x, w, spec,
                                                 tiles=(128, 128, 128),
                                                 interpret=True))
        us_ref = _time(lambda: matmul_grau_ref(x, w, spec))
        ok = bool(jnp.all(ops.matmul_grau(x, w, spec, tiles=(128, 128, 128),
                                          interpret=True)
                          == matmul_grau_ref(x, w, spec)))
        fused_b, unfused_b = traffic_model(m, k, n)
        rows.append({"kernel": "matmul_grau", "shape": (m, k, n),
                     "us_fused_interp": us_fused, "us_ref": us_ref,
                     "bitexact": ok,
                     "traffic_saving": 1 - fused_b / unfused_b})
        print(f"kernel,matmul_grau,{m}x{k}x{n},us_interp={us_fused:.0f},"
              f"us_ref={us_ref:.0f},bitexact={ok},"
              f"hbm_traffic_saving={100 * (1 - fused_b / unfused_b):.1f}%",
              flush=True)

    # standalone GRAU unit vs element count (throughput of the epilogue alone)
    xq = jax.random.randint(jax.random.PRNGKey(1), (512, 1024), -60000, 60000,
                            dtype=jnp.int32)
    us = _time(lambda: ops.grau(xq, spec, interpret=True))
    ok = bool(jnp.all(ops.grau(xq, spec, interpret=True) == grau_ref(xq, spec)))
    print(f"kernel,grau,512x1024,us_interp={us:.0f},bitexact={ok}", flush=True)
    rows.append({"kernel": "grau", "shape": (512, 1024),
                 "us_fused_interp": us, "bitexact": ok})
    return rows


def wq_traffic_model(m, k, n, bits, k_tiles):
    """Weight bytes to/from HBM for one decode-shaped GEMM: packed
    power-of-two planes (bits/8 bytes per element + one exponent byte per
    (tile, column)) vs the f32 weight matrix.  Activations and outputs are
    identical on both sides, so the saving is the pure weight-stream term —
    the model-bytes/step quantity serving_bench's weight_quant section
    measures end-to-end from the compiled HLO."""
    packed = k * n * bits / 8 + k_tiles * n
    dense = 4 * k * n
    return packed, dense


def bench_matmul_wq(quick: bool):
    rows = []
    spec = _grau_spec()
    shapes = [(8, 512, 256)] if quick else [(8, 512, 256), (256, 1024, 512)]
    for m, k, n in shapes:
        key = jax.random.PRNGKey(0)
        x = jax.random.normal(key, (m, k), jnp.float32)
        w = jax.random.normal(jax.random.PRNGKey(1), (k, n), jnp.float32)
        for bits in (8, 4):
            qw = wq_lib.pack_tensor(w, bits, caxis=-2)
            us = _time(lambda: ops.matmul_wq(x, qw, tiles=(8, 128),
                                             interpret=True))
            us_ref = _time(lambda: matmul_wq_ref(x, qw))
            ok = bool(jnp.all(ops.matmul_wq(x, qw, tiles=(8, 128),
                                            interpret=True)
                              == matmul_wq_ref(x, qw)))
            # fused GRAU epilogue: the kernel's int8 activation bus must be
            # bit-identical to dequant-matmul -> attn_output_quant
            gok = bool(jnp.all(
                ops.matmul_wq(x, qw, spec, s_in=2**-8, tiles=(8, 128),
                              interpret=True)
                == matmul_wq_ref(x, qw, spec, s_in=2**-8)))
            packed_b, dense_b = wq_traffic_model(m, k, n, bits, qw.e.shape[0])
            rows.append({"kernel": "matmul_wq", "shape": (m, k, n),
                         "bits": bits, "us_kernel_interp": us,
                         "us_ref": us_ref, "bitexact": ok,
                         "grau_epilogue_bitexact": gok,
                         "weight_bytes_packed": packed_b,
                         "weight_bytes_f32": dense_b,
                         "weight_traffic_saving": 1 - packed_b / dense_b})
            print(f"kernel,matmul_wq,{m}x{k}x{n},bits={bits},"
                  f"us_interp={us:.0f},us_ref={us_ref:.0f},bitexact={ok},"
                  f"grau_bitexact={gok},weight_traffic_saving="
                  f"{100 * (1 - packed_b / dense_b):.1f}%", flush=True)
    return rows


def bench_paged_attention(quick: bool):
    rows = []
    rng = np.random.default_rng(0)
    slots, h, kvh, d, bs = 4, 8, 2, 64, 16
    full_blocks = 32 if quick else 128          # slot capacity in blocks
    num_blocks = slots * full_blocks + 1
    lengths = np.array([9, 25, 17, 30], np.int32)   # live << capacity
    live_blocks = int(max(-(-int(n) // bs) for n in lengths))
    bucket = 1 << (live_blocks - 1).bit_length()

    q = jnp.asarray(rng.normal(size=(slots, h, d)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(num_blocks, kvh, bs, d)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(num_blocks, kvh, bs, d)), jnp.float32)
    table = np.zeros((slots, full_blocks), np.int32)
    free = list(range(1, num_blocks))
    rng.shuffle(free)
    for s in range(slots):
        for j in range(-(-int(lengths[s]) // bs)):
            table[s, j] = free.pop()
    bt = jnp.asarray(table)
    ln = jnp.asarray(lengths)

    want = paged_attention_ref(q, kp, vp, bt[:, :bucket], ln)
    got = paged_attention(q, kp, vp, bt[:, :bucket], ln)
    close = bool(np.allclose(np.asarray(got), np.asarray(want),
                             rtol=3e-5, atol=3e-5))

    spec = _grau_spec()
    gq = paged_attention(q, kp, vp, bt[:, :bucket], ln, spec=spec,
                         s_in=2**-8)
    wq = paged_attention_ref(q, kp, vp, bt[:, :bucket], ln, spec=spec,
                             s_in=2**-8)
    bitexact = bool(np.array_equal(np.asarray(gq), np.asarray(wq)))

    us_bucket = _time(lambda: paged_attention(q, kp, vp, bt[:, :bucket], ln))
    us_full = _time(lambda: paged_attention(q, kp, vp, bt, ln))
    us_gather_bucket = _time(
        lambda: paged_attention_ref(q, kp, vp, bt[:, :bucket], ln))
    us_gather_full = _time(lambda: paged_attention_ref(q, kp, vp, bt, ln))
    live_b, full_b = paged_traffic_model(slots, kvh, h, d, bs, live_blocks,
                                         full_blocks)
    row = {
        "kernel": "paged_attention",
        "slots": slots, "kv_heads": kvh, "head_dim": d, "block_size": bs,
        "blocks_per_slot": full_blocks, "live_blocks": live_blocks,
        "bucket": bucket,
        "grid_bucket": decode_grid(slots, kvh, bucket),
        "grid_full": decode_grid(slots, kvh, full_blocks),
        "us_kernel_interp_bucket": us_bucket,
        "us_kernel_interp_full_table": us_full,
        "us_gather_bucket": us_gather_bucket,
        "us_gather_full_table": us_gather_full,
        "float_close": close,
        "grau_epilogue_bitexact": bitexact,
        "kv_bytes_per_step_live": live_b,
        "kv_bytes_per_step_full": full_b,
        "traffic_saving": 1 - live_b / full_b,
    }
    rows.append(row)
    print(f"kernel,paged_attention,slots={slots},bpslot={full_blocks},"
          f"live={live_blocks},us_interp_bucket={us_bucket:.0f},"
          f"us_interp_full={us_full:.0f},float_close={close},"
          f"grau_bitexact={bitexact},"
          f"kv_traffic_saving={100 * (1 - live_b / full_b):.1f}%",
          flush=True)
    return rows


def run(quick: bool = False, out: str | None = None):
    rows = (bench_matmul_grau(quick) + bench_matmul_wq(quick)
            + bench_paged_attention(quick))
    if out:
        with open(out, "w") as f:
            json.dump({"rows": rows}, f, indent=2, default=str)
        print(f"wrote {out}", flush=True)
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--out", default="BENCH_kernels.json")
    args = ap.parse_args()
    run(quick=args.quick, out=args.out)
