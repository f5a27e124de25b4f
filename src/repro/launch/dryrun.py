"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell: jax.jit(step).lower(*specs).compile() on the production mesh,
then record memory_analysis(), cost_analysis() and the parsed collective
bytes (roofline inputs) to experiments/dryrun/<arch>__<shape>__<mesh>.json.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3.2-3b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--mesh single|multi|both]
"""
from __future__ import annotations

import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512").strip()

import argparse
import json
import pathlib
import time
import traceback

import jax

from repro.configs.archs import ARCHS, get_config
from repro.configs.shapes import SHAPES, cell_status
from repro.launch import steps as steps_lib
from repro.launch.mesh import make_production_mesh, named_shardings

OUT_DIR = pathlib.Path(__file__).resolve().parents[3] / "experiments" / "dryrun"


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             overrides: dict | None = None, tag: str = "") -> dict:
    from repro.roofline.analyze import roofline_terms
    from repro.roofline.hlo import analyze_hlo

    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "multi" if multi_pod else "single"
    overrides = overrides or {}

    t0 = time.time()
    bundle = steps_lib.build_cell(cfg, shape, mesh, **overrides)
    with jax.set_mesh(mesh):
        jitted = jax.jit(bundle.fn,
                         in_shardings=named_shardings(mesh,
                                                      bundle.in_shardings),
                         donate_argnums=bundle.donate_argnums)
        lowered = jitted.lower(*bundle.args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):   # pre-0.5 returns [dict], newer dict
        cost = cost[0]
    hlo = compiled.as_text()
    # Trip-count-aware totals (raw cost_analysis counts while bodies once;
    # see roofline/hlo.py). All values are per-device.
    totals = analyze_hlo(hlo)

    n_chips = mesh.devices.size
    record = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_name,
        "tag": tag,
        "chips": n_chips,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "flops": totals.flops,
        "bytes_accessed": totals.bytes,
        "collective_bytes": totals.collective,
        "dot_bytes": totals.dot_bytes,
        "collective_by_op": totals.collective_by_op,
        "raw_cost_analysis": {
            "flops": cost.get("flops", 0.0),
            "bytes_accessed": cost.get("bytes accessed", 0.0),
        },
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
            "output_bytes": getattr(mem, "output_size_in_bytes", 0),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
            "generated_code_bytes": getattr(mem, "generated_code_size_in_bytes", 0),
        },
        "roofline": roofline_terms(
            flops=totals.flops,
            bytes_accessed=totals.bytes,
            collective_bytes=totals.collective, chips=n_chips),
    }
    OUT_DIR.mkdir(parents=True, exist_ok=True)
    suffix = f"__{tag}" if tag else ""
    out = OUT_DIR / f"{arch}__{shape_name}__{mesh_name}{suffix}.json"
    out.write_text(json.dumps(record, indent=2))
    return record


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--tag", default="")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--remat", default="full", choices=["dots", "full", "none"])
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--pad-heads", action="store_true")
    ap.add_argument("--ep-full", action="store_true",
                    help="serving EP: experts sharded over all mesh axes")
    ap.add_argument("--mla-cache-shard", action="store_true",
                    help="shard MLA latent cache seq axis over model")
    args = ap.parse_args()

    overrides = {
        "remat": None if args.remat == "none" else args.remat,
        "fsdp": not args.no_fsdp,
        "microbatches": args.microbatches,
        "pad_heads": args.pad_heads,
        "ep_full": args.ep_full,
        "mla_cache_shard": args.mla_cache_shard,
    }

    cells = []
    archs = list(ARCHS) if (args.all or args.arch is None) else [args.arch]
    shapes = list(SHAPES) if (args.all or args.shape is None) else [args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                cells.append((arch, shape, mp))

    failures = 0
    for arch, shape, mp in cells:
        status = cell_status(arch, shape)
        label = f"{arch} x {shape} x {'multi' if mp else 'single'}"
        if status is not None:
            print(f"SKIP  {label}: {status}", flush=True)
            continue
        try:
            rec = run_cell(arch, shape, multi_pod=mp, overrides=overrides,
                           tag=args.tag)
            r = rec["roofline"]
            print(f"OK    {label}: compile={rec['compile_s']}s "
                  f"flops={rec['flops']:.3e} coll={rec['collective_bytes']:.3e} "
                  f"dominant={r['dominant']}", flush=True)
        except Exception as e:  # noqa: BLE001 — report, keep sweeping
            failures += 1
            print(f"FAIL  {label}: {type(e).__name__}: {e}", flush=True)
            traceback.print_exc()
    if failures:
        raise SystemExit(f"{failures} cells failed")


if __name__ == "__main__":
    main()
