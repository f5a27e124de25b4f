"""Training launcher.

Host mode (this container): --host runs a reduced config on the local
device(s); production mode assembles the 256/512-chip mesh cell exactly like
the dry-run, and would be started once per host by the cluster scheduler
(jax.distributed.initialize is a no-op single-host here).

Example:
  PYTHONPATH=src python -m repro.launch.train --arch llama3.2-3b --host \\
      --steps 50 --seq-len 128 --batch 8 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp

from repro.configs.archs import get_config
from repro.configs.shapes import SHAPES, ShapeSpec
from repro.data.pipeline import make_lm_batch_for
from repro.launch import steps as steps_lib
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.launch.runtime import use_repo_dirs
from repro.models import lm
from repro.train import optim
from repro.train.loop import LoopConfig, run


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--host", action="store_true",
                    help="reduced config on local devices (smoke/e2e)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--grau", action="store_true",
                    help="train with the GRAU activation surrogate")
    ap.add_argument("--lr", type=float, default=3e-3)
    args = ap.parse_args()
    use_repo_dirs()

    cfg = get_config(args.arch, smoke=args.host)
    if args.grau:
        from repro.models.config import GRAUConfig
        cfg = cfg.replace(grau=GRAUConfig())

    if args.host:
        mesh = make_host_mesh()
        shape = ShapeSpec("host", args.seq_len, args.batch, "train")
        dtype = jnp.float32
    else:
        mesh = make_production_mesh()
        shape = SHAPES[args.shape]
        dtype = jnp.bfloat16

    opt_cfg = optim.AdamWConfig(peak_lr=args.lr, warmup_steps=5,
                                total_steps=args.steps)
    step_fn = steps_lib.make_train_step(
        cfg, opt_cfg, remat=None if args.host else "full",
        q_chunk=min(1024, shape.seq_len), kv_chunk=min(1024, shape.seq_len))
    step_fn = steps_lib._with_shard_ctx(step_fn, mesh,
                                        steps_lib.act_rules(cfg, mesh))

    params, _ = lm.init_lm(cfg, jax.random.PRNGKey(0), dtype=dtype)
    opt_state = optim.init_opt_state(params)

    with jax.set_mesh(mesh):
        jitted = jax.jit(step_fn, donate_argnums=(0, 1))
        params, opt_state, hist = run(
            train_step=jitted,
            params=params,
            opt_state=opt_state,
            batch_fn=lambda s: make_lm_batch_for(cfg, shape, s, dtype=dtype),
            loop=LoopConfig(total_steps=args.steps, ckpt_every=args.ckpt_every,
                            ckpt_dir=args.ckpt_dir, log_every=1),
        )
    print(f"final loss: {hist['losses'][-1]:.4f} "
          f"(first {hist['losses'][0]:.4f})")


if __name__ == "__main__":
    main()
