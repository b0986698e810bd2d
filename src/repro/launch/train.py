"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2.5-3b \
        --steps 200 --batch 8 --seq 256 --reduced --ckpt /tmp/ckpt

On a real TPU pod this runs under the production mesh with the same
sharding specs the dry-run validated; on CPU (``--reduced``) it runs the
same code path end-to-end with the smoke mesh — checkpoint/restart,
watchdog and heartbeat included.  Multi-host init (``jax.distributed``)
is activated by the standard TPU env vars when present.
"""

from __future__ import annotations

import argparse
import dataclasses
import os

import jax
from jax.sharding import PartitionSpec as P

from repro import compat


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-3b")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--reduced", action="store_true",
                    help="reduced (smoke) config for CPU runs")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--data", default="affine",
                    choices=["affine", "uniform", "zipf"])
    ap.add_argument("--production-mesh", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    args = ap.parse_args()
    compat.enable_compile_cache()

    if "TPU_PROCESS_BOUNDS" in os.environ:      # multi-host pod
        jax.distributed.initialize()

    from repro.configs import get_config
    from repro.configs.base import ShapeConfig
    from repro.data import SyntheticConfig, SyntheticStream
    from repro.distributed import sharding as shd
    from repro.launch.mesh import make_production_mesh, make_smoke_mesh
    from repro.models import build_model
    from repro.optim import AdamWConfig, Schedule, adamw_init, opt_state_specs
    from repro.train import (TrainLoopConfig, make_train_step,
                             run_train_loop, train_state_init)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    shape = ShapeConfig("cli", "train", args.seq, args.batch)
    mesh = (make_production_mesh(multi_pod=args.multi_pod)
            if args.production_mesh else make_smoke_mesh())
    if args.production_mesh:
        cfg = dataclasses.replace(cfg, batch_axes=shd.dp_axes(mesh))
    model = build_model(cfg)
    opt_cfg = AdamWConfig(
        schedule=Schedule(peak_lr=args.lr, warmup_steps=20,
                          decay_steps=args.steps),
        m_dtype="bfloat16" if cfg.fsdp else "float32",
        factored_v=cfg.fsdp)

    with mesh:
        params_shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        p_specs = shd.param_specs(cfg, mesh, params_shapes)
        o_specs = opt_state_specs(opt_cfg, params_shapes, p_specs)
        state_sh = {
            "params": jax.tree.map(lambda s: shd.named(mesh, s), p_specs,
                                   is_leaf=lambda x: isinstance(x, P)),
            "opt": jax.tree.map(lambda s: shd.named(mesh, s), o_specs,
                                is_leaf=lambda x: isinstance(x, P)),
        }
        state = jax.jit(
            lambda k: train_state_init(model, opt_cfg, k),
            out_shardings=state_sh)(jax.random.PRNGKey(0))
        step_fn = jax.jit(
            make_train_step(model, opt_cfg, accum_steps=args.accum,
                            dp_axes=shd.dp_axes(mesh)),
            donate_argnums=(0,))
        stream = SyntheticStream(cfg, shape, SyntheticConfig(kind=args.data))
        loop_cfg = TrainLoopConfig(
            total_steps=args.steps,
            checkpoint_dir=args.ckpt,
            checkpoint_every=max(args.steps // 4, 10))
        state, history = run_train_loop(step_fn, state, stream, loop_cfg)
    print(f"[train] done: final loss {history[-1]['loss']:.4f}")


if __name__ == "__main__":
    main()
