import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The lines above MUST run before any jax-importing import — jax locks
the platform and device count at first init.  (They are intentionally
before the module docstring's imports, per the deployment spec.)  The
dry-run simulates the pod on host devices, so it pins the CPU platform
and never takes a chip from another process.

For each cell this:
  1. builds parameter / optimizer / batch / cache ShapeDtypeStructs
     (``jax.eval_shape`` — no allocation),
  2. lowers the step function under the production mesh with explicit
     in/out shardings from ``repro.distributed.sharding``,
  3. compiles, and extracts cost_analysis / memory_analysis / collective
     bytes (``repro.core.hlo_analysis``),
  4. computes the three roofline terms vs TPU v5e constants
     (``repro.core.roofline``) and writes
     ``results/dryrun/<arch>__<shape>__<mesh>.json``.

CLI:
  python -m repro.launch.dryrun --arch qwen2.5-3b --shape train_4k
  python -m repro.launch.dryrun --arch qwen2.5-3b --shape train_4k --multi-pod
  python -m repro.launch.dryrun --all          # every runnable cell, both meshes
"""

import argparse
import json
import subprocess
import sys
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

# Per-arch gradient-accumulation for the train_4k cell: keeps the live
# microbatch activation footprint within HBM (the dry-run memory analysis
# verifies this).  global_batch 256 / accum 8 = 32 >= dp size on both meshes.
TRAIN_ACCUM_STEPS = 8


def _specs_to_shardings(mesh, tree):
    from repro.distributed.sharding import named
    return jax.tree.map(lambda s: named(mesh, s), tree,
                        is_leaf=lambda x: isinstance(x, P))


def optimized_overrides(shape_kind: str, seq_len: int,
                        n_heads: int = 0, model_axis: int = 16
                        ) -> Dict[str, Any]:
    """The §Perf-adopted beyond-baseline settings per shape kind:

    * single-chunk attention for 4k training (kills the online-softmax
      scan-carry round-trips, measured -18% memory),
    * fp8 KV storage for decode (measured -33% memory),
    * context-parallel attention when the head count cannot shard on the
      model axis (llama3.2's 24 heads / gemma's 8 on 16-way TP leave the
      whole mixer replicated: measured -83% compute / -85% memory,
      MFU 0.021 -> 0.135 on llama3.2 train).
    """
    out: Dict[str, Any] = {}
    if shape_kind == "train" and seq_len <= 4096:
        out["attn_chunk"] = seq_len
    if shape_kind == "decode":
        out["cache_dtype"] = "float8_e4m3fn"
    if (shape_kind in ("train", "prefill") and n_heads > 0
            and n_heads % model_axis != 0):
        out["attn_seq_shard"] = True
    return out


def build_cell(arch: str, shape_name: str, multi_pod: bool,
               extra: Optional[Dict[str, Any]] = None,
               variant: str = "baseline"):
    """Returns (step_fn_jitted, example_args (SDS), meta) for one cell."""
    from repro.configs import get_config, get_shape
    from repro.distributed import sharding as shd
    from repro.launch.mesh import make_production_mesh
    from repro.models import build_model
    from repro.models.model import batch_fields, batch_spec, decode_inputs_spec
    from repro.optim import AdamWConfig, adamw_init, opt_state_specs
    from repro.train import make_train_step

    import dataclasses
    cfg = get_config(arch)
    shape = get_shape(shape_name)
    if variant == "optimized":
        cfg = dataclasses.replace(
            cfg, **optimized_overrides(shape.kind, shape.seq_len,
                                       n_heads=cfg.n_heads))
    if extra:
        cfg = dataclasses.replace(cfg, **extra)
    mesh = make_production_mesh(multi_pod=multi_pod)
    cfg = dataclasses.replace(cfg, batch_axes=shd.dp_axes(mesh))
    model = build_model(cfg)
    opt_cfg = AdamWConfig(
        m_dtype="bfloat16" if cfg.fsdp else "float32",
        factored_v=cfg.fsdp)

    params_shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    p_specs = shd.param_specs(cfg, mesh, params_shapes)
    p_shardings = _specs_to_shardings(mesh, p_specs)

    meta = {"arch": arch, "shape": shape_name,
            "mesh": "pod2x16x16" if multi_pod else "pod16x16",
            "chips": 512 if multi_pod else 256,
            "params": cfg.param_count(),
            "active_params": cfg.active_param_count()}

    if shape.kind == "train":
        opt_shapes = jax.eval_shape(lambda p: adamw_init(opt_cfg, p),
                                    params_shapes)
        o_specs = opt_state_specs(opt_cfg, params_shapes, p_specs)
        state_shapes = {"params": params_shapes, "opt": opt_shapes}
        state_shardings = {"params": p_shardings,
                           "opt": _specs_to_shardings(mesh, o_specs)}
        b_specs = shd.batch_specs(cfg, shape, mesh,
                                  batch_fields(cfg, shape))
        b_shardings = _specs_to_shardings(mesh, b_specs)
        step = make_train_step(model, opt_cfg,
                               accum_steps=TRAIN_ACCUM_STEPS,
                               dp_axes=shd.dp_axes(mesh),
                               accum_dtype="bfloat16" if cfg.fsdp
                               else "float32")
        metric_keys = ("loss", "ce", "acc", "moe_lb_loss", "moe_z_loss",
                       "grad_norm")
        out_shardings = (state_shardings,
                         {k: _specs_to_shardings(mesh, P())
                          for k in metric_keys})
        jitted = jax.jit(step, in_shardings=(state_shardings, b_shardings),
                         out_shardings=out_shardings, donate_argnums=(0,))
        args = (state_shapes, batch_spec(cfg, shape))
        meta["tokens"] = shape.tokens
        meta["step_kind"] = "train_step"
        return mesh, jitted, args, meta

    if shape.kind == "prefill":
        b_specs = shd.batch_specs(cfg, shape, mesh,
                                  batch_fields(cfg, shape))
        b_shardings = _specs_to_shardings(mesh, b_specs)

        def prefill(params, batch):
            return model.prefill(params, batch, shape.seq_len)

        with mesh:   # tracing hits with_sharding_constraint
            out_shapes = jax.eval_shape(prefill, params_shapes,
                                        batch_spec(cfg, shape))
        logits_spec = P(shd.dp_axes(mesh), None)
        cache_specs_ = shd.cache_specs(cfg, mesh, out_shapes[1])
        out_shardings = (_specs_to_shardings(mesh, logits_spec),
                         _specs_to_shardings(mesh, cache_specs_))
        jitted = jax.jit(prefill, in_shardings=(p_shardings, b_shardings),
                         out_shardings=out_shardings)
        args = (params_shapes, batch_spec(cfg, shape))
        meta["tokens"] = shape.tokens
        meta["step_kind"] = "prefill_step"
        return mesh, jitted, args, meta

    # decode
    cache_shapes, token_s, pos_s = decode_inputs_spec(cfg, shape)
    c_specs = shd.cache_specs(cfg, mesh, cache_shapes)
    c_shardings = _specs_to_shardings(mesh, c_specs)
    tok_sharding = _specs_to_shardings(
        mesh, P(shd._maybe(mesh, shape.global_batch, shd.dp_axes(mesh))))
    logits_spec = P(shd.dp_axes(mesh) if shape.global_batch > 1 else None,
                    None)
    out_shardings = (_specs_to_shardings(mesh, logits_spec), c_shardings)
    jitted = jax.jit(
        model.decode_step,
        in_shardings=(p_shardings, c_shardings, tok_sharding, tok_sharding),
        out_shardings=out_shardings, donate_argnums=(1,))
    args = (params_shapes, cache_shapes, token_s, pos_s)
    meta["tokens"] = shape.global_batch       # one new token per row
    meta["step_kind"] = "serve_step"
    return mesh, jitted, args, meta


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             out_dir: str = "results/dryrun", verbose: bool = True,
             variant: str = "baseline") -> Dict[str, Any]:
    from repro.core import (TPU_V5E, analyze_compiled, build_report)

    t0 = time.time()
    mesh, jitted, args, meta = build_cell(arch, shape_name, multi_pod,
                                          variant=variant)
    meta["variant"] = variant
    with mesh:
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        mem = compiled.memory_analysis()
        stats = analyze_compiled(compiled)

    chips = meta["chips"]
    n_active = meta["active_params"]
    if meta["step_kind"] == "train_step":
        model_flops = 6.0 * n_active * meta["tokens"]
    else:
        model_flops = 2.0 * n_active * meta["tokens"]
    report = build_report(
        cell=f"{arch}/{shape_name}/{meta['mesh']}",
        stats=stats, device=TPU_V5E, chips=chips,
        dtype="bfloat16", model_flops=model_flops)

    result = {
        **meta,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "flops_per_device": stats.flops,
        "bytes_per_device": stats.bytes_accessed,
        "collective_bytes": stats.collectives.total_bytes,
        "collective_by_kind": dict(stats.collectives.bytes_by_kind),
        "collective_counts": dict(stats.collectives.count_by_kind),
        "memory": {
            "argument_bytes": stats.argument_bytes,
            "output_bytes": stats.output_bytes,
            "temp_bytes": stats.temp_bytes,
            "peak_bytes": stats.peak_bytes,
        },
        "structure": vars(stats.structure),
        "roofline": {
            "compute_s": report.compute_s,
            "memory_s": report.memory_s,
            "collective_s": report.collective_s,
            "dominant": report.dominant,
            "bound_s": report.bound_s,
            "model_flops": report.model_flops,
            "useful_ratio": report.useful_ratio,
            "mfu": report.mfu,
        },
    }
    if verbose:
        mm = result["memory"]
        print(f"[dryrun] {result['arch']:26s} {result['shape']:12s} "
              f"{result['mesh']:10s} compile {t_compile:6.1f}s  "
              f"args {mm['argument_bytes']/2**30:7.2f} GiB  "
              f"temp {mm['temp_bytes']/2**30:7.2f} GiB  "
              f"dominant={report.dominant:10s} mfu@bound={report.mfu:.3f}")
        print(f"         memory_analysis: {mem}")

    os.makedirs(out_dir, exist_ok=True)
    fname = f"{arch}__{shape_name}__{result['mesh']}.json"
    with open(os.path.join(out_dir, fname), "w") as f:
        json.dump(result, f, indent=1)
    return result


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--variant", default="baseline",
                    choices=["baseline", "optimized"])
    args = ap.parse_args()

    if args.all:
        # one subprocess per cell: fresh XLA state, bounded memory
        from repro.configs import all_cells
        failures = []
        for cfg, shape, ok, why in all_cells():
            for mp in (False, True):
                mesh_name = "pod2x16x16" if mp else "pod16x16"
                if not ok:
                    print(f"[dryrun] SKIP {cfg.name}/{shape.name}/"
                          f"{mesh_name}: {why}")
                    continue
                fname = os.path.join(
                    args.out, f"{cfg.name}__{shape.name}__{mesh_name}.json")
                if args.skip_existing and os.path.exists(fname):
                    continue
                cmd = [sys.executable, "-m", "repro.launch.dryrun",
                       "--arch", cfg.name, "--shape", shape.name,
                       "--out", args.out, "--variant", args.variant]
                if mp:
                    cmd.append("--multi-pod")
                r = subprocess.run(cmd)
                if r.returncode != 0:
                    failures.append((cfg.name, shape.name, mesh_name))
        if failures:
            print("FAILED cells:", failures)
            sys.exit(1)
        print("[dryrun] all cells passed")
        return

    assert args.arch and args.shape, "--arch/--shape or --all"
    run_cell(args.arch, args.shape, args.multi_pod, args.out,
             variant=args.variant)


if __name__ == "__main__":
    main()
