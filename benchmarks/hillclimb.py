"""§Perf hillclimb driver: re-lower one cell under a config variant and
diff the roofline terms against the recorded baseline.

    PYTHONPATH=src python -m benchmarks.hillclimb --arch qwen2.5-3b \
        --shape train_4k --set remat=full --set ssm_chunk=512

Each run prints before/after terms; the narrative log (hypothesis ->
confirmed/refuted) lives in EXPERIMENTS.md §Perf.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys


def parse_override(kv: str):
    k, v = kv.split("=", 1)
    for cast in (int, float):
        try:
            return k, cast(v)
        except ValueError:
            pass
    if v in ("true", "false"):
        return k, v == "true"
    return k, v


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--set", action="append", default=[],
                    help="config override key=value (repeatable)")
    ap.add_argument("--accum", type=int, default=None)
    ap.add_argument("--tag", default="variant")
    ap.add_argument("--baseline-dir", default="results/dryrun")
    ap.add_argument("--out", default="results/hillclimb")
    args = ap.parse_args()

    mesh_name = "pod2x16x16" if args.multi_pod else "pod16x16"
    base_file = os.path.join(
        args.baseline_dir, f"{args.arch}__{args.shape}__{mesh_name}.json")
    base = json.load(open(base_file)) if os.path.exists(base_file) else None

    # run the variant in a fresh subprocess (device-count isolation)
    code = f"""
import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
import json
from repro.launch import dryrun
overrides = dict({[parse_override(s) for s in args.set]!r})
if {args.accum!r} is not None:
    dryrun.TRAIN_ACCUM_STEPS = {args.accum!r}
import time
t0 = time.time()
mesh, jitted, cell_args, meta = dryrun.build_cell(
    {args.arch!r}, {args.shape!r}, {args.multi_pod!r}, extra=overrides)
from repro.core import TPU_V5E, analyze_compiled, build_report
with mesh:
    compiled = jitted.lower(*cell_args).compile()
    stats = analyze_compiled(compiled)
chips = meta["chips"]
mf = (6.0 if meta["step_kind"] == "train_step" else 2.0) \\
    * meta["active_params"] * meta["tokens"]
r = build_report("variant", stats, TPU_V5E, chips, model_flops=mf)
out = dict(
    compute_s=r.compute_s, memory_s=r.memory_s,
    collective_s=r.collective_s, dominant=r.dominant, mfu=r.mfu,
    useful=r.useful_ratio,
    temp_gib=stats.temp_bytes / 2**30,
    args_gib=stats.argument_bytes / 2**30,
    collective_by_kind=dict(stats.collectives.bytes_by_kind),
    compile_s=round(time.time() - t0, 1))
print("HILLCLIMB_RESULT " + json.dumps(out))
"""
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True)
    if r.returncode != 0:
        print(r.stderr[-3000:])
        sys.exit(1)
    line = [ln for ln in r.stdout.splitlines()
            if ln.startswith("HILLCLIMB_RESULT ")][-1]
    variant = json.loads(line.split(" ", 1)[1])

    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(
            args.out,
            f"{args.arch}__{args.shape}__{mesh_name}__{args.tag}.json"),
            "w") as f:
        json.dump({"overrides": args.set, "accum": args.accum,
                   **variant}, f, indent=1)

    def fmt(d, key, scale=1e3):
        return f"{d[key]*scale:9.3f}" if d else "       -"

    print(f"cell {args.arch}/{args.shape}/{mesh_name}  "
          f"variant: {args.set or args.accum}")
    print(f"{'term':12s} {'baseline':>9s} {'variant':>9s}")
    for term in ("compute_s", "memory_s", "collective_s"):
        b = base["roofline"][term] * 1e3 if base else None
        v = variant[term] * 1e3
        delta = f"  ({(v/b-1)*100:+.1f}%)" if b else ""
        print(f"{term:12s} {b if b else 0:9.3f} {v:9.3f}{delta}")
    print(f"dominant: {base['roofline']['dominant'] if base else '-'} -> "
          f"{variant['dominant']};  mfu {base['roofline']['mfu'] if base else 0:.3f} "
          f"-> {variant['mfu']:.3f};  temp {variant['temp_gib']:.1f} GiB")


if __name__ == "__main__":
    main()
