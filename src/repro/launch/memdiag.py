import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Memory diagnosis for one dry-run cell: histogram of the largest tensor
shapes in the optimized (partitioned) HLO — the 'profile' used by the
§Perf hillclimb loop to localize per-device memory blowups.

    PYTHONPATH=src python -m repro.launch.memdiag --arch llama3.2-3b \
        --shape train_4k [--multi-pod] [--top 20]
"""

import argparse
import collections
import re


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--top", type=int, default=20)
    ap.add_argument("--min-mib", type=float, default=64.0)
    args = ap.parse_args()

    from repro.launch.dryrun import build_cell

    mesh, jitted, cell_args, meta = build_cell(
        args.arch, args.shape, args.multi_pod)
    with mesh:
        compiled = jitted.lower(*cell_args).compile()
        txt = compiled.as_text()
        mem = compiled.memory_analysis()

    from repro import compat

    pat = re.compile(r"\b(f32|bf16|f16|f8e4m3fn|f8e5m2|f6e2m3fn|f6e3m2fn"
                     r"|f4e2m1fn|s32|u32|s16|s8|u8|pred)"
                     r"\[([0-9,]+)\]")
    # sub-byte HBM stores are accounted at the compat registry's *packed*
    # bytes/element (fp4 0.5, fp6 0.75) — the previous table charged
    # f4e2m1fn a full byte, double-counting every fp4 weight/KV tensor
    # in the per-device profile this tool exists to localize
    bytes_per = {"f32": 4.0, "s32": 4.0, "u32": 4.0, "bf16": 2.0,
                 "f16": 2.0, "s16": 2.0, "f8e4m3fn": 1.0, "f8e5m2": 1.0,
                 "s8": 1.0, "u8": 1.0, "pred": 1.0}
    for hlo_name, reg_name in (("f4e2m1fn", "float4_e2m1fn"),
                               ("f6e2m3fn", "float6_e2m3fn"),
                               ("f6e3m2fn", "float6_e3m2fn")):
        bytes_per[hlo_name] = compat.storage_bytes_per_element(
            reg_name, packed=True)

    counts = collections.Counter()
    for m in pat.finditer(txt):
        dt, dims = m.groups()
        n = 1
        for d in dims.split(","):
            n *= int(d)
        b = n * bytes_per[dt]
        if b >= args.min_mib * 2**20:
            counts[f"{dt}[{dims}]"] += 1

    print(f"cell {meta['arch']}/{meta['shape']}/{meta['mesh']}  "
          f"args={mem.argument_size_in_bytes/2**30:.2f} GiB  "
          f"temp={mem.temp_size_in_bytes/2**30:.2f} GiB")
    print(f"{'size':>10s} {'refs':>5s}  shape")
    for k, c in counts.most_common(args.top):
        dt, dims = k.split("[")
        n = 1
        for d in dims[:-1].split(","):
            n *= int(d)
        print(f"{n*bytes_per[dt]/2**30:8.2f}G {c:5d}  {k}")


if __name__ == "__main__":
    main()
