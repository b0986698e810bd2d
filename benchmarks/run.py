"""Benchmark harness — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only tab3_latency]

Prints ``name,key=value,...`` CSV lines per measurement and writes the
markdown report to results/characterization.md.
"""

from __future__ import annotations

import argparse
import dataclasses
import sys
import time

from benchmarks.common import append_history, parse_csv_row, write_report
from repro import compat

MODULES = [
    "tab3_latency",
    "fig2_3_ilp",
    "tab4_5_precision",
    "tab6_energy",
    "fig4_5_matmul",
    "fig6_10_memory",
    "tab7_gemm",
    "tab8_inference",
    "serve_throughput",
    "serve_scenarios",
    "collectives_bench",
    "roofline_table",
    "paper_claims",
]


def _headline(results) -> dict:
    """serve + tab8 headline numbers for the rolling trajectory file.

    Pulls from the CSV rows each module already emits (so the history
    line can never drift from the printed artifact): fused serving
    tok/s + per-device bandwidth per arch family, and tab8 tok/s +
    stored bytes/elem per precision."""
    head: dict = {}
    for res in results:
        if res.name == "serve_throughput":
            head["serve"] = [
                {k: a[k] for k in ("family", "arch", "kv_format", "mesh",
                                   "speedup", "bandwidth")}
                | {"tok_per_s_fused": a["fused"]["tok_per_s"]}
                for a in getattr(res, "artifacts", [])]
        elif res.name == "tab8_inference":
            rows = []
            for row in res.csv_rows:
                _, fields = parse_csv_row(row)
                if "tok_per_s_cpu" in fields:
                    rows.append({k: fields[k] for k in
                                 ("precision", "tok_per_s_cpu",
                                  "weight_bytes_per_elem",
                                  "kv_bytes_per_elem",
                                  "model_watts_v5e") if k in fields})
            head["tab8"] = rows
    return head


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small iteration counts (CI mode)")
    ap.add_argument("--only", default=None,
                    help="comma-separated module subset")
    ap.add_argument("--report", default="results/characterization.md")
    ap.add_argument("--history", default="results/BENCH_history.jsonl",
                    help="rolling per-PR trajectory JSONL ('' disables)")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None
    compat.enable_compile_cache()

    # capability header: every artifact records native vs. emulated paths
    rep = compat.report()
    compat_header = str(rep)
    print(compat_header)

    results = []
    failures = []
    for name in MODULES:
        if only and name not in only:
            continue
        mod = __import__(f"benchmarks.{name}", fromlist=["run"])
        t0 = time.time()
        try:
            res = mod.run(quick=args.quick)
        except Exception as e:                     # pragma: no cover
            failures.append((name, repr(e)))
            print(f"bench,{name},status=FAIL,error={e!r}",
                  file=sys.stderr)
            continue
        dt = time.time() - t0
        print(f"bench,{name},paper_ref={res.paper_ref!r},"
              f"wall_s={dt:.1f}")
        for row in res.csv_rows:
            print(row)
        results.append(res)

    if results:
        write_report(results, args.report, preamble=compat_header)
        print(f"bench,report,path={args.report}")
        head = _headline(results)
        if head and args.history:
            append_history({"bench": "run", "quick": args.quick,
                            "compat": dataclasses.asdict(rep), **head},
                           path=args.history)
            print(f"bench,history,path={args.history}")
    if failures:
        print(f"bench,failures,n={len(failures)}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
