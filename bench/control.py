"""Readings that set the limit of a cell's comparison.

    python3 bench/control.py --workload gptneox-1b.backlog \
        --seeds 11,12,13 --seconds 15

For each seed, in one process: one run of the cell as the benchmark runs
it (a shorter window at the cell's own load), its comparison with the
reference (the lower reading: the widest logit gap of the served
tokens), and the control's reading on the same prompts and tokens (the
gap of the token the fp8-weight, bfloat16 reference puts first).  The
benchmark's own runs never run the control.  Prints one JSON line per
seed and a summary line.
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, default=15.0)
    args = ap.parse_args()

    from harness import cell

    gaps, controls = [], []
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.monotonic()
        r = cell.run(args.workload, seed, args.seconds, False, t0,
                     control=True)
        c = r["checks"]
        gaps.append(c["max_logit_gap"]["value"])
        controls.append(c["control_gap"]["value"])
        print(json.dumps({"seed": seed, "correct": r["correct"],
                          "gap": gaps[-1], "control_gap": controls[-1],
                          "served": c["served_tokens_compared"]["value"],
                          "metrics": r["metrics"]}), flush=True)
    print(json.dumps({"workload": args.workload, "seeds": len(gaps),
                      "lower_reading": max(gaps),
                      "upper_reading": min(controls)}))


if __name__ == "__main__":
    main()
