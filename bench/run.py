"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload gptneox-1b.backlog --seed 7 \
        --seconds 30 --trace 0

Runs on the machine it is started on and needs the chips the cell asks
for: with no TPU, or too few, it exits non-zero and prints no result.
Set-up (weights from the seed, engine, warm-up of the cell's own
executables) counts from process start to the window's first instant.
``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics, read from a profiler trace of part of the window.
The last line of standard output is one JSON object.
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    from harness import cell

    try:
        result = cell.run(args.workload, args.seed, args.seconds,
                          bool(args.trace), T_START)
    except cell.NoChip as e:
        sys.exit(f"[bench] {e}; nothing was run")
    print(json.dumps(finite(result), allow_nan=False))


def finite(x):
    """The result with every infinite or NaN number (a tail over a
    request that never came) as null, so that the line is strict JSON."""
    if isinstance(x, dict):
        return {k: finite(v) for k, v in x.items()}
    if isinstance(x, list):
        return [finite(v) for v in x]
    if isinstance(x, float) and not math.isfinite(x):
        return None
    return x


if __name__ == "__main__":
    main()
