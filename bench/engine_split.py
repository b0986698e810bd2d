"""Split a cell's TTFT tail, or the chip's idle time, by what the engine
records of itself.

    python3 bench/engine_split.py --workload mamba2-2.7b.chat-bursty \
        --seed 7 --seconds 51 --trace 0

Builds and warms the cell's engine as ``bench/run.py`` does and serves
the cell's traffic for one window.  ``--trace 0``, profiler off: from
the engine's stamps on every request due in the window, the p90 of the
queue wait and of the request's own admission, and what the TTFT of the
requests at or above the TTFT p90 is made of.  ``--trace 1``: the
stretch ``bench/run.py`` traces, with the chip's idle time put down to
the innermost engine span around it.  Prints one JSON line
(``harness.enginetrace``).
"""

import argparse
import json
import os
import shutil
import sys
import tempfile
from typing import Optional

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))


def split(name: str, seed: int, seconds: float, trace: bool,
          root: Optional[str] = None, require_tpu: bool = True) -> dict:
    """One window of cell ``name``; returns the line's object."""
    import jax

    from harness import cell, e2e, enginetrace, tracefile, traffic
    from harness.loadgen import LoadGenerator

    root = root or cell.ROOT
    bm, wl = cell.workload(name, root)
    cell.check_devices(wl["chips"], require_tpu)
    cell.enable_compile_cache(root)
    cfg = cell.config(bm, wl["config"], root)
    mix = traffic.load_mix(os.path.join(root, "bench", "traffic",
                                        f"{wl['traffic']}.json"))
    engine, _ = cell.build(cfg, seed, [])
    vocab, eng = cfg["model"]["vocab_size"], cfg["engine"]
    reqs = traffic.generate(mix, seconds, seed, vocab, eng["max_seq"])
    cell.warm_up(engine, vocab)
    trace_dir, tracer = None, None
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="engine_split_")
        start = cell.TRACE_START * seconds
        tracer = (start, start + min(cell.TRACE_SECONDS, 0.5 * seconds),
                  lambda: jax.profiler.start_trace(trace_dir),
                  jax.profiler.stop_trace)
    gen = LoadGenerator(engine, eng["decode_block"])
    win = gen.run(reqs, seconds, trace=tracer)
    gen.settle(win, drain=mix["arrival"] != "backlog")
    out = {"workload": name, "seed": seed, "requests": len(win.tracks),
           "summary": e2e.summary(win)}
    if trace:
        data = tracefile.read(trace_dir, win.trace_span)
        idle = enginetrace.read(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        out.update(window_s=data.window_s, busy_s=data.busy_s,
                   engine_idle_ms={k: 1e3 * v for k, v in idle.items()})
        if data.window_s > 0:
            out["idle_share"] = 100.0 * (1 - data.busy_s / data.window_s)
            out["engine_idle_share"] = (100.0 * sum(idle.values())
                                        / data.window_s)
    else:
        tracks = list(win.tracks.values())
        out.update(
            queue_wait_p90_ms=enginetrace.stamp_p90_ms(
                tracks, "submit_t", "admit_t"),
            admission_p90_ms=enginetrace.stamp_p90_ms(
                tracks, "admit_t", "first_token_t"),
            ttft_tail=enginetrace.tail_split(tracks))
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    print(json.dumps(split(args.workload, args.seed, args.seconds,
                           bool(args.trace))), flush=True)


if __name__ == "__main__":
    main()
