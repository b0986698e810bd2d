"""Find the highest arrival rate a cell's engine sustains, by a sweep.

    python3 bench/sweep.py --workload gptneox-1b.chat \
        --rates 0.3,0.5,0.7 --seconds 40 --seed 5

One engine and one warm-up; then, for each rate, the cell's mix at that
rate through the load generator.  A rate is sustained when the queue
does not grow: in the window's last quarter it is on average no longer
than in its second quarter, or under one request (no backlog to grow).
The same rule holds for every rate and mix.  The cell's rate is then
written into its mix file by hand, at 0.8 x the highest rate sustained.
Prints one JSON line per rate, with the rate the window really offered
(a burst mix rounds to whole bursts).
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))


def quarter_queue(queue, seconds, q):
    xs = [n for t, n in queue if q * seconds / 4 <= t < (q + 1) * seconds / 4]
    return sum(xs) / len(xs) if xs else 0.0


def sustained(q2: float, q4: float) -> bool:
    return q4 <= q2 or q4 < 1.0


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()

    from harness import cell, e2e, traffic
    from harness.loadgen import LoadGenerator
    from harness.stats import percentile

    bm, wl = cell.workload(args.workload)
    cell.check_devices(wl["chips"], require_tpu=True)
    cell.enable_compile_cache(cell.ROOT)
    cfg = cell.config(bm, wl["config"])
    mix = traffic.load_mix(os.path.join(BENCH, "traffic",
                                        f"{wl['traffic']}.json"))
    engine, _ = cell.build(cfg, args.seed, [])
    vocab, eng = cfg["model"]["vocab_size"], cfg["engine"]
    cell.warm_up(engine, vocab)
    for r in (float(x) for x in args.rates.split(",")):
        engine.reset()
        reqs = traffic.generate(dict(mix, rate_per_s=r), args.seconds,
                                args.seed, vocab, eng["max_seq"])
        gen = LoadGenerator(engine, eng["decode_block"])
        win = gen.run(reqs, args.seconds)
        gen.settle(win, drain=True)
        q2 = quarter_queue(win.queue, args.seconds, 1)
        q4 = quarter_queue(win.queue, args.seconds, 3)
        print(json.dumps({
            "rate_per_s": r, "requests": len(reqs),
            "offered_per_s": len(reqs) / args.seconds,
            "queue_q2": q2, "queue_q4": q4, "sustained": sustained(q2, q4),
            "ttft_p50_ms": 1e3 * percentile(
                [e2e._late(t) for t in win.tracks.values()], 50),
            "ttft_p90_ms": e2e.ttft_p90_ms(win, 0.0),
            "tpot_p90_ms": e2e.tpot_p90_ms(win, 0.0),
            "output_tok_s": e2e.output_tok_s(win, 0.0)}), flush=True)


if __name__ == "__main__":
    main()
