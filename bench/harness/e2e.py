"""End-to-end metrics, by name, from one window's host-clock record.

Latency tails run over every request due in the window, from the time it
was due; a request that never finished counts as infinitely late.  Rates
take all the work of the window over all of its time.

A metric is computed by the function named by its name up to the first
dot, so ``ttft_p90_ms.bursty`` is ``ttft_p90_ms`` under a bound of its
own, for cells whose runs spread more widely.
"""

from __future__ import annotations

import math

from harness.stats import percentile, rate


def _late(t) -> float:
    return math.inf if t.first_t is None else t.first_t - t.due_t


def _ttfts(win):
    return [_late(t) for t in win.tracks.values()]


def _tpots(win):
    per = []
    for t in win.tracks.values():
        if t.status != "ok":
            per.append(math.inf)
        elif t.tokens > 1:
            per.append((t.finish_t - t.first_t) / (t.tokens - 1))
    return per


def ttft_p90_ms(win, setup_s):
    return 1e3 * percentile(_ttfts(win), 90)


def tpot_p90_ms(win, setup_s):
    return 1e3 * percentile(_tpots(win), 90)


def summary(win) -> str:
    """Medians and tails of the window, for the log."""
    ms = lambda xs, q: 1e3 * (percentile(xs, q) or 0.0)
    a, b = _ttfts(win), _tpots(win)
    return (f"ttft p50/p90 {ms(a, 50):.1f}/{ms(a, 90):.1f} ms, tpot "
            f"p50/p90 {ms(b, 50):.2f}/{ms(b, 90):.2f} ms over "
            f"{len(a)} requests")


def output_tok_s(win, setup_s):
    return rate(win.tokens_in_window, win.seconds)


def setup_s(win, setup_s):
    return setup_s


METRICS = {f.__name__: f for f in (ttft_p90_ms, tpot_p90_ms, output_tok_s,
                                   setup_s)}


def metric(name: str):
    return METRICS[name.split(".")[0]]
