import math
import types

import pytest

from harness import e2e
from harness.stats import percentile, rate


def test_percentile_nearest_rank():
    xs = list(range(1, 11))
    assert percentile(xs, 90) == 9
    assert percentile(xs, 50) == 5
    assert percentile(xs, 100) == 10
    assert percentile([], 90) is None


def test_failed_request_counts_as_missing():
    xs = [1.0] * 8 + [math.inf] * 2
    assert percentile(xs, 80) == 1.0
    assert percentile(xs, 90) == math.inf


def test_rate():
    assert rate(300, 30.0) == 10.0
    with pytest.raises(ValueError):
        rate(1, 0.0)


def _track(due, first, finish, tokens, status="ok"):
    return types.SimpleNamespace(due_t=due, first_t=first, finish_t=finish,
                                 tokens=tokens, status=status)


def test_latency_tails_from_due_time():
    tracks = {i: _track(0.0, 0.1 * (i + 1), 0.1 * (i + 1) + 0.9, 10)
              for i in range(10)}
    win = types.SimpleNamespace(tracks=tracks, tokens_in_window=100,
                                seconds=4.0)
    assert e2e.ttft_p90_ms(win, 0) == pytest.approx(900.0)
    assert e2e.tpot_p90_ms(win, 0) == pytest.approx(100.0)
    assert e2e.output_tok_s(win, 0) == 25.0
    tracks[3] = _track(0.0, None, None, 0, status=None)
    tracks[4] = _track(0.0, 0.2, 0.3, 4, status="faulted")
    tracks[5] = _track(0.0, None, None, 0, status=None)
    assert e2e.ttft_p90_ms(win, 0) == math.inf
    assert e2e.tpot_p90_ms(win, 0) == math.inf


def test_metric_found_by_name_up_to_its_first_dot():
    assert e2e.metric("ttft_p90_ms.bursty") is e2e.ttft_p90_ms
    assert e2e.metric("output_tok_s") is e2e.output_tok_s


def test_sample_spreads_over_slots():
    """The longest request, then one from each slot before any slot
    twice: 20 requests over 4 slots give 8 that cover all 4."""
    from harness import check

    tracks = [types.SimpleNamespace(rid=i, slot=i % 4, tokens=10 + i,
                                    status="ok" if i % 3 else "cut")
              for i in range(20)]
    picked = check.sample(tracks, seed=2**33 + 5)
    assert len(picked) == check.REQUESTS
    assert picked[0].rid == 19
    assert {t.slot for t in picked[:4]} == {0, 1, 2, 3}
    assert len({t.rid for t in picked}) == len(picked)
