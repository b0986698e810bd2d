"""The readers of the engine's own record: its request stamps (queue
wait, admission, the TTFT tail's parts) and its host spans (the
device's idle time by engine phase), on hand-made tracks and events and
on a trace recorded on a chip."""

import gzip
import math
import os
import types

import pytest

import engine_split
from harness import enginetrace, tracefile
from harness.loadgen import Track
from harness.traffic import Request
from harness.tracefile import Event

from repro.serve import GenerationResult


def _track(rid, submit, admit=None, first=None, finished=True, due=None):
    res = GenerationResult(rid, [1], [2] if first is not None else [],
                           submit_t=submit, admit_t=admit,
                           first_token_t=first, finish_t=first)
    due = submit if due is None else due
    return Track(Request(due, [1], 1), rid, due, submit, first_t=first,
                 result=res if finished else None)


def _queue(tracks):
    return enginetrace.stamp_p90_ms(tracks, "submit_t", "admit_t")


def _admission(tracks):
    return enginetrace.stamp_p90_ms(tracks, "admit_t", "first_token_t")


def test_stamp_tails_are_nearest_rank_p90():
    # queue waits 0.1 .. 1.0 s, admissions 0.01 .. 0.10 s: the 9th of 10
    tracks = [_track(i, 10.0 * i, 10.0 * i + 0.1 * (i + 1),
                     10.0 * i + 0.1 * (i + 1) + 0.01 * (i + 1))
              for i in range(10)]
    assert _queue(tracks) == pytest.approx(900.0)
    assert _admission(tracks) == pytest.approx(90.0)


def test_never_admitted_counts_as_infinitely_long():
    tracks = [_track(i, float(i), i + 0.5, i + 0.6) for i in range(9)]
    tracks.append(_track(9, 9.0))                      # shed: no admit_t
    assert _queue(tracks) == pytest.approx(500.0)
    tracks.append(_track(10, 10.0, finished=False))    # never finished
    assert math.isinf(_queue(tracks))
    assert math.isinf(_admission(tracks))


def test_tail_split_adds_up_over_the_tail_requests():
    # ten requests, due 0, 10, ..; the last two are the p90 tail (TTFTs
    # 8 and 9 s of 0..9): lateness 1 s, queue waits 5 and 6 s,
    # admissions 2 s
    tracks = [_track(i, 10.0 * i, 10.0 * i, 10.0 * i + i)
              for i in range(8)]
    tracks += [_track(i, 10.0 * i + 1, 10.0 * i + 1 + q,
                      10.0 * i + 3 + q, due=10.0 * i)
               for i, q in ((8, 5.0), (9, 6.0))]
    split = enginetrace.tail_split(tracks)
    assert split["ttft_p90_ms"] == pytest.approx(8000.0)
    assert (split["tail"], split["served"]) == (2, 2)
    assert split["median_ms"] == pytest.approx(
        {"lateness": 1000.0, "queue_wait": 5500.0, "admission": 2000.0})
    assert split["share_pct"] == pytest.approx(
        {"lateness": 100 * 2 / 17, "queue_wait": 100 * 11 / 17,
         "admission": 100 * 4 / 17})
    # a request never served is in the tail, and in no part: the tail is
    # now the 9 s request and it
    tracks.append(_track(10, 100.0))
    split = enginetrace.tail_split(tracks)
    assert split["ttft_p90_ms"] == pytest.approx(9000.0)
    assert (split["tail"], split["served"]) == (2, 1)
    assert split["share_pct"] == pytest.approx(
        {"lateness": 100 / 9, "queue_wait": 600 / 9, "admission": 200 / 9})
    assert enginetrace.tail_split(tracks[-1:])["share_pct"] == {
        "lateness": None, "queue_wait": None, "admission": None}


def test_a_program_without_admission_stamps_gives_nothing():
    old = types.SimpleNamespace(submit_t=0.0, first_token_t=1.0,
                                finish_t=2.0)
    tr = Track(Request(0.0, [1], 1), 0, 0.0, 0.0, first_t=1.0, result=old)
    assert _queue([tr]) is None and _admission([tr]) is None
    assert enginetrace.tail_split([tr]) is None


def test_idle_goes_to_the_innermost_engine_span():
    busy = [(1.0, 2.0), (5.0, 6.0), (8.0, 8.5)]
    host = [Event("decode_loop", 0.0, 9.0),
            Event("engine.admit#request_id=3,slot=0,prompt_len=40#",
                  0.5, 4.0),
            Event("engine.clear_slot#slot=0#", 0.5, 1.2),
            Event("engine.prefill#chunks=2#", 1.2, 3.0),
            Event("$engine.py:318 _host_read", 2.2, 2.8),
            Event("engine.decode#k=16#", 4.0, 4.1),
            Event("engine.harvest", 4.1, 8.8),
            Event("wait_for_arrival", 9.0, 12.0)]
    idle = enginetrace.idle_by_span(busy, host)
    # holes: 0-1 (midpoint 0.5 at the admit's start: clear_slot opens
    # there too, and is innermost), 2-5 (midpoint 3.5: admit), 6-8
    # (harvest), 8.5-12 (midpoint 10.25: waiting, in no engine span)
    assert idle == {"engine.admit": pytest.approx(3.0),
                    "engine.clear_slot": pytest.approx(1.0),
                    "engine.prefill": 0.0, "engine.decode": 0.0,
                    "engine.harvest": pytest.approx(2.0)}
    # the Python tracer's events do not hide the engine span around them
    busy = [(0.0, 2.0), (2.8, 9.0)]
    assert enginetrace.idle_by_span(busy, host)["engine.prefill"] == \
        pytest.approx(0.8)
    # a trace without engine spans reads nothing
    assert enginetrace.idle_by_span(busy, host[:1] + host[-1:]) == {}


# a trace recorded on a v5e chip with the engine's spans: the tiny
# gptneox chat cell, traced for half a second of its window; the numbers
# asserted are the ones that run printed on the chip
ENGINE_TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "data", "tiny-gptneox.chat.engine.xplane.pb.gz")
ENGINE_WINDOW_S = 0.4529366659999994


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    d = tmp_path_factory.mktemp("trace") / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    with gzip.open(ENGINE_TRACE) as src:
        (d / "run.xplane.pb").write_bytes(src.read())
    root = str(d.parents[2])
    return (tracefile.read(root, (0.0, ENGINE_WINDOW_S)),
            enginetrace.read(root))


def test_recorded_trace_splits_idle_by_engine_span(recorded):
    data, idle = recorded
    assert data.busy_s == pytest.approx(0.0018301089999997674, abs=1e-12)
    assert idle == pytest.approx({
        "engine.admit": 0.011918949000000116,
        "engine.clear_slot": 0.0027608699999999847,
        "engine.prefill": 0.023511960000000033,
        "engine.first_token": 0.007953702000000153,
        "engine.decode": 0.0,
        "engine.harvest": 0.041931447000000004}, abs=1e-12)
    # the rest of the idle time is the generator waiting between arrivals
    assert sum(idle.values()) < data.window_s - data.busy_s
    assert data.gaps[0][0] == "wait_for_arrival/$time sleep"


def test_engine_split_reads_a_served_window(tiny_root):
    """``bench/engine_split.py`` on a tiny cell, on the CPU: the stamp
    tails and the tail's parts with the profiler off; with it on, the
    traced stretch (a CPU trace has no chip, so no idle to split)."""
    out = engine_split.split("tiny-gptneox.chat", 3, 2.0, False,
                             root=tiny_root, require_tpu=False)
    assert out["requests"] > 0
    assert 0.0 <= out["queue_wait_p90_ms"] < math.inf
    assert 0.0 < out["admission_p90_ms"] < math.inf
    tail = out["ttft_tail"]
    assert tail["served"] == tail["tail"] >= 1
    assert sum(tail["share_pct"].values()) == pytest.approx(100.0)
    out = engine_split.split("tiny-gptneox.chat", 3, 2.0, True,
                             root=tiny_root, require_tpu=False)
    assert out["window_s"] > 0 and out["engine_idle_ms"] == {}
    assert "queue_wait_p90_ms" not in out
