"""Engine request stamps and host spans.

A served request's time since submit splits into its queue wait
(``submit_t`` to ``admit_t``, the hand-over to a slot), its own admission
(``admit_t`` to ``first_token_t``) and its decode (to ``finish_t``).
The engine's host spans (``engine.SPANS``) name the phase the host is in
on the profiler's clock, and change nothing the engine serves.
"""

import itertools

import jax
import pytest
from jax.profiler import ProfileData

from repro.configs import get_config
from repro.models import build_model
from repro.serve import AdmissionConfig, ServeEngine
from repro.serve.engine import SPANS

PROMPTS = [[1, 2, 3, 4], [5, 6, 7], list(range(8, 27)), [9]]


@pytest.fixture(scope="module")
def lm():
    model = build_model(get_config("gptneox-1b").reduced())
    return model, model.init(jax.random.PRNGKey(0))


def _engine(lm, batch=1, **kw):
    model, params = lm
    return ServeEngine(model, params, batch=batch, max_seq=64,
                       decode_block=4, prefill_chunk=8, **kw)


def _by_id(results):
    return {r.request_id: r for r in results}


def test_queue_wait_is_the_scripted_wait(lm):
    now = [0.0]
    eng = _engine(lm, clock=lambda: now[0])
    a = eng.submit([1, 2, 3, 4], max_new_tokens=3)
    now[0] = 1.0
    b = eng.submit([5, 6, 7], max_new_tokens=3)
    now[0] = 2.5
    eng.decode_loop()                  # admits a; its 3 tokens finish it
    now[0] = 4.0
    eng.decode_loop()                  # admits b
    now[0] = 6.0
    res = _by_id(eng.run())
    assert res[a].queue_wait == 2.5 and res[b].queue_wait == 3.0
    assert (res[a].submit_t, res[a].admit_t, res[a].first_token_t,
            res[a].finish_t) == (0.0, 2.5, 2.5, 2.5)
    assert (res[b].admit_t, res[b].first_token_t) == (4.0, 4.0)
    assert res[b].finish_t == 4.0


def test_stamps_are_ordered_for_every_served_request(lm):
    """On a clock that ticks at every reading, each stamp is a later
    reading than the one before it."""
    ticks = itertools.count()
    eng = _engine(lm, batch=2, clock=lambda: float(next(ticks)))
    for i, p in enumerate(PROMPTS):
        eng.submit(p, max_new_tokens=1 + 3 * i)
    res = eng.run()
    assert [r.status for r in res] == ["ok"] * len(PROMPTS)
    for r in res:
        assert r.submit_t < r.admit_t < r.first_token_t <= r.finish_t
        assert r.queue_wait == r.admit_t - r.submit_t > 0
    # the third request waits for a slot: longer than the first two
    assert res[2].queue_wait > max(res[0].queue_wait, res[1].queue_wait)


def test_requests_that_never_reach_a_slot_have_no_admit_stamp(lm):
    now = [0.0]
    eng = _engine(lm, clock=lambda: now[0],
                  admission=AdmissionConfig(queue_limit=1, policy="reject",
                                            deadline_ms=100.0))
    a = eng.submit([1, 2, 3], max_new_tokens=2)
    shed = eng.submit([4, 5], max_new_tokens=2)       # the queue is full
    eng.decode_loop()                                 # a served
    late = eng.submit([6, 7], max_new_tokens=2)
    now[0] = 10.0
    eng.decode_loop()                                 # late expired queued
    cancelled = eng.submit([8], max_new_tokens=2)
    assert eng.cancel(cancelled)
    eng.run()
    res = _by_id(eng.results)
    assert res[a].status == "ok" and res[a].admit_t == 0.0
    assert [res[r].status for r in (shed, late, cancelled)] == [
        "shed", "deadline_exceeded", "shed"]
    for rid in (shed, late, cancelled):
        assert res[rid].admit_t is None and res[rid].queue_wait is None
        assert res[rid].tokens == []


def _serve(eng):
    eng.reset()
    for i, p in enumerate(PROMPTS):
        eng.submit(p, max_new_tokens=2 + 5 * i)
    return [(r.request_id, r.tokens) for r in eng.run()]


def test_spans_reach_the_trace_and_change_no_token(lm, tmp_path):
    eng = _engine(lm, batch=2)
    plain = _serve(eng)
    jax.profiler.start_trace(str(tmp_path))
    try:
        traced = _serve(eng)
    finally:
        jax.profiler.stop_trace()
    assert traced == plain

    (path,) = tmp_path.glob("**/*.xplane.pb")
    events = [e for plane in ProfileData.from_file(str(path)).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for e in line.events
              if e.name.split("#")[0] in SPANS]
    by_name = {n: [e for e in events if e.name.split("#")[0] == n]
               for n in SPANS}
    n_req = len(PROMPTS)
    for name in ("engine.admit", "engine.clear_slot", "engine.prefill",
                 "engine.first_token"):
        assert len(by_name[name]) == n_req, name
    assert len(by_name["engine.decode"]) == len(by_name["engine.harvest"])
    assert len(by_name["engine.decode"]) > 0
    admits = by_name["engine.admit"]
    ids = sorted(dict(e.stats)["request_id"] for e in admits)
    assert ids == list(range(n_req))
    # each admission's phases nest inside its engine.admit span
    for name in ("engine.clear_slot", "engine.prefill",
                 "engine.first_token"):
        for e in by_name[name]:
            assert any(a.start_ns <= e.start_ns and
                       e.start_ns + e.duration_ns
                       <= a.start_ns + a.duration_ns for a in admits)
