"""Serving under fire: fault injection + isolated recovery, admission
control with deadlines and backpressure, cancellation, and the traffic
scenario harness — across every arch family.

The robustness contract these tests pin:

* a fault in one slot finishes ONLY that request (``status="faulted"``),
  every surviving stream is bit-identical to an uninjected run, and the
  slot is reusable immediately (``clear_slot`` recovery) — per family x
  kv_format;
* the sentinel detects what it can (non-finite logits, e8m0 overflow,
  inf recurrent state) and the documented gap stays documented: a
  ``kv_bitflip`` that decodes finite is SILENT (status ok, diverged
  tokens);
* every submitted request ends in exactly one terminal status — the
  accounting identity holds through shed, deadline, cancel, and fault
  paths, under deterministic virtual-clock traffic replay with zero
  recompiles.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.sanitize import CompileCounter
from repro.configs import get_config
from repro.models import build_model
from repro.serve import (AdmissionConfig, QueueFull, STATUSES,
                         ServeEngine, bursty_trace, poisson_trace,
                         replay)

# "attn" joins the matrix because fault isolation must hold on the plain
# ring-KV path too
ARCHS = {
    "attn": ("gptneox-1b", {}),
    "ssm": ("mamba2-2.7b", {}),
    "hybrid": ("jamba-v0.1-52b", {}),
    "enc-dec": ("seamless-m4t-medium", {}),
    "vlm": ("internvl2-2b", {}),
}


def _build(family):
    name, over = ARCHS[family]
    cfg = get_config(name).reduced()
    if over:
        cfg = dataclasses.replace(cfg, **over)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


@pytest.fixture(scope="module")
def models():
    return {f: _build(f) for f in ARCHS}


def _modal_inputs(cfg, seed=7):
    rng = np.random.RandomState(seed)
    frames = patches = None
    if cfg.is_encoder_decoder:
        frames = rng.randn(9, cfg.d_model).astype(np.float32) * 0.02
    if cfg.frontend == "vision":
        patches = rng.randn(5, cfg.d_model).astype(np.float32) * 0.02
    return frames, patches


def _submit(eng, cfg, prompt, max_new_tokens, **kw):
    frames, patches = _modal_inputs(cfg)
    return eng.submit(prompt, max_new_tokens=max_new_tokens,
                      frames=frames, patches=patches, **kw)


def _by_id(results):
    return {r.request_id: r for r in results}


# --------------------------------------------------------------------- #
# fault isolation: poisoned slot out, survivors bit-identical, slot back
# --------------------------------------------------------------------- #

@pytest.mark.parametrize("kv_format", [None, "float8_e4m3fn",
                                       "float4_e2m1fn"])
@pytest.mark.parametrize("family", list(ARCHS))
def test_fault_isolation_per_family(models, family, kv_format):
    cfg, model, params = models[family]
    mk = lambda: ServeEngine(model, params, batch=2, max_seq=64,
                             kv_format=kv_format, decode_block=4,
                             prefill_chunk=8)
    pa, pb = [1, 2, 3, 4, 5, 6, 7], [9, 8, 7]

    oracle = mk()
    _submit(oracle, cfg, pa, 12)
    _submit(oracle, cfg, pb, 12)
    want = {r.request_id: r.tokens for r in oracle.run()}

    eng = mk()
    a = _submit(eng, cfg, pa, 12)
    b = _submit(eng, cfg, pb, 12)
    eng.decode_loop()                      # admit both, 1+4 tokens each
    eng.inject_fault(a, "logits_nan", delay=1)
    res = _by_id(eng.run())

    # the poisoned slot: one more clean token after arming, then the
    # sentinel trips — partial stream is a prefix of the oracle
    assert res[a].status == "faulted"
    assert len(res[a].tokens) == 6
    assert res[a].tokens == want[a][:6]
    # the survivor never notices: bit-identical to the uninjected run
    assert res[b].status == "ok"
    assert res[b].tokens == want[b]
    acc = eng.accounting()
    assert acc["balanced"] and acc["faulted"] == 1 and acc["ok"] == 1

    # recovery: the faulted slot is re-initialized through clear_slot —
    # the same prompt through the same engine reproduces the oracle
    c = _submit(eng, cfg, pa, 12)
    res2 = _by_id(eng.run())
    assert res2[c].status == "ok"
    assert res2[c].tokens == want[a]
    assert eng.watchdog_report()["ok"]


def test_logits_inf_detected():
    cfg, model, params = _build("attn")
    eng = ServeEngine(model, params, batch=1, max_seq=64, decode_block=4)
    a = eng.submit([3, 1, 4, 1, 5], max_new_tokens=10)
    eng.decode_loop()
    eng.inject_fault(a, "logits_inf", delay=0)
    res = eng.run()[0]
    assert res.status == "faulted"
    assert len(res.tokens) == 5            # admission + first block only


# --------------------------------------------------------------------- #
# cache-fault taxonomy: detected kinds fault, the silent gap stays pinned
# --------------------------------------------------------------------- #

def _run_with_cache_fault(model, params, kind, kv_format=None):
    eng = ServeEngine(model, params, batch=1, max_seq=64,
                      kv_format=kv_format, decode_block=4)
    a = eng.submit([2, 7, 1, 8, 2, 8], max_new_tokens=12)
    eng.decode_loop()
    eng.inject_fault(a, kind)
    return eng.run()[0], eng


@pytest.mark.parametrize("kv_format", ["float8_e4m3fn", "float4_e2m1fn"])
def test_e8m0_overflow_detected(kv_format):
    """An overflowed scale byte (0xFF -> 2^128) decodes to inf: the
    sentinel sees it on the next attention read, no matter the packed
    value format."""
    cfg, model, params = _build("attn")
    res, eng = _run_with_cache_fault(model, params, "e8m0_overflow",
                                     kv_format=kv_format)
    assert res.status == "faulted"
    assert len(res.tokens) < 12
    assert eng.accounting()["balanced"]


def test_state_inf_detected_on_ssm(models):
    cfg, model, params = models["ssm"]
    res, eng = _run_with_cache_fault(model, params, "state_inf")
    assert res.status == "faulted"
    assert len(res.tokens) < 12
    # recovered slot serves clean again
    eng.submit([2, 7, 1, 8, 2, 8], max_new_tokens=4)
    assert eng.run()[-1].status == "ok"


def test_kv_bitflip_is_silent_corruption():
    """The documented sentinel gap: an XOR'd e8m0 scale byte decodes to
    a wrong-but-FINITE scale, so the run finishes ``ok`` while the
    stream silently diverges from the uninjected oracle.  This test
    exists to keep the gap visible — if the sentinel ever catches it,
    the taxonomy table in repro.serve.faults is stale."""
    cfg, model, params = _build("attn")
    oracle = ServeEngine(model, params, batch=1, max_seq=64,
                         kv_format="float4_e2m1fn", decode_block=4)
    oracle.submit([2, 7, 1, 8, 2, 8], max_new_tokens=12)
    want = oracle.run()[0].tokens
    res, eng = _run_with_cache_fault(model, params, "kv_bitflip",
                                     kv_format="float4_e2m1fn")
    assert res.status == "ok"              # sentinel cannot see it
    assert len(res.tokens) == 12
    assert res.tokens != want              # ...but the data is wrong
    assert res.tokens[:5] == want[:5]      # prefix (pre-injection) holds


def test_spec_kv_bitflip_survivor_isolation():
    """The silent-corruption gap, on the SPECULATIVE path: a bitflip
    over one slot's packed KV bytes — including the ring region where
    drafted-but-rejected rows would land — finishes ``ok`` with a
    diverged stream, while the surviving slot's stream stays
    bit-identical to an uninjected speculative run.  Rejected draft
    rows are never written to the target cache, so the flip has nothing
    speculative to corrupt beyond what the non-speculative engine
    already exposes (see repro.serve.faults)."""
    from repro.serve import SpecConfig

    cfg, model, params = _build("attn")
    spec = SpecConfig(draft_tokens=3, ngram_table=64)

    def mk():
        return ServeEngine(model, params, batch=2, max_seq=64,
                           kv_format="float4_e2m1fn", decode_block=8,
                           spec=spec)

    pa, pb = [2, 7, 1, 8, 2, 8], [3, 1, 4, 1, 5]
    oracle = mk()
    a = oracle.submit(pa, max_new_tokens=12)
    b = oracle.submit(pb, max_new_tokens=12)
    want = _by_id(oracle.run())

    eng = mk()
    a = eng.submit(pa, max_new_tokens=12)
    b = eng.submit(pb, max_new_tokens=12)
    eng.decode_loop()                      # admit + first verify block
    n_clean = len(eng.out_tokens[0])
    eng.inject_fault(a, "kv_bitflip")
    res = _by_id(eng.run())
    assert res[a].status == "ok"           # sentinel cannot see it
    assert len(res[a].tokens) == 12
    assert res[a].tokens != want[a].tokens           # silently wrong
    assert res[a].tokens[:n_clean] == want[a].tokens[:n_clean]
    # the survivor never notices, token for token
    assert res[b].status == "ok"
    assert res[b].tokens == want[b].tokens
    assert eng.spec_report()["blocks"] > 0 # speculation actually ran
    assert eng.accounting()["balanced"]


def test_cache_faults_require_matching_cache():
    cfg, model, params = _build("attn")
    dense = ServeEngine(model, params, batch=1, max_seq=64,
                        decode_block=4)
    a = dense.submit([1, 2, 3], max_new_tokens=32)
    dense.decode_loop()
    with pytest.raises(ValueError, match="quantized KV"):
        dense.inject_fault(a, "e8m0_overflow")
    with pytest.raises(ValueError, match="recurrent"):
        dense.inject_fault(a, "state_inf")
    with pytest.raises(ValueError, match="unknown fault kind"):
        dense.inject_fault(a, "cosmic_ray")


# --------------------------------------------------------------------- #
# cancellation
# --------------------------------------------------------------------- #

def test_cancel_inflight_and_queued():
    cfg, model, params = _build("attn")
    eng = ServeEngine(model, params, batch=1, max_seq=64, decode_block=4)
    a = eng.submit([1, 2, 3, 4], max_new_tokens=16)
    b = eng.submit([5, 6], max_new_tokens=16)
    eng.decode_loop()                      # a in flight, b queued
    assert eng.cancel(b) is True           # queued: never touches device
    assert eng.cancel(a) is True           # in flight: partial tokens
    res = _by_id(eng.results)
    assert res[b].status == "shed" and res[b].tokens == []
    assert res[a].status == "shed" and len(res[a].tokens) == 5
    assert eng.cancel(a) is False          # already finished
    assert eng.cancel(999) is False
    with pytest.raises(ValueError, match="not in"):
        eng.cancel(a, status="vaporized")
    acc = eng.accounting()
    assert acc["balanced"] and acc["in_flight"] == 0 and acc["queued"] == 0
    # the cancelled slot admits the next request cleanly
    eng.submit([7, 8, 9], max_new_tokens=4)
    assert eng.run()[-1].status == "ok"
    assert eng.watchdog_report()["ok"]


# --------------------------------------------------------------------- #
# admission control: bounded queue, policies, deadlines, scheduling
# --------------------------------------------------------------------- #

def test_submit_validates_max_new_tokens():
    """Regression: max_new_tokens=0 used to sample a token anyway and
    write remaining=-1 into the slot state."""
    cfg, model, params = _build("attn")
    eng = ServeEngine(model, params, batch=1, max_seq=64)
    for bad in (0, -3):
        with pytest.raises(ValueError, match="max_new_tokens"):
            eng.submit([1, 2, 3], max_new_tokens=bad)
    assert eng.accounting()["submitted"] == 0   # nothing half-entered
    a = eng.submit([1, 2, 3], max_new_tokens=1)
    res = _by_id(eng.run())
    assert res[a].status == "ok" and len(res[a].tokens) == 1


def test_admission_policies():
    cfg, model, params = _build("attn")

    def mk(policy):
        return ServeEngine(
            model, params, batch=1, max_seq=64, decode_block=4,
            admission=AdmissionConfig(queue_limit=1, policy=policy))

    # reject: the NEW request is shed, earlier ones keep their place
    eng = mk("reject")
    ids = [eng.submit([1, 2, 3], max_new_tokens=4) for _ in range(3)]
    res = _by_id(eng.run())
    assert res[ids[0]].status == "ok"
    assert [res[i].status for i in ids[1:]] == ["shed", "shed"]

    # shed_oldest: fresh arrivals displace the oldest queued request
    eng = mk("shed_oldest")
    ids = [eng.submit([1, 2, 3], max_new_tokens=4) for _ in range(3)]
    res = _by_id(eng.run())
    assert [res[i].status for i in ids] == ["shed", "shed", "ok"]

    # block: QueueFull raises and consumes NOTHING — same id succeeds
    # on retry after the queue drains
    eng = mk("block")
    a = eng.submit([1, 2, 3], max_new_tokens=4)
    with pytest.raises(QueueFull):
        eng.submit([4, 5, 6], max_new_tokens=4)
    assert eng.accounting()["submitted"] == 1
    eng.run()
    b = eng.submit([4, 5, 6], max_new_tokens=4)
    assert b == a + 1                      # no id burned by the refusal
    assert _by_id(eng.run())[b].status == "ok"


def test_shortest_prompt_first_scheduling():
    cfg, model, params = _build("attn")
    eng = ServeEngine(
        model, params, batch=1, max_seq=64, decode_block=4,
        admission=AdmissionConfig(scheduler="spf"))
    long = eng.submit(list(range(1, 17)), max_new_tokens=4)
    mid = eng.submit(list(range(1, 9)), max_new_tokens=4)
    short = eng.submit([1, 2, 3], max_new_tokens=4)
    res = _by_id(eng.run())
    t = {i: res[i].first_token_t for i in (short, mid, long)}
    assert t[short] < t[mid] < t[long]


def test_deadlines_with_virtual_clock():
    """Deterministic deadline accounting on an injected clock: an
    expired queued request never spends prefill, an expired in-flight
    request is cancelled with its partial tokens."""
    cfg, model, params = _build("attn")
    now = [0.0]
    eng = ServeEngine(
        model, params, batch=1, max_seq=64, decode_block=4,
        admission=AdmissionConfig(deadline_ms=100.0),
        clock=lambda: now[0])
    a = eng.submit([1, 2, 3, 4], max_new_tokens=64)
    b = eng.submit([5, 6, 7], max_new_tokens=4)
    eng.decode_loop()                      # a in flight, b queued
    now[0] = 10.0                          # blow both deadlines
    eng.run()
    res = _by_id(eng.results)
    assert res[a].status == "deadline_exceeded"
    assert len(res[a].tokens) >= 5         # partials delivered
    assert res[b].status == "deadline_exceeded"
    assert res[b].tokens == []             # no prefill was spent on b
    acc = eng.accounting()
    assert acc["balanced"] and acc["deadline_exceeded"] == 2
    # a fresh request under the same config gets a fresh deadline
    c = eng.submit([8, 9], max_new_tokens=4)
    assert _by_id(eng.run())[c].status == "ok"


def test_run_stall_guard(monkeypatch):
    """Regression: a non-admittable queue used to spin forever at the
    bare ``continue``; now it raises with a diagnosis."""
    cfg, model, params = _build("attn")
    eng = ServeEngine(model, params, batch=1, max_seq=64)
    eng.submit([1, 2, 3], max_new_tokens=4)
    monkeypatch.setattr(eng.queue, "take", lambda now: (None, []))
    with pytest.raises(RuntimeError, match="stalled"):
        eng.run()


def test_truncated_status_and_flush():
    cfg, model, params = _build("attn")
    eng = ServeEngine(model, params, batch=1, max_seq=64, decode_block=4)
    eng.submit([1, 2, 3], max_new_tokens=32)
    res = eng.run(max_steps=4)
    assert res[0].status == "truncated" and res[0].truncated
    assert 0 < len(res[0].tokens) < 32
    assert set(STATUSES) >= {"ok", "truncated", "shed",
                             "deadline_exceeded", "faulted"}
    assert eng.accounting()["balanced"]


# --------------------------------------------------------------------- #
# traffic harness: deterministic traces, exact accounting, no recompiles
# --------------------------------------------------------------------- #

def test_traces_are_deterministic():
    a = poisson_trace(n=12, rate=50.0, vocab_size=500, seed=5)
    b = poisson_trace(n=12, rate=50.0, vocab_size=500, seed=5)
    assert a == b and len(a.arrivals) == 12
    c = poisson_trace(n=12, rate=50.0, vocab_size=500, seed=6)
    assert c != a
    assert all(x.t <= y.t for x, y in zip(a.arrivals, a.arrivals[1:]))
    assert all(0 <= t < 500 for arr in a.arrivals for t in arr.prompt)


def test_replay_overload_accounting_and_compile_once():
    """Virtual-clock replay of an overloaded bursty trace: exact status
    accounting, deterministic across replays, and the (policy, K) sweep
    reuses the warmed executables with zero recompiles."""
    cfg, model, params = _build("attn")
    eng = ServeEngine(model, params, batch=2, max_seq=64,
                      decode_block=4, prefill_chunk=8)
    sc = bursty_trace(n_bursts=2, burst_size=6, gap_s=0.5,
                      vocab_size=cfg.vocab_size, seed=3,
                      prompt_lens=(4, 8), output_lens=(4, 8))
    adm = AdmissionConfig(queue_limit=2, policy="reject")
    first = replay(eng, sc, k=4, admission=adm, step_cost_s=1e-3)
    assert first.accounting_ok
    assert first.submitted == 12
    assert first.by_status.get("shed", 0) > 0      # genuinely overloaded
    assert sum(first.by_status.values()) == first.submitted
    with CompileCounter() as compiles:
        again = replay(eng, sc, k=4, admission=adm, step_cost_s=1e-3)
        swept = replay(
            eng, sc, k=4, step_cost_s=1e-3,
            admission=AdmissionConfig(queue_limit=2,
                                      policy="shed_oldest"))
    assert compiles.count == 0
    assert again == first                  # virtual clock: bit-for-bit
    assert swept.accounting_ok and swept.policy == "shed_oldest"


def test_replay_deadline_trace():
    cfg, model, params = _build("attn")
    eng = ServeEngine(model, params, batch=2, max_seq=64,
                      decode_block=4, prefill_chunk=8)
    sc = poisson_trace(n=8, rate=200.0, vocab_size=cfg.vocab_size,
                       seed=9, output_lens=(16,), deadline_ms=20.0)
    rep = replay(eng, sc, k=4, step_cost_s=5e-3)   # 16 tok > 20ms budget
    assert rep.accounting_ok
    assert rep.by_status.get("deadline_exceeded", 0) > 0
    assert rep.goodput_tok_s >= 0.0


# --------------------------------------------------------------------- #
# watchdog
# --------------------------------------------------------------------- #

def test_watchdog_flags_divergence():
    cfg, model, params = _build("attn")
    eng = ServeEngine(model, params, batch=2, max_seq=64, decode_block=4)
    eng.submit([1, 2, 3], max_new_tokens=16)
    eng.decode_loop()
    assert eng.watchdog_report()["ok"]
    # lost finish: host tenant on a deactivated device slot
    eng.state = dict(eng.state,
                     active=jnp.zeros_like(eng.state["active"]))
    rep = eng.watchdog_report()
    assert not rep["ok"]
    assert any("lost finish" in f for f in rep["findings"])
    # orphan: device-active slot with no host request
    eng.state = dict(eng.state,
                     active=jnp.ones_like(eng.state["active"]))
    rep = eng.watchdog_report()
    assert any("orphaned" in f for f in rep["findings"])
