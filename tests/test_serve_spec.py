"""Differential conformance suite for speculative decoding.

The tentpole contract: the speculative loop (draft -> one batched
verify -> commit accepted prefix -> pointer rollback) NEVER changes
what the engine emits, only how many dispatches it takes.  Emitted
tokens are always the true sampled tokens from the verify logits, so
greedy AND sampled streams must be bit-identical to the non-speculative
fused loop — per arch family x kv_format x mesh, through ring wraps,
mid-block finishes, faults, and arbitrary accept/reject patterns.

The scripted ``draft_fn`` hook turns acceptance into a controlled
input: a hypothesis-driven property test feeds adversarial per-position
match/mismatch patterns (accept-all, reject-all, alternating, random)
against an oracle stream precomputed from the non-speculative engine,
and asserts output invariance for every pattern.
"""

import dataclasses
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.sanitize import sanitize_spec
from repro.configs import get_config
from repro.models import build_model
from repro.serve import AdmissionConfig, ServeEngine, SpecConfig

# the MoE layer is dropless, so routing is per token and independent of
# batch composition: the hybrid needs no setting to compare exactly
ARCHS = {
    "attn": ("gptneox-1b", {}),
    "ssm": ("mamba2-2.7b", {}),
    "hybrid": ("jamba-v0.1-52b", {}),
}

KV_FORMATS = [None, "float8_e4m3fn", "float4_e2m1fn"]

PROMPTS = [[1, 2, 3, 4, 5, 6, 7], [9, 8, 7]]


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


def _tokens(results):
    return [r.tokens for r in sorted(results, key=lambda r: r.request_id)]


def _by_id(results):
    return {r.request_id: r for r in results}


# --------------------------------------------------------------------- #
# greedy identity matrix: family x kv_format
# --------------------------------------------------------------------- #

@pytest.mark.parametrize("family", list(ARCHS))
@pytest.mark.parametrize("kv_format", KV_FORMATS)
def test_spec_greedy_matches_nonspec(models, family, kv_format):
    """Greedy speculative decode must be token-identical to the
    non-speculative fused loop, including a slot that finishes
    mid-speculative-block (shorter second request)."""
    cfg, model, params = models[family]
    outs = []
    for spec in (SpecConfig(draft_tokens=3, ngram_table=64), None):
        eng = ServeEngine(model, params, batch=2, max_seq=64,
                          kv_format=kv_format, decode_block=6,
                          prefill_chunk=4, spec=spec)
        eng.submit(PROMPTS[0], max_new_tokens=12)
        eng.submit(PROMPTS[1], max_new_tokens=5)   # finishes mid-block
        res = eng.run()
        assert all(r.status == "ok" for r in res)
        outs.append(_tokens(res))
    assert outs[0] == outs[1]
    assert [len(t) for t in outs[0]] == [12, 5]


@pytest.mark.parametrize("family", list(ARCHS))
def test_spec_sampled_matches_nonspec(models, family):
    """Per-(request, position) key folding makes SAMPLED speculative
    streams identical too: the verify-row fold reproduces exactly the
    per-step folds the non-speculative loop would have made."""
    cfg, model, params = models[family]
    outs = []
    for spec in (SpecConfig(draft_tokens=4, ngram_table=64), None):
        eng = ServeEngine(model, params, batch=2, max_seq=64,
                          temperature=0.8, top_k=8, seed=3,
                          decode_block=5, spec=spec)
        eng.submit(PROMPTS[0], max_new_tokens=9)
        eng.submit(PROMPTS[1], max_new_tokens=6)
        outs.append(_tokens(eng.run()))
    assert outs[0] == outs[1]


def test_spec_sampled_batch_composition_independent(models):
    """A sampled speculative stream does not depend on what shares the
    pool: batch-2 speculative == batch-1 non-speculative per-step."""
    cfg, model, params = models["attn"]
    a = ServeEngine(model, params, batch=2, max_seq=64, temperature=0.8,
                    top_k=8, seed=3, decode_block=5,
                    spec=SpecConfig(draft_tokens=3, ngram_table=64))
    b = ServeEngine(model, params, batch=1, max_seq=64, temperature=0.8,
                    top_k=8, seed=3, decode_block=1)
    a.submit([4, 5, 6], max_new_tokens=7)
    a.submit([9, 9], max_new_tokens=3)             # batch companion
    b.submit([4, 5, 6], max_new_tokens=7)
    assert _tokens(a.run())[0] == _tokens(b.run())[0]


def test_spec_ring_wrap_matches_nonspec():
    """Speculate far past a sliding window so local-layer ring buffers
    wrap INSIDE a verify block and rejected tails roll back across the
    wrap boundary."""
    cfg = get_config("gemma2-2b").reduced()        # window 32
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    outs = []
    for spec in (SpecConfig(draft_tokens=3, ngram_table=64), None):
        eng = ServeEngine(model, params, batch=1, max_seq=64,
                          decode_block=8, prefill_chunk=8, spec=spec)
        eng.submit(list(range(1, 11)), max_new_tokens=45)  # 10+45 > 32
        outs.append(_tokens(eng.run()))
    assert outs[0] == outs[1]
    assert len(outs[0][0]) == 45


def test_spec_single_token_request(models):
    """max_new_tokens=1 is served entirely by admission: the spec loop
    must emit nothing for it and the stream must match non-spec."""
    cfg, model, params = models["attn"]
    outs = []
    for spec in (SpecConfig(draft_tokens=3, ngram_table=64), None):
        eng = ServeEngine(model, params, batch=2, max_seq=64,
                          decode_block=4, spec=spec)
        eng.submit([5, 4, 3], max_new_tokens=1)
        eng.submit([2, 2, 2], max_new_tokens=6)
        outs.append(_tokens(eng.run()))
    assert outs[0] == outs[1]
    assert len(outs[0][0]) == 1 and len(outs[0][1]) == 6


# --------------------------------------------------------------------- #
# scripted drafts: adversarial accept/reject patterns vs the oracle
# --------------------------------------------------------------------- #

D = 3                    # draft tokens for the scripted-pattern tests
MAX_SEQ = 64


@pytest.fixture(scope="module")
def oracle(models):
    """Non-speculative greedy streams + a device (slot, position) table
    of them: tbl[slot, p] = the token the oracle samples at position p
    (admission token at p = trunk_len, loop token j at trunk_len + j)."""
    cfg, model, params = models["attn"]
    eng = ServeEngine(model, params, batch=2, max_seq=MAX_SEQ,
                      decode_block=4)
    eng.submit(PROMPTS[0], max_new_tokens=12)
    eng.submit(PROMPTS[1], max_new_tokens=9)
    streams = _tokens(eng.run())
    tbl = np.full((2, MAX_SEQ), -7, np.int32)      # -7 never matches
    for slot, (prompt, toks) in enumerate(zip(PROMPTS, streams)):
        for j, t in enumerate(toks):
            tbl[slot, len(prompt) + j] = t
    return cfg, model, params, streams, jnp.asarray(tbl)


def _scripted_engine(model, params, tbl, pattern):
    """Spec engine whose drafts are scripted by ``pattern`` (b, MAX_SEQ)
    bool: True at [slot, p] -> the draft proposed for position p is the
    oracle token (accept), False -> a guaranteed-wrong token (reject)."""
    pat = jnp.asarray(pattern, bool)
    vocab = 512

    def draft_fn(st):
        # verify row d consumes draft d at position pos + 1 + d
        q = st["pos"][:, None] + 1 + jnp.arange(D)[None, :]
        q = jnp.minimum(q, MAX_SEQ - 1)
        rows = jnp.arange(pat.shape[0])[:, None]
        right = tbl[rows, q]
        wrong = (right + 1) % vocab                # differs even at -7
        return jnp.where(pat[rows, q], right, wrong).astype(jnp.int32)

    return ServeEngine(model, params, batch=2, max_seq=MAX_SEQ,
                       decode_block=2 * (D + 1),
                       spec=SpecConfig(draft_tokens=D, ngram_table=64,
                                       draft_fn=draft_fn))


def _run_scripted(oracle, pattern):
    cfg, model, params, streams, tbl = oracle
    eng = _scripted_engine(model, params, tbl, pattern)
    eng.submit(PROMPTS[0], max_new_tokens=12)
    eng.submit(PROMPTS[1], max_new_tokens=9)
    res = eng.run()
    assert all(r.status == "ok" for r in res)
    assert _tokens(res) == streams
    return eng


def test_scripted_accept_all_and_reject_all(oracle):
    """The two extremes bound acceptance accounting: reject-all commits
    exactly one (true) token per block (mean accepted length 1.0);
    accept-all commits full blocks wherever the budget allows."""
    full = _run_scripted(oracle, np.ones((2, MAX_SEQ), bool))
    none = _run_scripted(oracle, np.zeros((2, MAX_SEQ), bool))
    r_full, r_none = full.spec_report(), none.spec_report()
    assert r_none["mean_accepted_len"] == 1.0
    assert r_full["mean_accepted_len"] > 2.5
    assert r_full["blocks"] < r_none["blocks"]
    # loop tokens: 11 + 8 (admission emits each stream's first token)
    assert r_full["accepted_tokens"] == r_none["accepted_tokens"] == 19


def test_scripted_alternating_and_skew(oracle):
    """Alternating accept/reject and per-slot skewed patterns must not
    perturb the streams either."""
    alt = np.zeros((2, MAX_SEQ), bool)
    alt[:, ::2] = True
    _run_scripted(oracle, alt)
    skew = np.zeros((2, MAX_SEQ), bool)
    skew[0] = True                    # slot 0 races ahead, slot 1 crawls
    _run_scripted(oracle, skew)


try:
    import hypothesis
    from hypothesis import strategies as hyp_st
except ImportError:                                # pragma: no cover
    hypothesis = None

if hypothesis is not None:
    @hypothesis.settings(max_examples=8, deadline=None, database=None)
    @hypothesis.given(bits=hyp_st.lists(hyp_st.booleans(),
                                        min_size=2 * MAX_SEQ,
                                        max_size=2 * MAX_SEQ))
    def test_scripted_pattern_property(oracle, bits):
        """PROPERTY: for ANY per-(slot, position) accept/reject pattern
        the speculative engine reproduces the oracle streams exactly —
        drafts decide dispatch count, never content."""
        pattern = np.asarray(bits, bool).reshape(2, MAX_SEQ)
        _run_scripted(oracle, pattern)
else:                                              # pragma: no cover
    def test_scripted_pattern_property():
        pytest.skip("hypothesis not installed")


# --------------------------------------------------------------------- #
# faults inside a speculative block
# --------------------------------------------------------------------- #

def test_spec_fault_matches_nonspec(models):
    """A logits fault armed mid-stream fires at the same absolute token
    position under speculation: same partial prefix, same ``faulted``
    status, survivor bit-identical — even when the poisoned row lands
    inside a verify block's accepted prefix."""
    cfg, model, params = models["attn"]
    want = None
    for spec in (None, SpecConfig(draft_tokens=3, ngram_table=64)):
        eng = ServeEngine(model, params, batch=2, max_seq=64,
                          decode_block=6, spec=spec)
        a = eng.submit(PROMPTS[0], max_new_tokens=20)
        b = eng.submit(PROMPTS[1], max_new_tokens=20)
        eng.decode_loop()              # admit + first fused block
        # normalize to one absolute stream position: the engines have
        # emitted different counts after one block (that is the point
        # of speculation), so compute the arming delay per engine
        target = 10
        eng.inject_fault(a, "logits_nan",
                         delay=target - len(eng.out_tokens[0]))
        res = _by_id(eng.run())
        got = {rid: (r.status, r.tokens) for rid, r in res.items()}
        assert got[a][0] == "faulted" and len(got[a][1]) == target
        assert got[b][0] == "ok" and len(got[b][1]) == 20
        if want is None:
            want = got
        else:
            assert got == want
        assert eng.accounting()["balanced"]
        assert eng.watchdog_report()["ok"]


# --------------------------------------------------------------------- #
# seeded determinism across admission schedulers (FIFO vs SPF)
# --------------------------------------------------------------------- #

def test_spec_sampled_streams_scheduler_independent(models):
    """Two engines with identical seeds but different admission
    schedulers (FIFO vs shortest-prompt-first) admit requests in
    different orders into different slots — the per-request SAMPLED
    streams must still be identical, because keys fold from (request
    seed, position), never from slot index or dispatch pattern."""
    cfg, model, params = models["attn"]
    reqs = [([1, 2, 3, 4, 5, 6, 7], 6), ([8, 8], 6), ([5, 4, 3, 2], 6)]
    outs = {}
    for sched in ("fifo", "spf"):
        eng = ServeEngine(
            model, params, batch=1, max_seq=64, temperature=0.8,
            top_k=8, seed=3, decode_block=4,
            spec=SpecConfig(draft_tokens=3, ngram_table=64),
            admission=AdmissionConfig(queue_limit=8, scheduler=sched))
        ids = [eng.submit(p, max_new_tokens=n) for p, n in reqs]
        res = _by_id(eng.run())
        outs[sched] = [res[i].tokens for i in ids]
    assert outs["fifo"] == outs["spf"]
    # and both equal the non-speculative FIFO reference
    ref = ServeEngine(model, params, batch=1, max_seq=64,
                      temperature=0.8, top_k=8, seed=3, decode_block=4,
                      admission=AdmissionConfig(queue_limit=8))
    ids = [ref.submit(p, max_new_tokens=n) for p, n in reqs]
    res = _by_id(ref.run())
    assert outs["fifo"] == [res[i].tokens for i in ids]


# --------------------------------------------------------------------- #
# n-gram acceptance + draft-model leg
# --------------------------------------------------------------------- #

def test_ngram_acceptance_on_repetitive_stream(models):
    """A cyclic prompt seeds the per-slot n-gram table with the cycle;
    greedy continuations of reduced models are near-periodic, so the
    mean accepted length must beat the no-speculation floor of 1.0 —
    while the stream stays oracle-identical (the matrix test above
    already pins identity; this pins that speculation actually bites)."""
    cfg, model, params = models["attn"]
    eng = ServeEngine(model, params, batch=1, max_seq=128,
                      decode_block=8,
                      spec=SpecConfig(draft_tokens=3, ngram_table=128))
    eng.submit([1, 2, 3, 4] * 4, max_new_tokens=40)
    res = eng.run()
    assert res[0].status == "ok" and len(res[0].tokens) == 40
    rep = eng.spec_report()
    assert rep["enabled"] and rep["blocks"] > 0
    assert rep["mean_accepted_len"] > 1.0


def test_draft_model_self_draft_accepts_everything(models):
    """The target model drafting for itself proposes its own greedy
    continuations, so acceptance near-saturates (the draft leg's
    decode-step logits and the verify logits are the same math in
    different shapes — a numerical tie at the argmax can occasionally
    truncate a block) and the stream is identical to the
    non-speculative loop."""
    cfg, model, params = models["attn"]
    eng = ServeEngine(model, params, batch=1, max_seq=64,
                      decode_block=8, prefill_chunk=4,
                      spec=SpecConfig(draft_tokens=3, ngram_table=64,
                                      draft_model=model,
                                      draft_params=params))
    ref = ServeEngine(model, params, batch=1, max_seq=64,
                      decode_block=8, prefill_chunk=4)
    for e in (eng, ref):
        e.submit(PROMPTS[0], max_new_tokens=13)
    assert _tokens(eng.run()) == _tokens(ref.run())
    rep = eng.spec_report()
    assert rep["mean_accepted_len"] >= 3.0     # vs the 1.0 no-hit floor


def test_draft_model_random_weights_still_conformant(models):
    """An unrelated (randomly initialized) draft model mostly
    MIS-predicts — the rejected-tail rollback path runs constantly —
    yet the emitted streams must be untouched."""
    cfg, model, params = models["attn"]
    dcfg = dataclasses.replace(get_config("gptneox-1b").reduced(),
                               name="draft-tiny")
    dmodel = build_model(dcfg)
    dparams = dmodel.init(jax.random.PRNGKey(9))   # disagrees w/ target
    outs = []
    for spec in (SpecConfig(draft_tokens=3, ngram_table=64,
                            draft_model=dmodel, draft_params=dparams),
                 None):
        eng = ServeEngine(model, params, batch=2, max_seq=64,
                          decode_block=8, prefill_chunk=4, spec=spec)
        eng.submit(PROMPTS[0], max_new_tokens=12)
        eng.submit(PROMPTS[1], max_new_tokens=7)
        outs.append(_tokens(eng.run()))
    assert outs[0] == outs[1]


def test_spec_config_and_draft_validation(models):
    """Config/engine validation: speculation knobs and the draft-model
    restrictions fail loudly, not at trace time."""
    cfg, model, params = models["attn"]
    scfg, smodel, sparams = models["ssm"]
    with pytest.raises(ValueError, match="draft_tokens"):
        SpecConfig(draft_tokens=0)
    with pytest.raises(ValueError, match="go together"):
        SpecConfig(draft_model=model)
    with pytest.raises(ValueError, match="decoder-only attention"):
        ServeEngine(model, params, batch=1, max_seq=64,
                    spec=SpecConfig(draft_model=smodel,
                                    draft_params=sparams))
    vcfg = dataclasses.replace(get_config("gptneox-1b").reduced(),
                               name="draft-vocab", vocab_size=256)
    vmodel = build_model(vcfg)
    with pytest.raises(ValueError, match="vocab"):
        ServeEngine(model, params, batch=1, max_seq=64,
                    spec=SpecConfig(draft_model=vmodel,
                                    draft_params=vmodel.init(
                                        jax.random.PRNGKey(2))))
    from repro.launch.mesh import make_serving_mesh
    with pytest.raises(NotImplementedError, match="single-device"):
        ServeEngine(model, params, batch=1, max_seq=64,
                    mesh=make_serving_mesh((1,)),
                    spec=SpecConfig(draft_model=model,
                                    draft_params=params))


def test_spec_state_fields(models):
    """The speculation slot-state fields exist exactly when speculation
    is on (trace-safety: the fused loop's carry layout is decided at
    engine build, never data-dependent)."""
    cfg, model, params = models["attn"]
    spec = SpecConfig(draft_tokens=3, ngram_context=3, ngram_table=64)
    eng = ServeEngine(model, params, batch=2, max_seq=64, spec=spec)
    ref = ServeEngine(model, params, batch=2, max_seq=64)
    assert eng.state["spec_hist"].shape == (2, 3)
    assert eng.state["spec_ngram"].shape == (2, 64)
    assert eng.state["spec_accept"].shape == (2,)
    for f in ("spec_hist", "spec_ngram", "spec_accept", "spec_blocks"):
        assert f not in ref.state
    assert not ref.spec_report()["enabled"]


# --------------------------------------------------------------------- #
# sanitizers + mesh
# --------------------------------------------------------------------- #

@pytest.mark.slow
def test_sanitize_spec_clean():
    """The speculative serving path compiles each executable exactly
    once, runs the timed loop with zero implicit transfers, and its
    emitted streams match both a warmed re-run and the non-speculative
    engine."""
    rep = sanitize_spec()
    assert rep["compiled_exactly_once"], rep
    assert rep["zero_implicit_loop_transfers"], rep
    assert rep["tokens_match_warmup"], rep
    assert rep["tokens_match_nonspec"], rep
    assert rep["spec_report"]["blocks"] > 0


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CASES = os.path.join(REPO, "tests", "sharded_cases.py")


def _run_case(*names):
    """Run sharded conformance cases in a subprocess where XLA_FLAGS can
    still carve the host CPU into fake devices (same harness as
    test_serve_sharded)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=4 "
                        + env.get("XLA_FLAGS", ""))
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    proc = subprocess.run([sys.executable, CASES, *names],
                          capture_output=True, text=True, env=env,
                          timeout=1800)
    assert proc.returncode == 0, (
        f"sharded spec case(s) {names} failed:\n--- stdout ---\n"
        f"{proc.stdout}\n--- stderr ---\n{proc.stderr}")
    for name in names:
        assert f"CASE_OK {name}" in proc.stdout


@pytest.mark.slow
def test_spec_sharded_conformance():
    """Speculative decode on a (2,2) serving mesh stays bit-identical
    to the single-device non-speculative engine (greedy + sampled)."""
    _run_case("spec_matrix")
