"""``correct`` on the tiny cells, on the CPU: a sound run passes, and the
control and each fault a serving cell can have fail.

These skip only the harness's look for a chip; the rest of a run is the
benchmark's own: weights from the seed, engine, warm-up, the open-loop
window, and the comparison with the plain reference.  The tiny cells'
limits (``data/bench/configs``) sit between the sound runs' readings on
the CPU (at most 0.012 for tiny-gptneox, 0.048 for tiny-mamba2 over five
seeds) and the control's (at least 0.136 and 0.28).
"""

import time

import jax.numpy as jnp
import pytest

from harness import cell

CELLS = ["tiny-gptneox.chat", "tiny-mamba2.chat"]
E2E = {"tiny-gptneox.chat": {"ttft_p90_ms", "tpot_p90_ms", "setup_s"},
       "tiny-mamba2.chat": {"ttft_p90_ms.bursty", "tpot_p90_ms.bursty",
                            "setup_s"}}


def _run(root, name, seed=3, **kw):
    return cell.run(name, seed, 2.0, False, time.monotonic(), root=root,
                    require_tpu=False, **kw)


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(tiny_root, name):
    r = _run(tiny_root, name)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert list(r["checks"])[0] == "max_logit_gap"
    assert set(r["metrics"]) == E2E[name]


def test_backlog_reports_its_rate(tiny_root):
    r = _run(tiny_root, "tiny-gptneox.backlog")
    assert r["correct"], r["checks"]
    assert set(r["metrics"]) == {"output_tok_s", "setup_s"}
    assert r["metrics"]["output_tok_s"]["value"] > 0


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(tiny_root, name):
    c = _run(tiny_root, name, control=True)["checks"]
    assert c["control_gap"]["value"] > c["control_gap"]["limit"]


@pytest.mark.parametrize("name", CELLS)
def test_decode_step_that_keeps_its_state_is_caught(tiny_root, name,
                                                    monkeypatch):
    from repro.models.model import Model

    step = Model.decode_step

    def stale(self, params, cache, token, pos, active=None):
        logits, _ = step(self, params, cache, token, pos, active=active)
        return logits, cache

    monkeypatch.setattr(Model, "decode_step", stale)
    r = _run(tiny_root, name)
    assert not r["correct"], r["checks"]


def test_slots_that_never_advance_are_caught(tiny_root, monkeypatch):
    """Half of the batch left out: odd slots stay active but never step,
    so their requests are cut at the close short of their tokens."""
    from repro.serve.engine import ServeEngine

    make = ServeEngine._make_decode_loop

    def half(self, k):
        loop = make(self, k)

        def stalled(params, cache, state, key):
            live = state["active"]
            keep = jnp.arange(live.shape[0]) % 2 == 0
            cache, st, toks, emitted = loop(
                params, cache, dict(state, active=live & keep), key)
            return (cache, dict(st, active=st["active"] | (live & ~keep)),
                    toks, emitted)
        return stalled

    monkeypatch.setattr(ServeEngine, "_make_decode_loop", half)
    r = _run(tiny_root, "tiny-gptneox.backlog")
    assert not r["correct"], r["checks"]
    assert r["failed"] > 0


@pytest.mark.parametrize("name", CELLS)
def test_token_altered_where_sampled_is_caught(tiny_root, name,
                                               monkeypatch):
    from repro.serve import engine as engine_mod

    sample = engine_mod.sample_tokens

    def off_by_one(logits, *a, **kw):
        return (sample(logits, *a, **kw) + 1) % jnp.int32(logits.shape[-1])

    monkeypatch.setattr(engine_mod, "sample_tokens", off_by_one)
    r = _run(tiny_root, name)
    assert not r["correct"], r["checks"]
