"""The comparison that decides ``correct``.

After the window, a sample of the requests the engine finished (in a
backlog cell, also those cut after the close, with the tokens served to
them) -- the one with the most served tokens, then others in an order
drawn from the seed, one from each slot not yet sampled before any slot
is sampled twice, until ``REQUESTS`` requests -- is run through the
configuration's plain float32 reference once, prompt and every served
token together.  At every position that produced a served token
(the first from the prompt, the rest from decoding) the gap is the
reference's best logit minus its logit for the served token; the number
compared is the widest gap.  Decoding is greedy, so a faithful program
reads only rounding here.

The control is the same reference one precision step down
(``harness.lowp``): at the same positions, the gap of the token the
control puts first.
"""

from __future__ import annotations

from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

REQUESTS = 8


def sample(tracks, seed: int) -> List:
    """The sample to compare, spread over the batch's slots.  A request
    admitted and finished in one call was never seen in a slot
    (``slot`` None) and counts as a slot of its own."""
    done = [t for t in tracks if t.status in ("ok", "cut")]
    if not done:
        return []
    done.sort(key=lambda t: (-t.tokens, t.rid))
    order = np.random.default_rng(seed).permutation(len(done) - 1)
    rest = [done[1 + i] for i in order]
    picked = [done[0]]
    for fresh in (True, False):
        for t in rest:
            if len(picked) == REQUESTS:
                return picked
            seen = {p.slot for p in picked if p.slot is not None}
            if any(t is p for p in picked) or (fresh and t.slot in seen):
                continue
            picked.append(t)
    return picked


def _padded(length: int, block: int) -> int:
    return -(-length // block) * block


class Comparison:
    """Jitted gap readers over one padded sequence length."""

    def __init__(self, ref, cfg: dict, length: int):
        self.length = _padded(length, getattr(ref, "BLOCK", 1))

        def gap(params, seq, target, mask):
            lg = ref.logits(cfg, params, seq)
            g = lg.max(-1) - jnp.take_along_axis(lg, target[:, None], 1)[:, 0]
            return jnp.max(jnp.where(mask, g, 0.0))

        def control_gap(params, seq, mask):
            lg = ref.logits(cfg, params, seq)
            low = ref.logits(cfg, params, seq, control=True)
            pick = jnp.argmax(low, -1)
            g = lg.max(-1) - jnp.take_along_axis(lg, pick[:, None], 1)[:, 0]
            return jnp.max(jnp.where(mask, g, 0.0))

        self._gap = jax.jit(gap)
        self._control_gap = jax.jit(control_gap)

    def _inputs(self, prompt: Sequence[int], tokens: Sequence[int]):
        seq = np.zeros(self.length, np.int32)
        full = list(prompt) + list(tokens)
        seq[:len(full)] = full
        target = np.zeros(self.length, np.int32)
        mask = np.zeros(self.length, bool)
        first = len(prompt) - 1
        target[first:first + len(tokens)] = tokens
        mask[first:first + len(tokens)] = True
        return jnp.asarray(seq), jnp.asarray(target), jnp.asarray(mask)

    def max_gap(self, params, picked) -> float:
        out = 0.0
        for t in picked:
            seq, target, mask = self._inputs(t.req.prompt, t.result.tokens)
            out = max(out, float(self._gap(params, seq, target, mask)))
        return out

    def max_control_gap(self, params, picked) -> float:
        out = 0.0
        for t in picked:
            seq, _, mask = self._inputs(t.req.prompt, t.result.tokens)
            out = max(out, float(self._control_gap(params, seq, mask)))
        return out
