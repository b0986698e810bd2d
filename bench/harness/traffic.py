"""One general generator of open-loop traffic from a mix file.

A mix file (``bench/traffic/<name>.json``) holds parameters only:

* ``arrival``: ``"backlog"`` (every request queued at window start),
  ``"poisson"`` (``rate_per_s``) or ``"bursts"`` (``burst`` simultaneous
  requests, bursts at mean rate ``rate_per_s / burst``);
* ``requests``: how many requests a backlog holds (arrival mixes take
  ``rate_per_s`` x the window instead);
* ``prompt`` and ``output``: lognormal lengths, each
  ``{"median", "sigma", "min", "max"}``, clipped to ``[min, max]``.

The schedule -- the lengths and the gaps between arrivals, stratified
quantiles of their distributions put in one fixed shuffled order -- is
the same for every seed; the seed draws the prompts' token ids, uniform
over the whole vocabulary.  So every seed asks for the same work at the
same times and the run-to-run spread measures the system, not the draw:
with a few dozen requests in a window, the order alone moved a cell's
90th percentile by a fifth between seeds.
"""

from __future__ import annotations

import dataclasses
import json
from statistics import NormalDist
from typing import List

import numpy as np

ARRIVALS = ("backlog", "poisson", "bursts")
SCHEDULE_SEED = 0        # orders every mix's lengths and gaps, for all seeds


@dataclasses.dataclass(frozen=True)
class Request:
    due: float            # seconds after the window opens
    prompt: List[int]
    max_new: int


def load_mix(path: str) -> dict:
    with open(path) as f:
        mix = json.load(f)
    if mix.get("arrival") not in ARRIVALS:
        raise ValueError(f"{path}: arrival must be one of {ARRIVALS}")
    return mix


def lognormal_lengths(n: int, spec: dict) -> np.ndarray:
    """The n stratified quantiles of a clipped lognormal: the same n
    lengths for every seed."""
    nd = NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.rint(spec["median"] * np.exp(spec["sigma"] * z))
    return np.clip(x, spec["min"], spec["max"]).astype(np.int64)


def exponential_gaps(n: int, total: float) -> np.ndarray:
    """n stratified exponential quantiles, scaled to sum to ``total``."""
    q = -np.log1p(-(np.arange(n) + 0.5) / n)
    return q * (total / q.sum())


def count(mix: dict, seconds: float) -> int:
    """Requests the mix asks for in a window of ``seconds``."""
    if mix["arrival"] == "backlog":
        return int(mix["requests"])
    n = max(1, round(mix["rate_per_s"] * seconds))
    if mix["arrival"] == "bursts":
        b = int(mix["burst"])
        n = b * max(1, round(n / b))
    return n


def due_times(mix: dict, n: int, seconds: float,
              rng: np.random.Generator) -> np.ndarray:
    """Arrival offsets in [0, seconds), sorted."""
    if mix["arrival"] == "backlog":
        return np.zeros(n)
    group = int(mix["burst"]) if mix["arrival"] == "bursts" else 1
    gaps = rng.permutation(exponential_gaps(n // group, seconds))
    starts = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return np.repeat(starts, group)


def generate(mix: dict, seconds: float, seed: int, vocab: int,
             max_seq: int) -> List[Request]:
    """The seed's requests, in due order."""
    n = count(mix, seconds)
    order = np.random.default_rng(SCHEDULE_SEED)
    prompts = order.permutation(lognormal_lengths(n, mix["prompt"]))
    outputs = order.permutation(lognormal_lengths(n, mix["output"]))
    if int((prompts + outputs).max()) >= max_seq:
        raise ValueError(f"prompt + output reaches max_seq {max_seq}: "
                         f"lower the mix's clips")
    due = due_times(mix, n, seconds, order)
    ids = np.random.default_rng(seed)
    return [Request(float(t), ids.integers(0, vocab, int(p)).tolist(),
                    int(o))
            for t, p, o in zip(due, prompts, outputs)]
