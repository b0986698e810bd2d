"""Read what the engine records of itself: its request stamps and its
host spans.

Stamps (engine clock, on each ``GenerationResult``): ``submit_t``,
``admit_t`` (the hand-over to a slot), ``first_token_t``, ``finish_t``.
A request's queue wait is ``admit_t - submit_t``, its own admission
``first_token_t - admit_t``.

Spans (the profiler's clock, in the same ``.xplane.pb`` as the device's
operations): ``engine.admit``, ``engine.clear_slot``, ``engine.prefill``,
``engine.first_token``, ``engine.decode`` and ``engine.harvest``, on the
host thread that holds the harness's spans.  Their arguments may ride in
the event's name (``engine.admit#request_id=3,...#``), so a name is
matched on the part before ``#``.  The holes in chip 0's busy intervals
are cut as :mod:`harness.tracefile` cuts them, inside the harness's
spans; each goes to the innermost engine span that encloses its
midpoint.  A hole inside no engine span (the load generator waiting, the
harness's own code) goes to none.

The stamps are read from a window served with the profiler off (the
Python tracer and the profiler's stop would sit in them), the spans from
a traced one; ``bench/engine_split.py`` runs either.  A program that
records neither gives nothing to read: :func:`stamp_p90_ms` and
:func:`tail_split` return ``None``, :func:`read` an empty dict.
"""

from __future__ import annotations

import glob
import math
import os
import statistics
from typing import Dict, List, Optional, Sequence, Tuple

from harness import tracefile
from harness.stats import percentile
from harness.tracefile import Event

PREFIX = "engine."


def span_name(event_name: str) -> str:
    return event_name.split("#", 1)[0]


def idle_by_span(busy: List[Tuple[float, float]], host: List[Event]
                 ) -> Dict[str, float]:
    """Idle seconds of chip 0 under each engine span name seen on the
    host line (0.0 for a span no hole fell in)."""
    spans = [Event(span_name(e.name), e.start, e.end) for e in host
             if e.name in tracefile.HARNESS_SPANS
             or span_name(e.name).startswith(PREFIX)]
    out = {e.name: 0.0 for e in spans if e.name.startswith(PREFIX)}
    if out:
        for activity, secs in tracefile._gaps(busy, spans):
            inner = activity.rsplit("/", 1)[-1]
            if inner in out:
                out[inner] += secs
    return out


def read(trace_dir: str) -> Dict[str, float]:
    """:func:`idle_by_span` of the trace in ``trace_dir``."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    prof = ProfileData.from_file(path)
    host: List[Event] = []
    for plane in prof.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = tracefile._events(line)
                if any(e.name == "decode_loop" for e in evs):
                    host = evs
    chips = sorted((p for p in prof.planes
                    if p.name.startswith("/device:TPU:")),
                   key=lambda p: p.name)
    if not chips:
        return {}
    ops = [tracefile._events(line) for line in chips[0].lines
           if line.name == "XLA Ops"]
    return idle_by_span(tracefile._union(ops[0] if ops else []), host)


def stamp_p90_ms(tracks: Sequence, since: str, until: str
                 ) -> Optional[float]:
    """Nearest-rank p90 (ms) of ``result.<until> - result.<since>`` over
    every request due in the window; a request missing either stamp
    (never admitted, or never finished) counts as infinitely long.
    ``None`` where the program stamps no admission."""
    if not any(hasattr(t.result, "admit_t") for t in tracks):
        return None
    values = [math.inf if a is None or b is None else b - a
              for a, b in ((getattr(t.result, since, None),
                            getattr(t.result, until, None))
                           for t in tracks)]
    p = percentile(values, 90)
    return None if p is None else 1e3 * p


def tail_split(tracks: Sequence) -> Optional[dict]:
    """What the TTFT of the requests at or above the TTFT p90 is made of.
    A request's TTFT (first token - due, as ``harness.e2e`` counts it) is
    the sum of its generator lateness (due to submit), queue wait (submit
    to ``admit_t``) and own admission (``admit_t`` to first token), all
    on the host's monotonic clock.  Gives the tail's size, the median of
    each part (ms), and each part's share (%) of the tail's summed TTFT
    over the tail requests that were served (a p90 of a sum is no sum of
    p90s, so the shares are what adds up).  ``None`` where the program
    stamps no admission."""
    if not any(hasattr(t.result, "admit_t") for t in tracks):
        return None
    late = [math.inf if t.first_t is None else t.first_t - t.due_t
            for t in tracks]
    p90 = percentile(late, 90)
    tail = [t for t, x in zip(tracks, late) if x >= p90]
    parts = {"lateness": [], "queue_wait": [], "admission": []}
    for t in tail:
        r = t.result
        if r is None or r.admit_t is None or r.first_token_t is None:
            continue
        parts["lateness"].append(r.submit_t - t.due_t)
        parts["queue_wait"].append(r.admit_t - r.submit_t)
        parts["admission"].append(r.first_token_t - r.admit_t)
    total = sum(sum(v) for v in parts.values())
    return {"ttft_p90_ms": 1e3 * p90, "tail": len(tail),
            "served": len(parts["admission"]),
            "median_ms": {k: 1e3 * statistics.median(v) if v else None
                          for k, v in parts.items()},
            "share_pct": {k: 100.0 * sum(v) / total if total > 0 else None
                          for k, v in parts.items()}}
