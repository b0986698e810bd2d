"""Open-loop load generator over the engine's public serving surface.

It uses ``submit``, ``decode_loop(k)``, ``results``, ``queue``,
``slot_req`` and ``out_tokens``, and ``cancel`` once the window has
closed.  Requests are
submitted when they are due, whatever the engine is doing (between its
calls: it blocks), and every latency runs from the due time: a stall
delays every request behind it, and the generator's own lateness is
reported.  ``decode_loop`` is always called at the cell's
fixed K, so the window never needs an executable that set-up did not
warm.

Tokens are counted as the engine delivers them to the host: a finished
request's from its result, one in flight's from the tokens its slot
holds (``out_tokens``) after each call.  Beside them the generator keeps
what the steps a request was active owe it -- one token at admission,
then up to K per call -- so that a slot that stalls shows as a request
short of its tokens, and the work the per-layer readers count: for each
``decode_loop`` call, the prompts it admitted and, per decode row, the
context length and the steps it was active.
"""

from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax

from harness.traffic import Request

DRAIN_S = 60.0       # how long past the close a request due in it may take


@dataclasses.dataclass
class Track:
    req: Request
    rid: int
    due_t: float
    submit_t: float
    tokens: int = 0                     # delivered to the host so far
    owed: int = 0                       # what its active steps owe it
    slot: Optional[int] = None          # last slot it was seen in
    first_t: Optional[float] = None
    finish_t: Optional[float] = None
    status: Optional[str] = None        # set when finished; "cut" when
                                        # cancelled in flight after the
                                        # window (tokens served so far)
    result: object = None               # the engine's GenerationResult

    @property
    def short(self) -> bool:
        """Delivered fewer tokens than its active steps owe it."""
        return self.tokens < self.owed


@dataclasses.dataclass
class Call:
    """One ``decode_loop`` call: admitted prompt lengths and the decode
    rows as (context positions at the first step, steps active)."""
    admits: List[int]
    rows: List[Tuple[int, int]]
    traced: bool = False


@dataclasses.dataclass
class WindowResult:
    t0: float
    close_t: float                      # end of the last call in the window
    tracks: Dict[int, Track]
    calls: List[Call]
    tokens_in_window: int
    lateness_s: List[float]
    queue: List[Tuple[float, int]]      # (seconds into window, queued)
    trace_span: Optional[Tuple[float, float]] = None

    @property
    def seconds(self) -> float:
        return self.close_t - self.t0


def span(name: str):
    """A host span in the profiler's trace (cheap when not tracing)."""
    return jax.profiler.TraceAnnotation(name)


class LoadGenerator:
    def __init__(self, engine, k: int, clock: Callable[[], float] =
                 time.monotonic):
        self.engine, self.k, self.clock = engine, k, clock
        self.tracks: Dict[int, Track] = {}
        self.calls: List[Call] = []
        self._seen_results = 0
        self._tracing = False

    # -- engine calls --------------------------------------------------- #
    def _submit(self, r: Request, t0: float) -> None:
        with span("submit"):
            now = self.clock()
            rid = self.engine.submit(r.prompt, max_new_tokens=r.max_new)
        self.tracks[rid] = Track(r, rid, t0 + r.due, now)

    def _in_flight(self) -> Dict[int, object]:
        return {q.request_id: q for q in self.engine.slot_req
                if q is not None}

    def _busy(self) -> bool:
        return bool(self.engine.queue) or bool(self._in_flight())

    def _decode_loop(self) -> None:
        before = self._in_flight()
        with span("decode_loop"):
            self.engine.decode_loop(self.k)
        after = self._in_flight()
        new = self.engine.results[self._seen_results:]
        self._seen_results = len(self.engine.results)
        finished = {res.request_id: res for res in new}
        admitted = [rid for rid in list(after) + list(finished)
                    if rid not in before]
        rows = []
        for rid in list(before) + admitted:
            tr = self.tracks[rid]
            if rid in admitted:
                tr.owed = 1
            plen, left = len(tr.req.prompt), tr.req.max_new - tr.owed
            if left > 0:
                rows.append((plen + tr.owed, min(self.k, left)))
                tr.owed += min(self.k, left)
        for slot, q in enumerate(self.engine.slot_req):
            if q is not None:
                tr = self.tracks[q.request_id]
                tr.slot, tr.first_t = slot, q.first_token_t
                tr.tokens = len(self.engine.out_tokens[slot])
        for rid, res in finished.items():
            tr = self.tracks[rid]
            tr.tokens, tr.status, tr.result = (len(res.tokens), res.status,
                                               res)
            tr.first_t, tr.finish_t = res.first_token_t, res.finish_t
        self.calls.append(Call([len(self.tracks[r].req.prompt)
                                for r in admitted], rows, self._tracing))

    def settle(self, win: "WindowResult", drain: bool) -> None:
        """After the close.  With ``drain``, keep serving, without new
        arrivals, until every request due in the window has finished or
        ``DRAIN_S`` has passed; without, cancel what is in flight (a
        backlog: its answers are not due in the window)."""
        if drain:
            while self._pending:
                self._submit(self._pending.popleft(), win.t0)
                tr = self.tracks[max(self.tracks)]
                win.lateness_s.append(tr.submit_t - tr.due_t)
            limit = win.close_t + DRAIN_S
            while self._busy() and self.clock() < limit:
                self._decode_loop()
        else:
            self._cut_in_flight()

    def _cut_in_flight(self) -> None:
        """Cancel what is still in flight once the window has closed, so
        that the tokens it was served reach ``results`` and can be
        compared.  Nothing runs between the close and the cancel, so a
        cut request holds the tokens it was delivered in the window."""
        for rid in self._in_flight():
            self.engine.cancel(rid, status="shed")
        for res in self.engine.results[self._seen_results:]:
            tr = self.tracks[res.request_id]
            if tr.status is None and res.tokens:
                tr.tokens, tr.status, tr.result = (len(res.tokens), "cut",
                                                   res)
                tr.first_t = res.first_token_t
        self._seen_results = len(self.engine.results)

    # -- the window ----------------------------------------------------- #
    def run(self, requests: Sequence[Request], seconds: float,
            trace: Optional[Tuple[float, float, Callable, Callable]] = None
            ) -> WindowResult:
        """Serve ``requests`` (due offsets in seconds, all inside the
        window) for ``seconds``; then :meth:`settle` what is left.
        ``trace`` = (start, stop, begin, end): call ``begin()`` at the
        first call boundary ``start`` seconds in and ``end()`` at the
        first one ``stop`` seconds in."""
        pending = deque(sorted(requests, key=lambda r: r.due))
        lateness: List[float] = []
        queue: List[Tuple[float, int]] = []
        t0 = self.clock()
        end = t0 + seconds
        trace_span = None

        def submit_due(now: float) -> None:
            while pending and t0 + pending[0].due <= now:
                self._submit(pending.popleft(), t0)
                tr = self.tracks[max(self.tracks)]
                lateness.append(tr.submit_t - tr.due_t)

        while True:
            now = self.clock()
            if trace is not None:
                if not self._tracing and trace_span is None \
                        and now >= t0 + trace[0]:
                    trace[2]()
                    self._tracing, trace_span = True, (self.clock(), None)
                elif self._tracing and now >= t0 + trace[1]:
                    stop_t = self.clock()
                    trace[3]()
                    self._tracing = False
                    trace_span = (trace_span[0], stop_t)
            if now >= end:
                break
            submit_due(now)
            queue.append((now - t0, len(self.engine.queue)))
            if self._busy():
                self._decode_loop()
            else:
                wake = min(end, t0 + pending[0].due) if pending else end
                with span("wait_for_arrival"):
                    time.sleep(max(0.0, wake - self.clock()))
        if self._tracing:
            stop_t = self.clock()
            trace[3]()
            self._tracing = False
            trace_span = (trace_span[0], stop_t)
        close_t = self.clock()
        tokens = sum(tr.tokens for tr in self.tracks.values())
        self._pending = pending
        return WindowResult(t0, close_t, self.tracks, self.calls, tokens,
                            lateness, queue, trace_span)
