"""Reduce a JAX profiler trace of part of the window to what the per-layer
readers need.

On a TPU the ``.xplane.pb`` holds one plane per chip (``/device:TPU:n``)
with a line of XLA module executions (``XLA Modules``, named
``jit_<function>(<id>)``) and a line of XLA operations (``XLA Ops``,
nested: a ``while`` contains its body's operations), and a host plane
whose Python thread holds the harness's spans (``submit``,
``decode_loop``, ``wait_for_arrival``) and the Python tracer's function
events.  Times are nanoseconds from the start of the profile, on one
clock for host and device.

* busy: the union of the operation intervals of each chip, averaged
  over the chips;
* modules: the durations of each module's executions, by
  ``jit_<function>``;
* device operations by self time (a ``while`` counts what its body does
  not), named ``<module>/<op> <result shape>``;
* idle gaps on chip 0: the spaces between busy intervals inside the
  harness's spans, each named ``<harness span>/<innermost host event>``
  after what encloses its midpoint.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
from typing import Dict, List, Optional, Tuple

HARNESS_SPANS = ("submit", "decode_loop", "wait_for_arrival")
TOP = 10


@dataclasses.dataclass
class Event:
    name: str
    start: float        # seconds
    end: float


@dataclasses.dataclass
class TraceData:
    window_s: float
    busy_s: float
    chips: int
    modules: Dict[str, List[float]]
    op_self: Dict[str, float]
    gaps: List[Tuple[str, float]]

    def module_time(self, names) -> Tuple[int, float]:
        """(executions, seconds) of the modules named, over all chips."""
        durs = [d for n in names for d in self.modules.get(n, [])]
        return len(durs), sum(durs)

    def breakdown(self) -> dict:
        ops = sorted(self.op_self.items(), key=lambda kv: -kv[1])[:TOP]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in self.gaps[:TOP]]}


def module_name(event_name: str) -> str:
    return event_name.split("(", 1)[0]


def op_name(event_name: str) -> str:
    """``%fusion.3 = bf16[16,2048]{...} fusion(...)`` ->
    ``fusion.3 bf16[16,2048]``."""
    head, _, rest = event_name.partition(" = ")
    shape = rest.split("{", 1)[0].split(" ", 1)[0] if rest else ""
    return f"{head.lstrip('%')} {shape}".strip()


def _events(line) -> List[Event]:
    return [Event(e.name, e.start_ns * 1e-9,
                  (e.start_ns + e.duration_ns) * 1e-9) for e in line.events]


def _union(evs: List[Event]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for e in sorted(evs, key=lambda e: e.start):
        if out and e.start <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e.end))
        else:
            out.append((e.start, e.end))
    return out


def _self_times(ops: List[Event], modules: List[Event]) -> Dict[str, float]:
    mods = sorted(modules, key=lambda e: e.start)
    starts = [m.start for m in mods]
    out: Dict[str, float] = {}
    stack: List[list] = []          # [event, time of its children]

    def close(item):
        ev, child = item
        i = bisect.bisect_right(starts, ev.start) - 1
        mod = module_name(mods[i].name) if i >= 0 and \
            mods[i].end >= ev.start else "?"
        key = f"{mod.removeprefix('jit_')}/{op_name(ev.name)}"
        out[key] = out.get(key, 0.0) + (ev.end - ev.start) - child

    for ev in sorted(ops, key=lambda e: (e.start, -e.end)):
        while stack and stack[-1][0].end <= ev.start:
            close(stack.pop())
        if stack:
            stack[-1][1] += ev.end - ev.start
        stack.append([ev, 0.0])
    while stack:
        close(stack.pop())
    return out


def _activity(stack: List[Event]) -> str:
    """``<harness span>/<innermost host event>`` of an open-event stack."""
    if not stack:
        return "no host span"
    outer = next((e.name for e in stack if e.name in HARNESS_SPANS), None)
    inner = stack[-1].name
    return inner if outer in (None, inner) else f"{outer}/{inner}"


def _gaps(busy: List[Tuple[float, float]], host: List[Event]
          ) -> List[Tuple[str, float]]:
    spans = [e for e in host if e.name in HARNESS_SPANS]
    if not spans:
        return []
    lo, hi = min(e.start for e in spans), max(e.end for e in spans)
    edges = [(lo, lo)] + [b for b in busy if lo < b[1] and b[0] < hi] \
        + [(hi, hi)]
    holes = [(max(a[1], lo), min(b[0], hi)) for a, b in zip(edges, edges[1:])]
    holes = sorted((h for h in holes if h[1] > h[0]),
                   key=lambda h: (h[0] + h[1]) / 2)
    evs = sorted(host, key=lambda e: (e.start, -e.end))
    stack: List[Event] = []
    i, out = 0, []
    for a, b in holes:
        mid = (a + b) / 2
        while i < len(evs) and evs[i].start <= mid:
            while stack and stack[-1].end <= evs[i].start:
                stack.pop()
            stack.append(evs[i])
            i += 1
        while stack and stack[-1].end <= mid:
            stack.pop()
        out.append((_activity(stack), b - a))
    return sorted(out, key=lambda g: -g[1])


def read(trace_dir: str, span: Optional[Tuple[float, float]]) -> TraceData:
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace in {trace_dir}, found "
                           f"{len(paths)}")
    prof = ProfileData.from_file(paths[0])
    chips = sorted((p for p in prof.planes
                    if p.name.startswith("/device:TPU:")),
                   key=lambda p: p.name)
    host: List[Event] = []
    for plane in prof.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = _events(line)
                if any(e.name == "decode_loop" for e in evs):
                    host = evs
    modules: Dict[str, List[float]] = {}
    op_self: Dict[str, float] = {}
    busy_total, gaps = 0.0, []
    for n, plane in enumerate(chips):
        lines = {line.name: line for line in plane.lines}
        ops = _events(lines["XLA Ops"]) if "XLA Ops" in lines else []
        mods = _events(lines["XLA Modules"]) if "XLA Modules" in lines \
            else []
        for m in mods:
            modules.setdefault(module_name(m.name), []).append(m.end - m.start)
        busy = _union(ops)
        busy_total += sum(b - a for a, b in busy)
        for key, s in _self_times(ops, mods).items():
            op_self[key] = op_self.get(key, 0.0) + s / len(chips)
        if n == 0:
            gaps = _gaps(busy, host)
    window = (span[1] - span[0]) if span and span[1] else 0.0
    return TraceData(window_s=window,
                     busy_s=busy_total / max(len(chips), 1),
                     chips=len(chips), modules=modules, op_self=op_self,
                     gaps=gaps)
