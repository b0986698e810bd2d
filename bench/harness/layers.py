"""Shared arithmetic of the per-layer readers in ``bench/metrics/``.

A reader gets a :class:`Context`: the trace of part of the window, the
``decode_loop`` calls made while it was traced (what they admitted and
decoded), the configuration's sizes and cost functions, and the chip's
peaks.  Each function returns ``None`` where the trace holds nothing to
read, and the metric is then left out of the result line.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence

from harness.loadgen import Call
from harness.tracefile import TraceData


@dataclasses.dataclass
class Context:
    cfg: dict
    k: int
    chunk: int
    costs: object
    peaks: dict
    trace: TraceData
    calls: List[Call]


def _floor_s(ctx: Context, flops: float, nbytes: float) -> float:
    """The least time the chip could take: operations over peak or
    bytes over bandwidth, whichever is longer."""
    return max(flops / ctx.peaks["bf16_flop_s"],
               nbytes / ctx.peaks["hbm_byte_s"])


def chunks(ctx: Context):
    """(offset, valid) of every prompt chunk the traced calls ran."""
    return [(off, min(ctx.chunk, plen - off))
            for call in ctx.calls for plen in call.admits
            for off in range(0, plen, ctx.chunk)]


def steps(ctx: Context):
    """Per decode step of the traced calls, the context length of each
    active row."""
    out = []
    for call in ctx.calls:
        for j in range(ctx.k if call.rows else 0):
            out.append([c + j for c, active in call.rows if j < active])
    return out


def prefill_chunk_ms(ctx: Context, modules: Sequence[str]
                     ) -> Optional[float]:
    n, s = ctx.trace.module_time(modules)
    return 1e3 * s / n if n else None


def decode_step_ms(ctx: Context, modules: Sequence[str]) -> Optional[float]:
    n, s = ctx.trace.module_time(modules)
    return 1e3 * s / (n * ctx.k) if n else None


def prefill_roofline(ctx: Context, modules: Sequence[str]
                     ) -> Optional[float]:
    """Share (%) of the chunks' device time that their roofline needs."""
    n, s = ctx.trace.module_time(modules)
    work = chunks(ctx)
    if not n or len(work) != n:
        return None
    floor = sum(_floor_s(ctx, *ctx.costs.prefill_chunk(ctx.cfg, off, v))
                for off, v in work)
    return 100.0 * floor / s


def decode_roofline(ctx: Context, modules: Sequence[str]) -> Optional[float]:
    """Share (%) of the fused loops' device time that the roofline of
    their active rows' steps needs (a step with no active row needs
    nothing)."""
    n, s = ctx.trace.module_time(modules)
    if not n or n != sum(1 for c in ctx.calls if c.rows):
        return None
    floor = sum(_floor_s(ctx, *ctx.costs.decode_step(ctx.cfg, lives))
                for lives in steps(ctx) if lives)
    return 100.0 * floor / s


def step_mfu(ctx: Context) -> Optional[float]:
    """Operations the traced prompt and output tokens need, over the
    device's busy time at the bf16 peak (%)."""
    busy = ctx.trace.busy_s * ctx.trace.chips
    if busy <= 0:
        return None
    flops = sum(ctx.costs.prefill_chunk(ctx.cfg, off, v)[0]
                for off, v in chunks(ctx))
    flops += sum(ctx.costs.decode_step(ctx.cfg, lives)[0]
                 for lives in steps(ctx) if lives)
    if not flops:
        return None
    return 100.0 * flops / (busy * ctx.peaks["bf16_flop_s"])


def idle_share(ctx: Context) -> Optional[float]:
    """Share (%) of the traced window in which no operation ran."""
    t = ctx.trace
    if t.window_s <= 0 or t.busy_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
