"""The reduction from trace events to per-layer metrics, on hand-made
events and calls (``test_tracefile`` reads a trace recorded on a chip)."""

import json
import os

import pytest

from costs import gptneox
from harness import layers, tracefile
from harness.loadgen import Call
from harness.tracefile import Event, TraceData

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(BENCH, "configs", "gptneox-1b.json")) as f:
    NEOX = json.load(f)["model"]
V5E = {"bf16_flop_s": 197e12, "hbm_byte_s": 819e9}


def test_union_and_self_times():
    ops = [Event("%while.1 = f32[2]{0} while(...)", 0.0, 10.0),
           Event("%fusion.2 = bf16[4,8]{1,0} fusion(...)", 1.0, 4.0),
           Event("%copy.3 = f32[2]{0} copy(...)", 5.0, 6.0),
           Event("%fusion.4 = bf16[4]{0} fusion(...)", 12.0, 13.0)]
    mods = [Event("jit_loop(1)", 0.0, 10.5), Event("jit_other(2)", 11.5, 14.0)]
    assert tracefile._union(ops) == [(0.0, 10.0), (12.0, 13.0)]
    st = tracefile._self_times(ops, mods)
    assert st == {"loop/while.1 f32[2]": 6.0, "loop/fusion.2 bf16[4,8]": 3.0,
                  "loop/copy.3 f32[2]": 1.0, "other/fusion.4 bf16[4]": 1.0}


def test_idle_gaps_named_by_innermost_host_event():
    busy = [(1.0, 2.0), (5.0, 6.0)]
    host = [Event("decode_loop", 0.0, 6.5),
            Event("$engine.py:318 _host_read", 2.0, 4.0),
            Event("wait_for_arrival", 6.5, 9.0)]
    # holes: 0-1 in decode_loop, 2-5 in the host read, 6-6.5 in
    # decode_loop and 6.5-9 waiting (one hole, named at its midpoint)
    assert tracefile._gaps(busy, host) == [
        ("decode_loop/$engine.py:318 _host_read", 3.0),
        ("wait_for_arrival", 3.0),
        ("decode_loop", 1.0)]


def _ctx(modules, calls, busy=0.5, window=1.0):
    data = TraceData(window_s=window, busy_s=busy, chips=1,
                     modules=modules, op_self={}, gaps=[])
    return layers.Context(cfg=NEOX, k=4, chunk=32, costs=gptneox,
                          peaks=V5E, trace=data, calls=calls)


def test_layer_reads_from_modules_and_calls():
    calls = [Call(admits=[40], rows=[(41, 4), (100, 2)])]
    ctx = _ctx({"jit_prefill_chunk": [0.010, 0.012], "jit_loop": [0.040]},
               calls)
    assert layers.prefill_chunk_ms(ctx, ["jit_prefill_chunk"]) == \
        pytest.approx(11.0)
    assert layers.decode_step_ms(ctx, ["jit_loop"]) == pytest.approx(10.0)
    assert layers.chunks(ctx) == [(0, 32), (32, 8)]
    assert layers.steps(ctx) == [[41, 100], [42, 101], [43], [44]]
    floor = sum(max(f / 197e12, b / 819e9) for f, b in
                (gptneox.prefill_chunk(NEOX, 0, 32),
                 gptneox.prefill_chunk(NEOX, 32, 8)))
    assert layers.prefill_roofline(ctx, ["jit_prefill_chunk"]) == \
        pytest.approx(100 * floor / 0.022)
    share = layers.decode_roofline(ctx, ["jit_loop"])
    assert 0 < share < 100
    assert layers.idle_share(ctx) == pytest.approx(50.0)
    assert 0 < layers.step_mfu(ctx) < 100


def test_nothing_to_read_gives_nothing():
    ctx = _ctx({}, [], busy=0.0)
    for read in (layers.prefill_chunk_ms, layers.decode_step_ms,
                 layers.prefill_roofline, layers.decode_roofline):
        assert read(ctx, ["jit_loop"]) is None
    assert layers.step_mfu(ctx) is None
    assert layers.idle_share(ctx) is None
    # executions that do not match the calls recorded: no share
    ctx = _ctx({"jit_loop": [0.04, 0.04]},
               [Call(admits=[], rows=[(10, 4)])])
    assert layers.decode_roofline(ctx, ["jit_loop"]) is None


def test_every_per_layer_metric_finds_its_reader():
    """Readers are found by the metric's name up to its first dot, so
    ``.chat`` and ``.backlog`` metrics share one file."""
    from harness import cell

    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        names = [m["name"] for m in json.load(f)["per_layer"]]
    readers = {n: cell.metric_reader(n) for n in names}
    assert all(callable(r.read) for r in readers.values())
    assert (readers["decode_step_ms.chat"].__file__
            == readers["decode_step_ms.backlog"].__file__)
