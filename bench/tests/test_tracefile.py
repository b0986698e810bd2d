"""The trace reduction on a trace recorded on a v5e chip: the tiny
gptneox chat cell (``data/``), traced for half a second of its window.
The numbers asserted are the ones that run printed on the chip."""

import gzip
import os

import pytest

from harness import tracefile

TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                     "tiny-gptneox.chat.xplane.pb.gz")
WINDOW_S = 0.5194280649999996          # host clock, as the run measured it


@pytest.fixture(scope="module")
def data(tmp_path_factory):
    d = tmp_path_factory.mktemp("trace") / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    with gzip.open(TRACE) as src:
        (d / "run.xplane.pb").write_bytes(src.read())
    return tracefile.read(str(d.parents[2]), (0.0, WINDOW_S))


def test_busy_and_window(data):
    assert data.chips == 1
    assert data.window_s == WINDOW_S
    assert data.busy_s == pytest.approx(0.0016972390000007317, abs=1e-12)


def test_modules_by_function_name(data):
    n_prefill, s_prefill = data.module_time(["jit_prefill_chunk"])
    n_loop, s_loop = data.module_time(["jit_loop"])
    assert n_prefill > 0 and n_loop > 0
    assert 0 < s_prefill + s_loop <= data.busy_s
    assert data.module_time(["jit_no_such_module"]) == (0, 0.0)


def test_breakdown(data):
    bd = data.breakdown()
    assert len(bd["device_ops"]) == 10 and len(bd["idle_gaps"]) == 10
    name, secs = bd["device_ops"][0]
    assert name == "loop/fusion.191 bf16[4,4,16]"
    assert secs == pytest.approx(0.000126087, rel=1e-6)
    assert [s for _, s in bd["device_ops"]] == sorted(
        (s for _, s in bd["device_ops"]), reverse=True)
    # the longest idle stretch is the generator waiting for an arrival
    assert bd["idle_gaps"][0][0] == "wait_for_arrival/$time sleep"
    assert bd["idle_gaps"][0][1] == pytest.approx(0.257082447, rel=1e-6)
