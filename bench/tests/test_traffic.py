import os

import numpy as np
import pytest

from harness import traffic

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MIXES = sorted(f[:-5] for f in os.listdir(os.path.join(BENCH, "traffic")))


def _mix(name):
    return traffic.load_mix(os.path.join(BENCH, "traffic", f"{name}.json"))


@pytest.mark.parametrize("name", MIXES)
def test_mix_is_deterministic_by_seed(name):
    mix = _mix(name)
    a = traffic.generate(mix, 30.0, 2**33 + 5, 50000, 2048)
    b = traffic.generate(mix, 30.0, 2**33 + 5, 50000, 2048)
    c = traffic.generate(mix, 30.0, 2**33 + 6, 50000, 2048)
    assert a == b
    assert a != c


@pytest.mark.parametrize("name", MIXES)
def test_lengths_inside_clips_and_same_schedule_every_seed(name):
    mix = _mix(name)
    runs = [traffic.generate(mix, 30.0, s, 50000, 2048) for s in (1, 2)]
    for reqs in runs:
        for r in reqs:
            p = mix["prompt"]
            assert p["min"] <= len(r.prompt) <= p["max"]
            assert mix["output"]["min"] <= r.max_new <= mix["output"]["max"]
            assert len(r.prompt) + r.max_new < 2048
            assert all(0 <= t < 50000 for t in r.prompt)
            assert 0.0 <= r.due < 30.0
    shape = [[(r.due, len(r.prompt), r.max_new) for r in reqs]
             for reqs in runs]
    assert shape[0] == shape[1]
    assert runs[0][0].prompt != runs[1][0].prompt


def test_arrival_shapes():
    base = {"prompt": {"median": 8, "sigma": 0.5, "min": 2, "max": 16},
            "output": {"median": 8, "sigma": 0.5, "min": 2, "max": 16}}
    backlog = traffic.generate(dict(base, arrival="backlog", requests=10),
                               5.0, 0, 100, 64)
    assert len(backlog) == 10 and all(r.due == 0.0 for r in backlog)
    poisson = traffic.generate(dict(base, arrival="poisson", rate_per_s=4.0),
                               10.0, 0, 100, 64)
    assert len(poisson) == 40
    dues = [r.due for r in poisson]
    assert dues == sorted(dues) and len(set(dues)) == 40
    bursts = traffic.generate(dict(base, arrival="bursts", rate_per_s=4.0,
                                   burst=8), 10.0, 0, 100, 64)
    assert len(bursts) == 40
    assert all(n == 8 for n in np.unique([r.due for r in bursts],
                                          return_counts=True)[1])


def test_clip_that_overruns_max_seq_is_refused():
    mix = {"arrival": "backlog", "requests": 4,
           "prompt": {"median": 60, "sigma": 0.1, "min": 50, "max": 70},
           "output": {"median": 60, "sigma": 0.1, "min": 50, "max": 70}}
    with pytest.raises(ValueError):
        traffic.generate(mix, 1.0, 0, 100, 64)
