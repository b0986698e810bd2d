"""End-to-end behaviour: train -> checkpoint -> restart -> serve on one
architecture, plus the fault-tolerance machinery (watchdog, heartbeat)."""

import dataclasses
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config, smoke_shape
from repro.data import make_stream
from repro.distributed import Heartbeat, StepWatchdog
from repro.models import build_model
from repro.optim import AdamWConfig, Schedule
from repro.serve import ServeEngine
from repro.train import (TrainLoopConfig, make_train_step, run_train_loop,
                         train_state_init)


def test_train_checkpoint_serve_pipeline(tmp_path, key):
    """The full lifecycle on CPU: train a reduced model, checkpoint,
    restore into a fresh process-state, serve batched requests."""
    cfg = dataclasses.replace(get_config("gptneox-1b").reduced(),
                              n_layers=2)
    model = build_model(cfg)
    opt = AdamWConfig(schedule=Schedule(peak_lr=5e-3, warmup_steps=5,
                                        decay_steps=60))
    state = train_state_init(model, opt, key)
    stream = make_stream(cfg, smoke_shape("train"))
    step = jax.jit(make_train_step(model, opt))
    ckdir = str(tmp_path / "ck")
    state, history = run_train_loop(
        step, state, stream,
        TrainLoopConfig(total_steps=30, checkpoint_every=15,
                        checkpoint_dir=ckdir, log_every=10,
                        async_checkpoint=False))
    assert history[-1]["loss"] < history[0]["loss"]

    # restore into a new state and serve
    from repro.checkpoint import Checkpointer
    ck = Checkpointer(ckdir)
    like = train_state_init(model, opt, key)
    restored, step_no = ck.restore_latest(like=like)
    assert step_no == 30
    eng = ServeEngine(model, restored["params"], batch=2, max_seq=64)
    eng.submit([1, 2, 3], max_new_tokens=4)
    eng.submit([4, 5, 6, 7], max_new_tokens=4)
    results = eng.run()
    assert len(results) == 2
    assert all(len(r.tokens) == 4 for r in results)


def test_watchdog_flags_straggler():
    events = []
    wd = StepWatchdog(deadline_factor=5.0,
                      on_straggler=lambda e: events.append(e))
    for i in range(6):
        wd.start_step(i)
        time.sleep(0.002)
        wd.end_step()
    wd.start_step(6)
    time.sleep(0.1)                      # 50x the median: a straggler
    ev = wd.end_step()
    assert ev is not None and events and events[0].step == 6


def test_heartbeat(tmp_path):
    hb = Heartbeat(str(tmp_path), process_index=3)
    hb.beat(42)
    step, ts = hb.last()
    assert step == 42
    assert not hb.stale(timeout_s=60)
    assert hb.stale(timeout_s=0)


@pytest.mark.slow
def test_dryrun_single_cell_subprocess(tmp_path):
    """The real multi-pod dry-run, smallest cell, in a subprocess (it
    forces 512 host devices)."""
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun",
         "--arch", "seamless-m4t-medium", "--shape", "decode_32k",
         "--out", str(tmp_path)],
        env=env, cwd=os.path.dirname(os.path.dirname(__file__)),
        capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-2000:]
    import json, glob
    files = glob.glob(str(tmp_path / "*.json"))
    assert len(files) == 1
    d = json.load(open(files[0]))
    assert d["flops_per_device"] > 0
    assert d["roofline"]["dominant"] in ("compute", "memory", "collective")
