"""Run one cell of ``BENCHMARK.json`` once.

Everything that belongs to one configuration, traffic mix or per-layer
metric is found by its name:

* ``bench/configs/<config>.json``: the sizes (source keys), the
  program's architecture to serve them with, the engine settings, the
  reference family and the limit of the comparison;
* ``bench/reference/<family>.py`` and ``bench/costs/<family>.py``: the
  plain reference (and the weights, made from the seed) and the counts
  of operations and bytes;
* ``bench/traffic/<traffic>.json``: the mix, for ``harness.traffic``;
* ``bench/metrics/<stem>.py``: one reader per per-layer metric, named
  by the metric's name up to its first dot: the suffix (``.chat``,
  ``.backlog``) says which end-to-end metric a number moves, not how it
  is read, so ``decode_step_ms.chat`` and ``decode_step_ms.backlog``
  share ``decode_step_ms.py``.
"""

from __future__ import annotations

import dataclasses
import functools
import gc
import importlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time

import jax
import numpy as np

from harness import check, e2e, traffic
from harness.loadgen import LoadGenerator
from harness.peaks import peaks

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
TRACE_START = 0.25        # share of the window before the trace starts
TRACE_SECONDS = 4.0       # traced stretch of the window


class NoChip(RuntimeError):
    pass


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def workload(name: str, root: str = ROOT):
    bm = load_json(os.path.join(root, "BENCHMARK.json"))
    for wl in bm["workloads"]:
        if wl["name"] == name:
            return bm, wl
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json")


def config(bm: dict, name: str, root: str = ROOT) -> dict:
    for c in bm["configs"]:
        if c["name"] == name:
            return load_json(os.path.join(root, c["file"]))
    raise SystemExit(f"no configuration {name!r} in BENCHMARK.json")


def family(cfg: dict):
    fam = cfg["family"]
    return (importlib.import_module(f"reference.{fam}"),
            importlib.import_module(f"costs.{fam}"))


def metric_reader(name: str):
    stem = name.split(".")[0]
    path = os.path.join(BENCH, "metrics", f"{stem}.py")
    spec = importlib.util.spec_from_file_location(f"metric_{stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(bm: dict, wl: dict, kind: str) -> list:
    """The ``end_to_end`` or ``per_layer`` entries this cell reports."""
    return [m for m in bm[kind]
            if wl["name"] in m.get("workloads", [wl["name"]])]


def seed_key(seed: int) -> jax.Array:
    """A key from all the bits of a seed of up to 64 bits."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def enable_compile_cache(root: str) -> None:
    """JAX's persistent cache at a fixed path in the checkout, unless
    ``JAX_COMPILATION_CACHE_DIR`` names one; every program is kept, so a
    second run compiles nothing."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(root, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def build(cfg: dict, seed: int, marks: list):
    """(engine, weights): the program's engine over weights made here,
    from the seed, in one jitted call on the device.  Appends the time
    each phase ended to ``marks``."""
    from repro.configs import get_config
    from repro.models import build_model
    from repro.serve import ServeEngine

    ref, _ = family(cfg)
    prog = cfg["program"]
    arch = dataclasses.replace(get_config(prog["arch"]),
                               **prog.get("overrides", {}))
    want = ref.arch_fields(cfg["model"])
    got = {k: getattr(arch, k) for k in want}
    if got != want:
        raise SystemExit(f"the program's {prog['arch']} is not the "
                         f"configuration: {got} != {want}")
    model = build_model(arch)
    params = jax.jit(functools.partial(ref.init_weights, cfg["model"]))(
        seed_key(seed))
    jax.block_until_ready(params)
    marks.append(("weights", time.monotonic()))
    layout = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    shapes = lambda t: jax.tree.map(lambda a: (a.shape, str(a.dtype)), t)
    if shapes(params) != shapes(layout):
        raise SystemExit("the reference's weights do not have the "
                         "program's layout")
    eng = cfg["engine"]
    engine = ServeEngine(model, params, batch=eng["batch"],
                         max_seq=eng["max_seq"],
                         decode_block=eng["decode_block"],
                         prefill_chunk=eng["prefill_chunk"],
                         kv_format=eng.get("kv_format"))
    jax.block_until_ready(engine.cache)
    marks.append(("engine", time.monotonic()))
    return engine, params


def warm_up(engine, vocab: int) -> None:
    """Compile what the window runs: clear_slot, prefill_chunk, the
    admission write and the fused loop at K; then clear all state."""
    k, chunk = engine.decode_block, engine.prefill_chunk
    prompt = np.random.default_rng(0).integers(0, vocab, chunk + 1)
    engine.submit(prompt.tolist(), max_new_tokens=k + 2)
    engine.decode_loop(k)
    engine.decode_loop(k)
    engine.reset()
    jax.block_until_ready(engine.cache)


def check_devices(chips: int, require_tpu: bool):
    devices = jax.devices()
    dev = devices[0]
    if require_tpu and dev.platform != "tpu":
        raise NoChip(f"no TPU: JAX's first device is {dev.platform} "
                     f"({dev.device_kind})")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees "
                     f"{len(devices)}")
    return dev, devices


def run(name: str, seed: int, seconds: float, trace: bool,
        t_start: float, root: str = ROOT, require_tpu: bool = True,
        control: bool = False) -> dict:
    """One run of cell ``name``; returns the result line's object.
    ``control`` adds the control's reading (``bench/control.py``)."""
    from repro.analysis.sanitize import CompileCounter

    bm, wl = workload(name, root)
    dev, devices = check_devices(wl["chips"], require_tpu)
    marks = [("start", t_start), ("devices", time.monotonic())]
    enable_compile_cache(root)
    cfg = config(bm, wl["config"], root)
    mix = traffic.load_mix(os.path.join(root, "bench", "traffic",
                                        f"{wl['traffic']}.json"))
    ref, costs = family(cfg)
    engine, params = build(cfg, seed, marks)
    vocab, eng = cfg["model"]["vocab_size"], cfg["engine"]
    requests = traffic.generate(mix, seconds, seed, vocab, eng["max_seq"])
    warm_up(engine, vocab)
    marks.append(("warm-up", time.monotonic()))
    setup = marks[-1][1] - t_start

    trace_dir, tracer = None, None
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        start = TRACE_START * seconds
        tracer = (start, start + min(TRACE_SECONDS, 0.5 * seconds),
                  lambda: jax.profiler.start_trace(trace_dir),
                  jax.profiler.stop_trace)
    gen = LoadGenerator(engine, eng["decode_block"])
    with CompileCounter() as compiles:
        win = gen.run(requests, seconds, trace=tracer)
    gen.settle(win, drain=mix["arrival"] != "backlog")
    stats = dev.memory_stats() or {}
    late = sorted(win.lateness_s) or [0.0]
    print(f"[bench] {name} seed {seed}: {len(win.tracks)} requests, "
          f"{win.tokens_in_window} tokens in {win.seconds:.3f} s; "
          f"generator lateness p50 {1e3 * late[len(late) // 2]:.3f} ms "
          f"max {1e3 * late[-1]:.3f} ms; compiles in window "
          f"{compiles.count}; {e2e.summary(win)}; set-up "
          + ", ".join(f"{n} {b - a:.2f} s" for (_, a), (n, b)
                      in zip(marks, marks[1:])), flush=True)

    # a backlog's requests still queued at the close were never due; every
    # other request fails if it did not finish (or, in a backlog, was not
    # cut at the close) or was delivered fewer tokens than its steps owe
    tracks = list(win.tracks.values())
    if mix["arrival"] == "backlog":
        counted = [t for t in tracks if t.status is not None]
    else:
        counted = tracks
    failed = sum(t.status not in ("ok", "cut") or t.short for t in counted)
    result = {"correct": False, "attempted": len(counted),
              "failed": failed, "metrics": {},
              "device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": len(devices),
                         "memory_peak_bytes": stats.get(
                             "peak_bytes_in_use")}}
    if trace:
        from harness import layers, tracefile
        data = tracefile.read(trace_dir, win.trace_span)
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx = layers.Context(cfg=cfg["model"], k=eng["decode_block"],
                             chunk=engine.prefill_chunk, costs=costs,
                             peaks=peaks(dev.device_kind), trace=data,
                             calls=[c for c in win.calls if c.traced])
        for m in cell_metrics(bm, wl, "per_layer"):
            value = metric_reader(m["name"]).read(ctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        result["device"]["busy_s"] = data.busy_s
        result["device"]["window_s"] = data.window_s
        result["breakdown"] = data.breakdown()
    else:
        for m in cell_metrics(bm, wl, "end_to_end"):
            result["metrics"][m["name"]] = {
                "value": e2e.metric(m["name"])(win, setup),
                "unit": m["unit"]}

    # the comparison runs on the program's output alone: free its state
    picked = check.sample(tracks, seed)
    del engine, gen, win
    gc.collect()
    comparison = check.Comparison(ref, cfg["model"], eng["max_seq"])
    gap = comparison.max_gap(params, picked) if picked else None
    limit = cfg["limits"]["max_logit_gap"]
    served = sum(t.tokens for t in picked)
    print(f"[check] compared {len(picked)} requests from "
          f"{len({t.slot for t in picked})} slots", file=sys.stderr)
    result["correct"] = bool(picked) and failed == 0 and gap <= limit
    result["checks"] = {
        "max_logit_gap": {"value": gap, "limit": limit},
        "failed_requests": {"value": failed, "limit": 0},
        "served_tokens_compared": {"value": served, "limit": 1}}
    if control:
        result["checks"]["control_gap"] = {
            "value": comparison.max_control_gap(params, picked),
            "limit": limit}
    for k, v in result["checks"].items():
        comp = {"served_tokens_compared": ">=", "control_gap": ">"}.get(
            k, "<=")
        print(f"[check] {k} {v['value']} (must be {comp} {v['limit']})",
              file=sys.stderr)
    return result
