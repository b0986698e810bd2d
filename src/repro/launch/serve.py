"""Serving launcher: batched engine with continuous batching.

    PYTHONPATH=src python -m repro.launch.serve --arch gptneox-1b --reduced \
        --requests 8 --batch 4 --max-new 16 --precision float8_e4m3fn

Mesh-native serving: ``--mesh 2x2`` shards the engine over a
('data', 'model') device mesh (``--mesh 4`` = pure TP on ('model',)).
On a CPU host, pair it with ``--fake-devices N`` (must come before jax
touches a backend, which is why this launcher parses args before
importing anything that initializes jax).

Traffic mode: ``--scenario poisson|bursty|ramp`` replays a seeded
arrival trace (``repro.serve.traffic``) instead of pre-enqueueing
``--requests`` prompts, reporting TTFT/per-token tails, goodput, and
exact status accounting.  ``--queue-limit``/``--policy``/
``--deadline-ms`` bound the admission queue in either mode:

    PYTHONPATH=src python -m repro.launch.serve --arch gptneox-1b \
        --reduced --scenario ramp --queue-limit 4 --policy shed_oldest \
        --deadline-ms 500
"""

from __future__ import annotations

import argparse
import os
import time


def make_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gptneox-1b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=256)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--decode-block", type=int, default=16,
                    help="decode steps fused per dispatch (1 = per-token)")
    ap.add_argument("--prefill-chunk", type=int, default=32,
                    help="prompt tokens per pooled-prefill dispatch")
    ap.add_argument("--precision", default="bfloat16",
                    help="float32|bfloat16|float8_e4m3fn|float8_e5m2|"
                         "float6_e2m3fn|float6_e3m2fn|float4_e2m1fn")
    ap.add_argument("--kv-format", default=None,
                    help="quantized KV storage, e.g. float8_e4m3fn or "
                         "float4_e2m1fn; omit for compute-dtype KV")
    ap.add_argument("--seed", type=int, default=0,
                    help="parameter init seed")
    ap.add_argument("--mesh", default=None,
                    help="serving mesh shape, e.g. 2x2 (data x model) "
                         "or 4 (pure TP); omit for single-device")
    ap.add_argument("--fake-devices", type=int, default=0,
                    help="XLA host-platform fake device count (CPU mesh "
                         "smoke runs); pins the CPU platform")
    ap.add_argument("--scenario", default=None,
                    choices=["poisson", "bursty", "ramp"],
                    help="replay a seeded arrival trace instead of "
                         "pre-enqueueing --requests prompts")
    ap.add_argument("--scenario-seed", type=int, default=0)
    ap.add_argument("--queue-limit", type=int, default=None,
                    help="bound the admission queue (queued requests; "
                         "in-flight slots are bounded by --batch)")
    ap.add_argument("--policy", default="reject",
                    choices=["reject", "shed_oldest", "block"],
                    help="what a full queue does to the next submit")
    ap.add_argument("--scheduler", default="fifo",
                    choices=["fifo", "spf"])
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request deadline from submit; expired "
                         "requests finish as deadline_exceeded")
    return ap


def build_engine(args: argparse.Namespace):
    """The engine the CLI serves with, built from parsed arguments:
    random weights from ``--seed``, quantized to ``--precision``, on the
    ``--mesh`` (one device when omitted).  ``chip_smoke.py`` builds its
    engines through here too."""
    import jax

    from repro.configs import get_config
    from repro.launch.mesh import make_serving_mesh
    from repro.models import build_model
    from repro.serve import AdmissionConfig, ServeEngine, quantize_params

    mesh = make_serving_mesh(args.mesh)
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build_model(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(args.seed))
    params, qstats = quantize_params(params, args.precision)
    print(f"[serve] {cfg.name} precision={args.precision} "
          f"kv_format={args.kv_format} "
          f"quantized_bytes={qstats['quantized_bytes']/2**20:.1f} MiB "
          f"rel-mse={qstats['mse']:.2e}"
          + (f" mesh={dict(mesh.shape)}" if mesh is not None else ""))

    admission = None
    if (args.queue_limit is not None or args.deadline_ms is not None
            or args.policy != "reject" or args.scheduler != "fifo"):
        admission = AdmissionConfig(
            queue_limit=args.queue_limit, policy=args.policy,
            scheduler=args.scheduler, deadline_ms=args.deadline_ms)
    return ServeEngine(model, params, batch=args.batch,
                       max_seq=args.max_seq,
                       temperature=args.temperature,
                       kv_format=args.kv_format,
                       decode_block=args.decode_block,
                       prefill_chunk=args.prefill_chunk,
                       mesh=mesh, admission=admission)


def main() -> None:
    args = make_parser().parse_args()
    if args.fake_devices:
        # a simulated mesh lives on host devices: never claim a chip
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.fake_devices} "
            + os.environ.get("XLA_FLAGS", ""))

    import jax

    from repro import compat
    from repro.serve import replay
    from repro.serve.traffic import TRACES

    compat.enable_compile_cache()
    engine = build_engine(args)
    cfg = engine.model.cfg

    if args.scenario:
        trace_args = {
            "poisson": dict(n=args.requests, rate=200.0),
            "bursty": dict(n_bursts=max(args.requests // 8, 1),
                           burst_size=8, gap_s=0.25),
            "ramp": dict(n=args.requests, rate0=5.0, rate1=400.0),
        }[args.scenario]
        sc = TRACES[args.scenario](
            vocab_size=cfg.vocab_size, seed=args.scenario_seed,
            deadline_ms=args.deadline_ms, **trace_args)
        rep = replay(engine, sc, k=args.decode_block)
        print(f"[serve] scenario={rep.scenario} policy={rep.policy}/"
              f"{rep.scheduler} K={rep.k} submitted={rep.submitted} "
              f"by_status={rep.by_status}")

        def _ms(x):
            return "-" if x is None else f"{1e3 * x:.1f}ms"
        print(f"[serve] goodput={rep.goodput_tok_s:.1f} tok/s "
              f"ttft p50/p99={_ms(rep.ttft_p50)}/{_ms(rep.ttft_p99)} "
              f"tpt p50/p99={_ms(rep.tpt_p50)}/{_ms(rep.tpt_p99)} "
              f"accounting_ok={rep.accounting_ok}")
        if not rep.accounting_ok:
            raise SystemExit("[serve] accounting identity violated")
        return

    key = jax.random.PRNGKey(1)
    for i in range(args.requests):
        key, sub = jax.random.split(key)
        prompt = jax.random.randint(
            sub, (args.prompt_len,), 0, cfg.vocab_size).tolist()
        engine.submit(prompt, max_new_tokens=args.max_new)

    t0 = time.perf_counter()
    results = engine.run()
    dt = time.perf_counter() - t0
    n_tok = sum(len(r.tokens) for r in results)
    print(f"[serve] {len(results)} requests, {n_tok} tokens in {dt:.2f}s "
          f"({n_tok/dt:.1f} tok/s)")
    for r in results[:3]:
        print(f"  req {r.request_id}: {r.tokens[:12]}...")


if __name__ == "__main__":
    main()
