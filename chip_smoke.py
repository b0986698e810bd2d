"""Chip smoke test: serve gptneox-1b at its published widths on a TPU.

    python3 chip_smoke.py              # one chip
    python3 chip_smoke.py --mesh 2x2   # four chips: mesh engine only

Drives ``repro.serve.ServeEngine`` through the serving launcher's own
builder (``repro.launch.serve.build_engine``) with random weights from
``--seed`` and checks what comes out.  Phases, in order:

* device  - the first device must be a TPU; otherwise exit non-zero
            before building any model.  Prints the JAX version, the
            device kind and count, and the capability report, and
            requires natively compiled (Mosaic) kernels.
* serve   - 16 requests (prompt lengths 256-1024, 64 new tokens) on an
            engine with batch 8, max_seq 2048, decode_block 16.  Every
            request must end "ok" with 64 in-vocab tokens; each first
            token must match the argmax of a plain ``model.forward`` over
            its prompt unless the reference's top two logits are within
            TIE_MARGIN; after ``reset()`` an identical second run must
            compile nothing and give the same tokens.
* packed KV - the serve phase again with fp4 (e2m1) KV storage.
* kernels - ``flash_decode_quant`` (fp8, fp4) and ``qmatmul_packed``
            (fp4) at gptneox-1b widths with ``interpret=False``, each
            against its plain-jnp reference.
* mesh    - only with ``--mesh 2x2``: the same requests on a 2x2
            ``ServeEngine(mesh=...)`` and on an unsharded engine on one
            chip of the same process; statuses, first tokens, and the
            bytes in use on every chip.

Timings are printed as smoke figures, not benchmark numbers.  Any failed
check raises.  The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

ARCH = "gptneox-1b"
# published widths of GPT-NeoX ~1B (configs/gptneox_1b.py)
PUBLISHED = dict(n_layers=16, d_model=2048, n_heads=16, n_kv_heads=16,
                 head_dim=128, d_ff=8192, vocab_size=50432,
                 param_dtype="bfloat16", compute_dtype="bfloat16")
PARAMS_RANGE = (1.0e9, 1.02e9)
BATCH, MAX_SEQ, DECODE_BLOCK = 8, 2048, 16
N_REQUESTS, PROMPT_LO, PROMPT_HI, MAX_NEW = 16, 256, 1024, 64
PACKED_KV = "float4_e2m1fn"
# first-token tie rule, in logits (which have std ~1 at this init): the
# served path (chunked prefill into the slot cache) and the reference
# (one full-prompt forward) both compute in bf16, in different orders;
# fp4 KV adds its quantization error.  On a CPU host, at full width, the
# served last-prompt logits differ from the reference's by at most 0.04
# (bf16 KV) and 0.3 (fp4 KV).  Where the reference's top two logits are
# closer than the margin, any token within the margin of the top is a
# correct answer.
TIE_MARGIN = {None: 0.15, PACKED_KV: 1.0}
# kernel checks: max |kernel - reference| / max |reference|
KERNEL_RTOL = 1e-2


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise RuntimeError(f"[smoke] FAILED: {msg}")


def engine_argv(seed: int, kv_format=None, mesh=None) -> list:
    """The launcher command line this smoke serves with."""
    argv = ["--arch", ARCH, "--batch", str(BATCH), "--max-seq",
            str(MAX_SEQ), "--decode-block", str(DECODE_BLOCK),
            "--max-new", str(MAX_NEW), "--seed", str(seed)]
    if kv_format:
        argv += ["--kv-format", kv_format]
    if mesh:
        argv += ["--mesh", mesh]
    return argv


def make_prompts(seed: int, vocab: int):
    import numpy as np

    rng = np.random.default_rng(seed)
    lens = rng.integers(PROMPT_LO, PROMPT_HI + 1, size=N_REQUESTS)
    return [rng.integers(0, vocab, size=int(n)).tolist() for n in lens]


def build(argv):
    """(engine, set-up seconds) through the launcher's builder."""
    import jax

    from repro.launch import serve

    t0 = time.perf_counter()
    engine = serve.build_engine(serve.make_parser().parse_args(argv))
    jax.block_until_ready((engine.params, engine.cache))
    return engine, time.perf_counter() - t0


def check_widths(engine) -> None:
    import jax

    cfg = engine.model.cfg
    got = {k: getattr(cfg, k) for k in PUBLISHED}
    check(got == PUBLISHED, f"{cfg.name} widths {got} != {PUBLISHED}")
    n = sum(x.size for x in jax.tree.leaves(engine.params))
    print(f"[smoke] model {cfg.name}: {n} params")
    check(PARAMS_RANGE[0] <= n <= PARAMS_RANGE[1],
          f"{n} params outside {PARAMS_RANGE}")


def serve_once(engine, prompts):
    """Submit every prompt, run to completion: (results, seconds)."""
    for p in prompts:
        engine.submit(p, max_new_tokens=MAX_NEW)
    t0 = time.perf_counter()
    results = engine.run()
    return results, time.perf_counter() - t0


def check_results(results, vocab: int, label: str) -> None:
    check(len(results) == N_REQUESTS,
          f"{label}: {len(results)} results for {N_REQUESTS} requests")
    for r in results:
        check(r.status == "ok", f"{label}: request {r.request_id} "
              f"ended {r.status!r}")
        check(len(r.tokens) == MAX_NEW, f"{label}: request "
              f"{r.request_id} has {len(r.tokens)} tokens, not {MAX_NEW}")
        check(all(0 <= t < vocab for t in r.tokens),
              f"{label}: request {r.request_id} has ids outside the vocab")


def reference_logits(engine, prompts):
    """Last-position fp32 logits of a plain ``model.forward`` over each
    prompt (right-padded to PROMPT_HI: causal, so padding is unseen)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    model = engine.model

    @jax.jit
    def last_logits(params, tokens, n):
        logits, _ = model.forward(params, {"tokens": tokens[None]})
        return jax.lax.dynamic_index_in_dim(logits[0], n - 1,
                                            keepdims=False)

    out = []
    for p in prompts:
        toks = np.zeros((PROMPT_HI,), np.int32)
        toks[:len(p)] = p
        out.append(np.asarray(last_logits(engine.params, jnp.asarray(toks),
                                          jnp.int32(len(p))),
                              np.float32))
    return np.stack(out)


def check_first_tokens(results, ref, margin: float, label: str) -> int:
    """Each first token must be the reference argmax, or, where the
    reference's top two logits lie within ``margin`` (a tie), a token
    whose reference logit is within ``margin`` of the top.  Returns the
    number of ties."""
    import numpy as np

    ties = 0
    for r, row in zip(results, ref):
        top2 = np.sort(row)[-2:]
        tok = r.tokens[0]
        if top2[1] - top2[0] < margin:
            ties += 1
            check(row[tok] >= top2[1] - margin,
                  f"{label}: request {r.request_id} first token {tok} "
                  f"scores {row[tok]:.4f}, top {top2[1]:.4f} (tie)")
        else:
            check(tok == int(np.argmax(row)),
                  f"{label}: request {r.request_id} first token {tok} "
                  f"!= reference argmax {int(np.argmax(row))} "
                  f"(gap {top2[1] - top2[0]:.4f})")
    print(f"[smoke] {label}: first tokens agree with the reference "
          f"forward; ties (top-2 gap < {margin}) {ties}/{len(results)}")
    return ties


def serve_phase(seed: int, kv_format=None, ref=None):
    """One engine, two identical runs; returns the reference logits."""
    from repro.analysis.sanitize import CompileCounter

    label = f"serve[kv={kv_format or 'bf16'}]"
    engine, setup_s = build(engine_argv(seed, kv_format))
    check_widths(engine)
    vocab = engine.model.cfg.vocab_size
    prompts = make_prompts(seed, vocab)
    first, first_s = serve_once(engine, prompts)
    check_results(first, vocab, label)
    if ref is None:
        ref = reference_logits(engine, prompts)
    check_first_tokens(first, ref, TIE_MARGIN[kv_format], label)

    engine.reset()
    with CompileCounter() as compiles:
        second, wall_s = serve_once(engine, prompts)
    check(compiles.count == 0,
          f"{label}: second run compiled {compiles.count} executables")
    check([r.tokens for r in second] == [r.tokens for r in first],
          f"{label}: second run's tokens differ from the first's")
    n_tok = sum(len(r.tokens) for r in second)
    print(f"[smoke] {label}: second run compiled 0 executables, same "
          f"{n_tok} tokens")
    if kv_format:
        print(f"[smoke] {label}: kv bytes/element "
              f"{engine.kv_stats['bytes_per_elem']}")
    print(f"[smoke] smoke figure, not a benchmark: {label} set-up "
          f"(init + place) {setup_s:.2f} s")
    print(f"[smoke] smoke figure, not a benchmark: {label} first run "
          f"(compile + serve) {first_s:.2f} s")
    print(f"[smoke] smoke figure, not a benchmark: {label} second run "
          f"wall {wall_s:.3f} s")
    print(f"[smoke] smoke figure, not a benchmark: {label} second run "
          f"{n_tok / wall_s:.1f} tok/s")
    return ref


def _rel_err(got, want) -> float:
    import numpy as np

    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.abs(got - want).max() / np.abs(want).max())


def kernel_phase(seed: int) -> None:
    """Mosaic-compiled kernels at gptneox-1b widths vs references."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ref as kref
    from repro.kernels.flash_decode import flash_decode_quant_bhd
    from repro.kernels.ops import pack_for_qmatmul, quantize_for_qmatmul
    from repro.kernels.qmatmul import qmatmul_packed_mkn
    from repro.models import attention as A

    cfg = PUBLISHED
    b, S, h, d = BATCH, MAX_SEQ, cfg["n_heads"], cfg["head_dim"]
    key = jax.random.PRNGKey(seed)
    kq, kk, kv, kp, kx, kw = jax.random.split(key, 6)
    q = jax.random.normal(kq, (b, 1, h, d), jnp.float32)
    kd = jax.random.normal(kk, (b, S, h, d), jnp.float32)
    vd = jax.random.normal(kv, (b, S, h, d), jnp.float32)
    pos = jax.random.randint(kp, (b,), S // 4, S, jnp.int32)
    t = lambda a: a.transpose(0, 2, 1, 3)
    for fmt in ("float8_e4m3fn", "float4_e2m1fn"):
        cache = A.cache_write_prefill(
            A.init_kv_cache(b, S, h, d, jnp.float32, kv_format=fmt),
            kd, vd, kv_format=fmt)
        got = flash_decode_quant_bhd(
            q[:, 0], t(cache["k_q"]), t(cache["k_s"]), t(cache["v_q"]),
            t(cache["v_s"]), cache["slot_pos"], pos, fmt=fmt,
            interpret=False)
        kc, vc = A.cache_kv(cache, fmt, d)
        with jax.default_matmul_precision("highest"):
            want = A.decode_attention(q, kc, vc, cache["slot_pos"], pos)
        err = _rel_err(got, want[:, 0])
        print(f"[smoke] kernel flash_decode_quant[{fmt}] b={b} S={S} "
              f"h={h} d={d}: rel err {err:.2e} (tol {KERNEL_RTOL})")
        check(err <= KERNEL_RTOL, f"flash_decode_quant[{fmt}] rel err "
              f"{err:.3e} > {KERNEL_RTOL}")

    fmt = "float4_e2m1fn"
    m, k, n = 128, cfg["d_model"], cfg["d_ff"]      # the MLP up-projection
    x = jax.random.normal(kx, (m, k), jnp.float32).astype(jnp.bfloat16)
    w = jax.random.normal(kw, (k, n), jnp.float32)
    pw, sc = pack_for_qmatmul(w, fmt)
    qw, _ = quantize_for_qmatmul(w, fmt)
    got = qmatmul_packed_mkn(x, pw, sc, fmt, interpret=False)
    with jax.default_matmul_precision("highest"):
        want = kref.qmatmul_ref(x, qw, sc)
    err = _rel_err(got, want)
    print(f"[smoke] kernel qmatmul_packed[{fmt}] m={m} k={k} n={n}: "
          f"rel err {err:.2e} (tol {KERNEL_RTOL})")
    check(err <= KERNEL_RTOL,
          f"qmatmul_packed[{fmt}] rel err {err:.3e} > {KERNEL_RTOL}")


def mesh_phase(seed: int, mesh: str) -> None:
    """The same requests on a mesh engine and on an unsharded engine."""
    import jax

    label = f"serve[mesh={mesh}]"
    engine, setup_s = build(engine_argv(seed, mesh=mesh))
    check_widths(engine)
    vocab = engine.model.cfg.vocab_size
    prompts = make_prompts(seed, vocab)
    sharded, first_s = serve_once(engine, prompts)
    check_results(sharded, vocab, label)
    in_use = [dv.memory_stats()["bytes_in_use"] for dv in jax.devices()]
    for dv, nbytes in zip(jax.devices(), in_use):
        print(f"[smoke] {label}: device {dv.id} bytes_in_use {nbytes}")
    check(min(in_use) >= max(in_use) / 4,
          f"{label}: a chip holds under a quarter of the fullest "
          f"({in_use})")
    print(f"[smoke] smoke figure, not a benchmark: {label} set-up "
          f"{setup_s:.2f} s, first run (compile + serve) {first_s:.2f} s")
    del engine
    gc.collect()

    single, _ = build(engine_argv(seed))
    alone, _ = serve_once(single, prompts)
    check_results(alone, vocab, "serve[one chip]")
    ref = reference_logits(single, prompts)
    for res, name in ((sharded, label), (alone, "serve[one chip]")):
        check_first_tokens(res, ref, TIE_MARGIN[None], name)
    same = sum(a.tokens == s.tokens for a, s in zip(alone, sharded))
    print(f"[smoke] {label}: {same}/{len(prompts)} requests give the "
          f"same {MAX_NEW} tokens on the mesh and on one chip")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="weights, prompts and kernel inputs")
    ap.add_argument("--mesh", default=None, choices=["2x2"],
                    help="run only the four-chip mesh phase")
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        sys.exit(f"[smoke] no TPU: JAX's first device is {dev.platform} "
                 f"({dev.device_kind}); nothing was run")
    need = 4 if args.mesh else 1
    if len(devices) < need:
        sys.exit(f"[smoke] --mesh {args.mesh} needs {need} chips, JAX "
                 f"sees {len(devices)}")

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro import compat

    cache_dir = compat.enable_compile_cache()
    rep = compat.report()
    print(f"[smoke] jax {jax.__version__}, device_kind {dev.device_kind!r}, "
          f"{len(devices)} device(s), compile cache {cache_dir}")
    print(rep)
    check(rep.pallas_mode == "native-mosaic",
          f"kernels would run as {rep.pallas_mode}, not native-mosaic")

    if args.mesh:
        mesh_phase(args.seed, args.mesh)
    else:
        ref = serve_phase(args.seed)
        gc.collect()
        serve_phase(args.seed, PACKED_KV, ref=ref)
        gc.collect()
        kernel_phase(args.seed)

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
