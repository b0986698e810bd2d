"""Multi-device cases, run in a subprocess by ``tests/test_serve_sharded.py``
(and the expert-parallel MoE case by ``tests/test_moe.py``).

``--xla_force_host_platform_device_count`` only takes effect before the
first jax backend initialization, and ``tests/conftest.py`` imports jax
at collection time — so every case that needs 4 devices runs here, in a
fresh interpreter whose environment the pytest wrapper pins
(``XLA_FLAGS``, ``JAX_PLATFORMS=cpu``, ``PYTHONPATH=src``) before
Python starts.  Invoked by file path (tests/ is not a package):

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        PYTHONPATH=src python tests/sharded_cases.py greedy_attn

Each case prints ``CASE_OK <name>`` on success; any assertion failure
propagates as a nonzero exit the wrapper reports verbatim.
"""

import os
import sys

os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=4 "
                           + os.environ.get("XLA_FLAGS", ""))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np

import jax

from repro.configs import get_config
from repro.launch.mesh import make_serving_mesh
from repro.models import build_model
from repro.serve import ServeEngine

MESHES = (None, (2,), (2, 2))
PROMPTS = ([5, 7, 11, 13, 17], [3, 1, 4, 1, 5, 9, 2, 6], [2, 71, 82])


def _build(arch):
    cfg = get_config(arch).reduced()
    model = build_model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(0))


def _serve(model, params, mesh_shape, decode_block=4, prefill_chunk=4,
           seed=0, **kw):
    """One scripted serving run; returns the per-request token streams.

    A fresh numpy rng per call: both sides of an identity comparison
    must see bit-identical frames/patches (drawing from one shared rng
    sequentially would feed the two runs different inputs)."""
    cfg = model.cfg
    rng = np.random.default_rng(seed)
    eng = ServeEngine(model, params, batch=2, max_seq=64,
                      decode_block=decode_block,
                      prefill_chunk=prefill_chunk,
                      mesh=make_serving_mesh(mesh_shape), **kw)
    for p in PROMPTS:
        pk = {}
        if cfg.is_encoder_decoder:
            pk["frames"] = rng.standard_normal(
                (9, cfg.d_model)).astype(np.float32)
        if cfg.frontend == "vision":
            pk["patches"] = rng.standard_normal(
                (6, cfg.d_model)).astype(np.float32)
        eng.submit(p, max_new_tokens=10, **pk)
    return [r.tokens for r in
            sorted(eng.run(max_steps=200), key=lambda r: r.request_id)]


def _assert_identity(arch, **kw):
    """Greedy streams bit-identical across every mesh shape, plus
    fused-vs-per-step on the 2x2 mesh (decode_block=1 is the per-step
    dispatch pattern through the same scan body)."""
    cfg, model, params = _build(arch)
    ref = _serve(model, params, None, **kw)
    for shape in MESHES[1:]:
        got = _serve(model, params, shape, **kw)
        assert got == ref, (
            f"{arch} {kw}: mesh {shape} diverged from single-device "
            f"greedy decode:\n ref={ref}\n got={got}")
    per_step = _serve(model, params, (2, 2), decode_block=1, **kw)
    assert per_step == ref, (
        f"{arch} {kw}: per-step dispatch on 2x2 mesh diverged from the "
        f"fused loop:\n ref={ref}\n got={per_step}")


def greedy_attn():
    """Attention family across every KV storage format: the quantized
    ring pools (packed codes + e8m0 scales) shard and decode exactly."""
    for kv_format in (None, "float8_e4m3fn", "float4_e2m1fn"):
        _assert_identity("gptneox-1b", kv_format=kv_format)
    # true bit-packed weight storage through the sharded store
    _assert_identity("gptneox-1b", weight_format="float4_e2m1fn")


def greedy_ssm_hybrid():
    """SSM conv/state carries (sectioned layout) and the hybrid
    attn+SSM stack through the same sharded fused loop."""
    _assert_identity("mamba2-2.7b")
    _assert_identity("jamba-v0.1-52b")


def greedy_encdec_vlm():
    """Slot-resident enc_out + quantized cross-KV, and VLM patch-prefix
    admission, on the sharded pool."""
    _assert_identity("seamless-m4t-medium")
    _assert_identity("internvl2-2b")


def logits_and_prefill():
    """(a) sharded-vs-unsharded prefill logits agree numerically (same
    math, different partitioning — reassociated psums, so allclose not
    bit-equal); (b) chunked prefill into the sharded pool is
    chunk-size-invariant bit-exactly (greedy streams)."""
    cfg, model, params = _build("gptneox-1b")
    prompt = [5, 7, 11, 13, 17, 19, 23, 29]

    def prefill_logits(mesh_shape):
        eng = ServeEngine(model, params, batch=2, max_seq=64,
                          decode_block=4, prefill_chunk=4,
                          mesh=make_serving_mesh(mesh_shape))
        logits = eng._prefill_into_slot(
            0, type("R", (), {"prompt": prompt, "frames": None,
                              "patches": None})())
        return np.asarray(jax.device_get(logits))

    ref = prefill_logits(None)
    got = prefill_logits((2, 2))
    np.testing.assert_allclose(got, ref, atol=1e-5, rtol=1e-5)

    streams = [_serve(model, params, (2, 2), prefill_chunk=pc)
               for pc in (2, 4, 8)]
    assert streams[0] == streams[1] == streams[2], (
        f"sharded chunked prefill is chunk-size-dependent: {streams}")


def sanitize_sharded():
    """The mesh-native engine passes the full sanitizer stack on a real
    2x2 mesh: compile-exactly-once, zero implicit transfers in the
    fused loop, and no all-gather larger than the sample-point logits
    in the partitioned scan HLO."""
    from repro.analysis.sanitize import sanitize_serving

    mesh = make_serving_mesh((2, 2))
    for kw in ({}, {"kv_format": "float4_e2m1fn"}):
        rep = sanitize_serving(arch="gptneox-1b", mesh=mesh, **kw)
        assert rep["compiled_exactly_once"], rep
        assert rep["zero_implicit_loop_transfers"], rep
        assert rep["tokens_match_warmup"], rep
        assert rep["no_oversized_gathers"], rep
        assert rep["mesh"] == "2x2", rep


def spec_matrix():
    """Speculative decode (n-gram drafting) on the sharded pool stays
    bit-identical to the single-device NON-speculative engine — greedy
    across KV formats, plus a sampled stream (folded keys are position-
    keyed, so neither the mesh nor the draft/verify dispatch pattern
    may perturb them)."""
    from repro.serve import SpecConfig

    cfg, model, params = _build("gptneox-1b")
    spec = SpecConfig(draft_tokens=3, ngram_table=64)
    for kv_format in (None, "float8_e4m3fn"):
        ref = _serve(model, params, None, kv_format=kv_format)
        for shape in MESHES[1:]:
            got = _serve(model, params, shape, kv_format=kv_format,
                         spec=spec)
            assert got == ref, (
                f"spec kv={kv_format}: mesh {shape} diverged from "
                f"single-device non-spec:\n ref={ref}\n got={got}")
    sampled_kw = dict(temperature=0.8, top_k=8)
    ref = _serve(model, params, None, **sampled_kw)
    got = _serve(model, params, (2, 2), spec=spec, **sampled_kw)
    assert got == ref, (
        f"sampled spec on 2x2 mesh diverged:\n ref={ref}\n got={got}")


def contracts_sharded():
    """jaxpr contracts (packed-upcast, host-callback, cache-width) hold
    for the sharded entry points traced on a real 2x2 mesh."""
    from repro.analysis.contracts import check_entry_points

    findings = check_entry_points(mesh=make_serving_mesh((2, 2)))
    assert not findings, [f"{f.rule}: {f.message}" for f in findings]


def moe_expert_parallel():
    """The MoE layer under a training mesh -- 8 experts split over a
    2-way 'model' axis, tokens over a 2-way 'data' axis: each shard runs
    its own experts inside one shard_map and the shares are summed --
    gives the one-device layer's output, loss and gradients."""
    import dataclasses

    import jax.numpy as jnp
    from jax.sharding import Mesh

    from repro.models import moe as M

    cfg = dataclasses.replace(get_config("kimi-k2-1t-a32b").reduced(),
                              d_model=16, moe_d_ff=32, moe_num_experts=8,
                              moe_top_k=3)
    ep = dataclasses.replace(cfg, batch_axes=("data",))
    key = jax.random.PRNGKey(0)
    p = M.init_moe(key, cfg, jnp.float32)
    x = jax.random.normal(jax.random.fold_in(key, 1), (4, 8, cfg.d_model))

    def loss(p, x, c):
        y, aux = M.apply_moe(p, x, c)
        return jnp.sum(y * jnp.sin(y)) + aux["moe_lb_loss"], y

    grad = lambda c: jax.jit(jax.value_and_grad(
        lambda p, x: loss(p, x, c), argnums=(0, 1), has_aux=True))
    (want_l, want_y), want_g = grad(cfg)(p, x)
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                ("data", "model"))
    with mesh:
        assert "shard_map" in str(jax.make_jaxpr(
            lambda p, x: M.apply_moe(p, x, ep))(p, x))
        (got_l, got_y), got_g = grad(ep)(p, x)
    np.testing.assert_allclose(np.asarray(got_y), np.asarray(want_y),
                               atol=1e-5, rtol=0)
    np.testing.assert_allclose(float(got_l), float(want_l), rtol=1e-5)
    for g, w in zip(jax.tree.leaves(got_g), jax.tree.leaves(want_g)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=1e-4, rtol=1e-4)


CASES = {fn.__name__: fn for fn in (
    greedy_attn, greedy_ssm_hybrid, greedy_encdec_vlm,
    logits_and_prefill, spec_matrix, sanitize_sharded,
    contracts_sharded, moe_expert_parallel)}


def main(argv):
    assert len(jax.devices()) >= 4, (
        f"expected >=4 host devices, got {jax.devices()} — XLA_FLAGS "
        "was set after jax initialized?")
    names = argv or sorted(CASES)
    for name in names:
        CASES[name]()
        print(f"CASE_OK {name}", flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
