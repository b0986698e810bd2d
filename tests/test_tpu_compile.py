"""Compile the served path and its kernels for a described TPU v5e chip.

Nothing runs: ``jax.experimental.topologies`` describes a v5e:2x2 host
without attaching it, and each program is lowered and compiled by the
TPU compiler for one of its chips.  This catches what interpret mode
cannot: Mosaic's tiling rules, unsupported casts and reshapes, and
programs that do not fit the chip's 16 GB of HBM.

The topology is described inside a module fixture (never at import, so
pytest-xdist workers all collect the same tests), and the persistent
compile cache is off around these compiles: a program compiled for an
absent chip cannot be read back from it.
"""

import dataclasses
import importlib
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import compat
from repro.configs import get_config
from repro.models import build_model

HBM_BYTES = 16e9                       # one v5e chip
BATCH, MAX_SEQ, CHUNK = 8, 2048, 32    # the chip smoke's engine


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    log_dir = os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()
    if log_dir == "disabled":
        os.environ.pop("TPU_LOG_DIR", None)


def _on(sharding, tree):
    """ShapeDtypeStructs of ``tree`` placed on ``sharding``."""
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


# granite-4.0-h-small as the benchmark serves it: one chip's share of an
# 8-way expert-parallel deployment (9 of 72 experts), 20 of 40 layers
CUTS = {"granite-4.0-h-small": {"n_layers": 20, "moe_experts_held": 9}}


def _model(kv_format=None, arch="gptneox-1b"):
    cfg = dataclasses.replace(get_config(arch), **CUTS.get(arch, {}))
    if kv_format:
        cfg = dataclasses.replace(cfg, kv_format=kv_format)
    return build_model(cfg)


def _fits(compiled) -> float:
    ma = compiled.memory_analysis()
    used = ma.argument_size_in_bytes + ma.temp_size_in_bytes
    assert used < HBM_BYTES, f"{used / 1e9:.2f} GB does not fit one chip"
    return used


@pytest.mark.parametrize("kv_format", [None, "float4_e2m1fn"])
def test_decode_step_compiles_at_full_width(one_chip, kv_format):
    model = _model(kv_format)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: model.init_cache(BATCH, MAX_SEQ))
    ids = jax.ShapeDtypeStruct((BATCH,), jnp.int32, sharding=one_chip)
    active = jax.ShapeDtypeStruct((BATCH,), jnp.bool_, sharding=one_chip)
    compiled = jax.jit(
        lambda p, c, t, q, a: model.decode_step(p, c, t, q, active=a)
    ).lower(_on(one_chip, params), _on(one_chip, cache), ids, ids,
            active).compile()
    # bf16 params alone are ~2 GB; the bf16 KV pool another ~2 GB
    assert _fits(compiled) > 2e9


def test_prefill_chunk_compiles_at_full_width(one_chip):
    model = _model()
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: model.init_cache(BATCH, MAX_SEQ))
    i32 = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    tokens = jax.ShapeDtypeStruct((CHUNK,), jnp.int32, sharding=one_chip)
    compiled = jax.jit(model.prefill_chunk).lower(
        _on(one_chip, params), _on(one_chip, cache), tokens, i32, i32,
        i32).compile()
    assert _fits(compiled) > 2e9


def _array_less_engine(model):
    """An engine that holds no arrays, with its own executables."""
    from repro.serve import ServeEngine
    engine = object.__new__(ServeEngine)
    engine.model, engine.batch, engine.max_seq = model, BATCH, MAX_SEQ
    engine._temperature, engine._top_k = 0.0, 0
    engine.spec, engine.mesh, engine._sh = None, None, None
    engine._draft_cache = None
    engine._make_executables()
    return engine


def _body_copied_shapes(hlo: str) -> set:
    """Shapes ``d0,d1,...`` of every ``copy`` in optimized HLO text
    outside the entry computation, i.e. inside the loops, per step."""
    body = re.sub(r"^ENTRY .*?^}$", "", hlo, flags=re.M | re.S)
    return set(re.findall(r"= \w+\[([\d,]*)\]\S* copy\(", body))


def _executable(engine, name, params, cache):
    """(jitted executable, its arguments) for the engine's ``name``."""
    i32 = jax.ShapeDtypeStruct((), jnp.int32)
    if name == "loop":
        state = jax.eval_shape(engine._init_state)
        key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
        return engine._make_decode_loop(2), (params, cache, state, key)
    if name == "prefill_chunk":
        tokens = jax.ShapeDtypeStruct((CHUNK,), jnp.int32)
        return engine._prefill_chunk_fn, (params, cache, tokens, i32, i32,
                                          i32)
    assert name == "clear_slot"
    return engine._clear_slot_fn, (cache, i32)


@pytest.mark.parametrize("arch,kv_format", [
    ("gptneox-1b", None), ("gptneox-1b", "float4_e2m1fn"),
    ("mamba2-2.7b", None), ("granite-4.0-h-small", None)])
@pytest.mark.parametrize("name", ["loop", "prefill_chunk", "clear_slot"])
def test_executable_updates_pool_in_place(one_chip, name, arch, kv_format):
    """The fused loop, the prefill chunk and ``clear_slot`` each donate
    the slot pool and update it in place: the output aliases the whole
    pool, and no loop body (the decode steps, the chunk's layer scan)
    copies anything pool-shaped.  A dense pool (bf16 KV, SSM state, or
    both in granite's mixed pool beside its grouped-matmul MoE) is then
    never held twice: the temporaries stay far below one pool.  The
    packed fp4 pool is exempt from that bound: the loop keeps it in a
    layout of its own, into which the entry converts it once per call,
    and its XLA path dequantizes a layer's K/V per step."""
    model = _model(kv_format, arch)
    engine = _array_less_engine(model)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: model.init_cache(BATCH, MAX_SEQ))
    fn, args = _executable(engine, name, params, cache)
    compiled = fn.lower(*_on(one_chip, args)).compile()
    pool = jax.tree.leaves(cache)
    pool_bytes = sum(a.size * a.dtype.itemsize for a in pool)
    ma = compiled.memory_analysis()
    assert ma.alias_size_in_bytes == pool_bytes
    pool_shapes = {",".join(map(str, a.shape)) for a in pool}
    assert not _body_copied_shapes(compiled.as_text()) & pool_shapes
    if kv_format is None:
        assert ma.temp_size_in_bytes < pool_bytes / 4


def _kernel_case(name):
    """(fn, argument shapes) for one kernel at gptneox-1b widths."""
    fd = importlib.import_module("repro.kernels.flash_decode")
    qm = importlib.import_module("repro.kernels.qmatmul")
    cfg = get_config("gptneox-1b")
    b, h, d, S = BATCH, cfg.n_heads, cfg.head_dim, MAX_SEQ
    S_ = jax.ShapeDtypeStruct
    q = S_((b, h, d), jnp.bfloat16)
    sp, pos = S_((b, S), jnp.int32), S_((b,), jnp.int32)
    if name == "flash_decode":
        kv = S_((b, h, S, d), jnp.bfloat16)
        return (lambda *a: fd.flash_decode_bhd(*a, interpret=False),
                (q, kv, kv, sp, pos))
    if name.startswith("flash_decode_quant"):
        fmt = name.split(":")[1]
        spec = compat.dtype_spec(fmt)
        if spec.packed is not None:
            stored = S_((b, h, S, d // 2), jnp.uint8)
        else:
            stored = S_((b, h, S, d), spec.container)
        scales = S_((b, h, S, d // 32), jnp.uint8)
        return (lambda *a: fd.flash_decode_quant_bhd(
            *a, fmt=fmt, interpret=False),
            (q, stored, scales, stored, scales, sp, pos))
    m, k, n = 128, cfg.d_model, cfg.d_ff
    x, sc = S_((m, k), jnp.bfloat16), S_((n, k // 32), jnp.float32)
    if name == "qmatmul:float8_e4m3fn":
        return (lambda *a: qm.qmatmul_mkn(*a, interpret=False),
                (x, S_((n, k), jnp.float8_e4m3fn), sc))
    assert name == "qmatmul_packed:float4_e2m1fn"
    return (lambda *a: qm.qmatmul_packed_mkn(*a, "float4_e2m1fn",
                                             interpret=False),
            (x, S_((n, k // 2), jnp.uint8), sc))


@pytest.mark.parametrize("name", [
    "flash_decode", "flash_decode_quant:float8_e4m3fn",
    "flash_decode_quant:float4_e2m1fn", "qmatmul:float8_e4m3fn",
    "qmatmul_packed:float4_e2m1fn"])
def test_kernel_compiles_with_mosaic(one_chip, name):
    fn, shapes = _kernel_case(name)
    compiled = jax.jit(fn).lower(*_on(one_chip, shapes)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _off_path_case(name):
    """(fn, argument shapes) for a kernel off the served path.
    ``ssd_scan`` is not here: Mosaic has no lowering for its cumsum."""
    mod = importlib.import_module(f"repro.kernels.{name}")
    S_ = jax.ShapeDtypeStruct
    if name == "flash_attention":
        qkv = S_((1, 16, 1024, 128), jnp.bfloat16)
        return (lambda *a: mod.flash_attention_bhsd(*a, interpret=False),
                (qkv, qkv, qkv))
    if name == "probe_mma":
        return (lambda *a: mod.mma_probe(*a, interpret=False),
                (S_((1, 256, 256), jnp.bfloat16),
                 S_((256, 256), jnp.bfloat16)))
    if name == "probe_chase":
        return (lambda b: mod.chase(b, steps=16, interpret=False),
                (S_((1024, 128), jnp.int32),))
    assert name == "probe_dep_chain"
    return (lambda x: mod.dep_chain(x, chain_len=16, interpret=False),
            (S_((1,) + mod.TILE, jnp.float32),))


@pytest.mark.parametrize("name", ["flash_attention", "probe_mma",
                                  "probe_chase", "probe_dep_chain"])
def test_off_path_kernel_compiles_with_mosaic(one_chip, name):
    fn, shapes = _off_path_case(name)
    compiled = jax.jit(fn).lower(*_on(one_chip, shapes)).compile()
    assert "tpu_custom_call" in compiled.as_text()
