"""repro.compat — capability detection, the dtype registry, the
interpret-mode pallas_call path on the CPU, the compile-cache helper, and
device-model detection: a missing or unknown device raises instead of
falling back."""

import functools
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compat


# --------------------------------------------------------------------- #
# version / backend probing
# --------------------------------------------------------------------- #

def test_jax_version_tuple():
    v = compat.jax_version()
    assert isinstance(v, tuple) and len(v) >= 2
    assert all(isinstance(p, int) for p in v)
    assert v >= (0, 4)


def test_backend_platform_known():
    assert compat.backend_platform() in ("cpu", "gpu", "tpu")
    assert compat.is_tpu() == (compat.backend_platform() == "tpu")


def test_backend_error_propagates(monkeypatch):
    """A backend that fails to initialise must not read as a CPU run."""
    def broken():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    compat.backend_platform.cache_clear()
    monkeypatch.setattr(jax, "devices", broken)
    try:
        with pytest.raises(RuntimeError, match="initialize backend"):
            compat.backend_platform()
        with pytest.raises(RuntimeError, match="initialize backend"):
            compat.report()
    finally:
        monkeypatch.undo()
        compat.backend_platform.cache_clear()


# --------------------------------------------------------------------- #
# dtype registry
# --------------------------------------------------------------------- #

def test_registry_covers_all_paper_formats():
    names = compat.available_formats()
    assert set(names) == {"float8_e4m3fn", "float8_e5m2", "float6_e2m3fn",
                          "float6_e3m2fn", "float4_e2m1fn"}


def test_registry_containers_are_jax_usable():
    """Every container must actually hold a JAX array — the whole point
    of the fallback ladder."""
    for name in compat.available_formats():
        spec = compat.dtype_spec(name)
        arr = jnp.zeros((4,), dtype=spec.container)
        assert arr.shape == (4,), name
        assert spec.bits in (4, 6, 8)
        assert spec.max_finite > 0


def test_emulated_specs_always_carry_round_dtype():
    """Invariant: an emulated container MUST host-round, else 'fp8 on a
    JAX without fp8' would silently measure the container's precision."""
    for name in compat.available_formats():
        spec = compat.dtype_spec(name)
        if spec.emulated:
            assert spec.round_dtype is not None, name
        else:
            assert spec.round_dtype is None, name


def test_fp6_always_emulated_fp8_native_or_emulated():
    """fp6 has no jnp dtype in any JAX release — must carry a host
    rounding dtype.  fp8 e4m3/e5m2 have been native for years."""
    for name in ("float6_e2m3fn", "float6_e3m2fn"):
        spec = compat.dtype_spec(name)
        assert spec.emulated and spec.round_dtype is not None, name
    assert compat.dtype_spec("float8_e4m3fn").native


def test_fp4_fallback_selection():
    """On JAX without jnp.float4_e2m1fn the registry must degrade fp4 to
    a host-rounded e4m3 container; on newer JAX it must be native.
    Either way values survive the round trip exactly (every e2m1 value
    is representable in e4m3)."""
    spec = compat.dtype_spec("float4_e2m1fn")
    has_native = getattr(jnp, "float4_e2m1fn", None) is not None
    if not has_native:
        assert spec.emulated
        assert np.dtype(spec.container).itemsize == 1
        assert spec.round_dtype is not None
    # fp4's exact value set must survive container storage
    import ml_dtypes
    vals = np.asarray([0.0, 0.5, 1.0, 1.5, 2.0, 3.0, 4.0, 6.0, -6.0],
                      np.float32)
    rounded = vals.astype(ml_dtypes.float4_e2m1fn).astype(np.float32)
    np.testing.assert_array_equal(rounded, vals)
    stored = jnp.asarray(rounded).astype(spec.container).astype(jnp.float32)
    np.testing.assert_array_equal(np.asarray(stored), vals)


def test_dtype_spec_unknown_name():
    with pytest.raises(KeyError):
        compat.dtype_spec("float3_e1m1")


def test_describe_distinguishes_native_and_emulated():
    descs = {n: compat.dtype_spec(n).describe()
             for n in compat.available_formats()}
    assert descs["float8_e4m3fn"] == "native"
    assert "emulated" in descs["float6_e2m3fn"]


# --------------------------------------------------------------------- #
# shard_map
# --------------------------------------------------------------------- #

def test_resolve_shard_map_source():
    assert compat.shard_map is jax.shard_map


@pytest.mark.parametrize("check_kwarg", [{}, {"check_vma": False}])
def test_shard_map_runs_with_either_check_spelling(check_kwarg):
    """shard_map runs on a world=1 mesh with and without its
    replication check (``check_vma``)."""
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:1]), ("d",))
    f = compat.shard_map(lambda x: jax.lax.psum(x, "d"), mesh=mesh,
                         in_specs=P("d"), out_specs=P(), **check_kwarg)
    out = f(jnp.arange(4, dtype=jnp.float32))
    np.testing.assert_allclose(np.asarray(out), np.arange(4), atol=0)


def test_shard_map_decorator_form():
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:1]), ("d",))

    @functools.partial(compat.shard_map, mesh=mesh, in_specs=P(),
                       out_specs=P(), check_vma=False)
    def double(x):
        return x * 2.0

    out = double(jnp.ones((4,)))
    np.testing.assert_allclose(np.asarray(out), 2.0)


# --------------------------------------------------------------------- #
# pallas interpret-mode fallback
# --------------------------------------------------------------------- #

def test_interpret_default_matches_platform():
    assert compat.pallas_interpret_default() == (
        compat.backend_platform() == "cpu")


def test_tpu_compiler_params_buildable():
    """``dimension_semantics`` becomes ``pltpu.CompilerParams`` and the
    call still runs (interpreted on the CPU)."""
    from jax.experimental import pallas as pl

    def kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.0

    x = jnp.arange(16, dtype=jnp.float32).reshape(2, 8)
    out = compat.pallas_call(
        kernel, grid=(2,),
        in_specs=[pl.BlockSpec((1, 8), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((1, 8), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((2, 8), jnp.float32),
        dimension_semantics=("parallel",))(x)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x) * 2.0)


def test_pallas_call_interpret_qmatmul_matches_reference(key):
    """End-to-end acceptance: qmatmul through the compat pallas_call
    (interpret mode on CPU) matches the bf16 dequant reference."""
    from repro.kernels.qmatmul import qmatmul_mkn
    from repro.serve.quant import dequantize_blockwise, quantize_blockwise

    k1, k2 = jax.random.split(key)
    x = jax.random.normal(k1, (128, 128), jnp.float32).astype(jnp.bfloat16)
    w = jax.random.normal(k2, (128, 128), jnp.float32)
    qw, scales = quantize_blockwise(w.T, "float8_e4m3fn")

    got = qmatmul_mkn(x, qw, scales)          # interpret auto-selected
    w_deq = dequantize_blockwise(qw, scales, jnp.bfloat16)
    want = (x.astype(jnp.float32) @ w_deq.astype(jnp.float32).T
            ).astype(jnp.bfloat16)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=0.05, atol=0.05)


def test_pallas_call_interpret_qmatmul_fp4_container(key):
    """fp4 rides the registry's container on this backend and still
    produces a usable matmul (coarser values, same pipeline)."""
    from repro.kernels.qmatmul import qmatmul_mkn
    from repro.serve.quant import dequantize_blockwise, quantize_blockwise

    k1, k2 = jax.random.split(key)
    x = jax.random.normal(k1, (128, 128), jnp.float32).astype(jnp.bfloat16)
    w = jax.random.normal(k2, (128, 128), jnp.float32)
    qw, scales = quantize_blockwise(w.T, "float4_e2m1fn")

    got = qmatmul_mkn(x, qw, scales)
    w_deq = dequantize_blockwise(qw, scales, jnp.bfloat16)
    want = (x.astype(jnp.float32) @ w_deq.astype(jnp.float32).T
            ).astype(jnp.bfloat16)
    # vs the *dequant* reference the kernel is exact-ish; fp4 coarseness
    # lives in quantize_blockwise, not the kernel
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=0.05, atol=0.05)


# --------------------------------------------------------------------- #
# capability report
# --------------------------------------------------------------------- #

def test_report_contents():
    rep = compat.report()
    assert rep.jax_version == jax.__version__
    assert rep.platform == compat.backend_platform()
    assert rep.pallas_mode in ("native-mosaic", "interpret")
    assert set(rep.formats) == set(compat.available_formats())
    text = str(rep)
    assert "compat,jax=" in text
    assert "float4_e2m1fn" in text
    assert len(rep.lines()) == 2 + len(rep.formats)


# --------------------------------------------------------------------- #
# persistent compile cache
# --------------------------------------------------------------------- #

@pytest.fixture
def cache_config():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_respects_env(monkeypatch, cache_config, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compat.CACHE_ENV, str(tmp_path))
    assert compat.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_fixed_checkout_path(monkeypatch, cache_config):
    monkeypatch.delenv(compat.CACHE_ENV, raising=False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = os.path.join(root, ".jax_cache")
    assert compat.enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert compat.enable_compile_cache() == want       # stable per call


# --------------------------------------------------------------------- #
# device models by device_kind
# --------------------------------------------------------------------- #

def _fake_devices(monkeypatch, platform, kind):
    dev = types.SimpleNamespace(platform=platform, device_kind=kind)
    monkeypatch.setattr(jax, "devices", lambda *a: [dev])


def test_detect_backend_model_by_device_kind(monkeypatch):
    from repro.core import device_model as dm

    _fake_devices(monkeypatch, "tpu", "TPU v5 lite")
    assert dm.detect_backend_model() is dm.TPU_V5E
    _fake_devices(monkeypatch, "cpu", "cpu")
    assert dm.detect_backend_model() is dm.HOST_CPU


@pytest.mark.parametrize("platform,kind", [("tpu", "TPU v4"),
                                           ("gpu", "NVIDIA H100 PCIe")])
def test_detect_backend_model_unknown_kind_raises(monkeypatch, platform,
                                                  kind):
    from repro.core import device_model as dm

    _fake_devices(monkeypatch, platform, kind)
    with pytest.raises(ValueError, match="no device model"):
        dm.detect_backend_model()
