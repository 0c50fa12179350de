"""Compiles for a TPU v5e that is described, not attached: nothing runs.

The chip's compiler refuses what interpret mode accepts (rank-1 kernel
blocks, unaligned slices, VMEM over the limit), so these compiles guard
the Pallas kernels at the top of their regimes and the fused program at
the official case1 size, on every test run and at no chip time. The
topology is described inside a fixture, never at import: only one
process at a time may load the TPU library.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.graph import OFFICIAL_CASE_SHAPES, powergrid_shape
from repro.core.pow2 import log2_ceil


@pytest.fixture(scope="module")
def topo():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        from jax.experimental import topologies

        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compile_quietly():
    """A compile for a described chip cannot be read back from the
    persistent cache here, so keep it out of the cache."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_mosaic(compiled):
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis() is not None


def test_tree_dist_compiles_at_regime_top(one_chip, compile_quietly):
    from repro.kernels import ops

    n, m = 32768, 8192
    s = lambda shape: _spec(one_chip, shape, jnp.int32)  # noqa: E731
    fn = jax.jit(lambda up, d, a, b: ops.tree_dist_pairs(
        up, d, a, b, interpret=False))
    _assert_mosaic(fn.lower(s((log2_ceil(n + 1), n)), s((n,)), s((m,)),
                            s((m,))).compile())


def test_spmv_compiles_at_regime_top(one_chip, compile_quietly):
    from repro.kernels import ops

    n, p, m = 8192, 64, 30000
    fn = jax.jit(lambda u, v, w, x: ops.laplacian_spmv_edges(
        u, v, w, x, interpret=False))
    _assert_mosaic(fn.lower(
        _spec(one_chip, (m,), jnp.int32), _spec(one_chip, (m,), jnp.int32),
        _spec(one_chip, (m,), jnp.float32),
        _spec(one_chip, (n, p), jnp.float32)).compile())


@pytest.mark.parametrize("use_tree_kernel", [False, True])
def test_lgrass_device_compiles_at_case1(one_chip, compile_quietly,
                                         monkeypatch, use_tree_kernel):
    """The fused program as the chip runs it: the backend picks the radix
    sort and compiled kernels there, so steer both here, where the
    backend is the CPU."""
    import repro.core.sort as sort
    import repro.kernels.ops as ops
    from repro.core.baseline import default_budget
    from repro.core.sparsify import _bucket_b_cap, lgrass_device

    monkeypatch.setattr(sort, "_default_engine", lambda: "radix")
    monkeypatch.setattr(ops, "_auto_interpret",
                        lambda i: False if i is None else i)
    jax.clear_caches()  # drop traces made under the CPU engine choices
    n, m = powergrid_shape(OFFICIAL_CASE_SHAPES["case1"]["n_side"],
                           OFFICIAL_CASE_SHAPES["case1"]["chord_frac"])
    compiled = lgrass_device.lower(
        _spec(one_chip, (m,), jnp.int32), _spec(one_chip, (m,), jnp.int32),
        _spec(one_chip, (m,), jnp.float32), _spec(one_chip, (), jnp.int32),
        n=n, b_cap=_bucket_b_cap([default_budget(n)]),
        use_tree_kernel=use_tree_kernel).compile()
    jax.clear_caches()
    assert ("tpu_custom_call" in compiled.as_text()) == use_tree_kernel
    assert compiled.memory_analysis().temp_size_in_bytes > 0
