"""The Eq.-7 pool kernel compiles for a TPU v5e chip at Table-4 widths.

Nothing runs: the TPU compiler that ships with libtpu compiles for a chip
that is described, not attached, so Mosaic's tiling and VMEM checks — which
interpret mode never makes — guard every change to the kernel.  The
topology is described inside a fixture, never at import: only one process
may hold libtpu, and every test worker imports this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.networks import head_schema
from repro.kernels.pool_mlp.ops import (pool_mlp_errors_features,
                                        pool_mlp_errors_features_masked)
from repro.sharding import spec as S

W, R = 3, 50                         # HFLConfig defaults (paper Table 4)
V5E_HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _args(sharding, nf, ns):
    head = jax.eval_shape(
        lambda: S.materialize(head_schema(W), jax.random.PRNGKey(0)))
    pool = {k: jax.ShapeDtypeStruct((ns,) + v.shape, v.dtype,
                                    sharding=sharding)
            for k, v in head.items()}
    xd = jax.ShapeDtypeStruct((nf, R, W), jnp.float32, sharding=sharding)
    y = jax.ShapeDtypeStruct((R,), jnp.float32, sharding=sharding)
    return pool, xd, y


def _check_compiled(compiled, nf, ns):
    assert "tpu_custom_call" in compiled.as_text()
    out = compiled.out_info
    assert out.shape == (nf, ns) and out.dtype == jnp.float32
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes + mem.generated_code_size_in_bytes)
    assert 0 < used < V5E_HBM_BYTES


# (nf, ns): a 32-hospital pool, a ragged pool, a single probe feature at a
# block-sized and a ragged pool, and a wide cohort pool
@pytest.mark.parametrize("nf,ns", [(4, 128), (4, 124), (1, 8), (1, 50),
                                   (8, 256)])
def test_pool_kernel_compiles_for_v5e(one_chip, no_compile_cache, nf, ns):
    pool, xd, y = _args(one_chip, nf, ns)
    compiled = pool_mlp_errors_features.lower(
        pool, xd, y, interpret=False).compile()
    _check_compiled(compiled, nf, ns)


def test_masked_pool_kernel_compiles_for_v5e(one_chip, no_compile_cache):
    """The cohort engine's padded union-pool sweep (and the mesh engine's
    per-device chunk) wraps the same kernel with a validity mask."""
    nf, ns = 5, 90
    pool, xd, y = _args(one_chip, nf, ns)
    valid = jax.ShapeDtypeStruct((ns,), jnp.bool_, sharding=one_chip)
    compiled = pool_mlp_errors_features_masked.lower(
        pool, xd, y, valid, interpret=False).compile()
    _check_compiled(compiled, nf, ns)
