"""Ahead-of-time TPU compiles of the fused EF kernels at real width.

Every kernel of the main path, and the whole fused pipeline, is compiled
by Mosaic for one chip of a described ``v5e:2x2`` topology at d = 2^24 —
no chip needed, only the TPU compiler.  What the interpreter accepts but
the chip refuses (a block off the (8, 128) tiling, an op Mosaic cannot
lower, more VMEM than a kernel may use) fails here.  The compiled HLO
must hold the Mosaic kernels as ``tpu_custom_call``s.

The topology is described inside a module-scoped fixture, never at
import: only one process at a time may load the TPU library.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.ef_fused import fused_compress_ef
from repro.kernels.ef_fused.compact_residual import compact_residual
from repro.kernels.ef_fused.fused_moments import fused_moments
from repro.kernels.ef_fused.tree_count import tree_count

D = 2 ** 24
DTYPES = (jnp.float32, jnp.bfloat16)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")     # no compiler logs in /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure means no TPU compiler
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip cannot be read back from the
        # persistent cache without the chip: keep it out of the cache
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            compilation_cache.reset_cache()


def _kernels(fn, *args) -> int:
    """Compile ``fn`` for the described chip; count its Mosaic kernels."""
    return jax.jit(fn).lower(*args).compile().as_text().count(
        "tpu_custom_call")


def _min_block(dtype) -> int:
    return 1024 if dtype == jnp.float32 else 2048


@pytest.mark.parametrize("with_e", [False, True], ids=["g", "g+e"])
@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
@pytest.mark.parametrize("with_hist", [False, True], ids=["moments", "hist"])
def test_fused_moments_compiles(one_chip, dtype, with_e, with_hist):
    block = 4 * _min_block(dtype)
    g = jax.ShapeDtypeStruct((D // block, block), dtype, sharding=one_chip)
    e = jax.ShapeDtypeStruct(g.shape, jnp.float32, sharding=one_chip)

    def f(g, e=None):
        return fused_moments(g, e, block=block, with_hist=with_hist,
                             backend="mosaic", interpret=False)

    assert _kernels(f, *((g, e) if with_e else (g,))) == 1


@pytest.mark.parametrize("with_e", [False, True], ids=["g", "g+e"])
@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
def test_tree_count_compiles(one_chip, dtype, with_e):
    block = 4 * _min_block(dtype)
    g = jax.ShapeDtypeStruct((D // block, block), dtype, sharding=one_chip)
    e = jax.ShapeDtypeStruct(g.shape, jnp.float32, sharding=one_chip)
    t = jax.ShapeDtypeStruct((15,), jnp.float32, sharding=one_chip)

    def f(g, t, e=None):
        return tree_count(g, e, t, n_t=15, block=block, backend="mosaic",
                          interpret=False)

    assert _kernels(f, *((g, t, e) if with_e else (g, t))) == 1


@pytest.mark.parametrize("with_e", [False, True], ids=["g", "g+e"])
@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
def test_compact_residual_compiles(one_chip, dtype, with_e):
    block = _min_block(dtype)
    g = jax.ShapeDtypeStruct((D // block, block), dtype, sharding=one_chip)
    e = jax.ShapeDtypeStruct(g.shape, jnp.float32, sharding=one_chip)
    t = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)

    def f(g, t, e=None):
        return compact_residual(g, e, t, bcap=64, k_cap=D // 750,
                                block=block, out_dtype="float32",
                                backend="mosaic", interpret=False)

    assert _kernels(f, *((g, t, e) if with_e else (g, t))) == 1


@pytest.mark.parametrize("with_e", [False, True], ids=["g", "g+e"])
@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
@pytest.mark.parametrize("name,passes", [("gaussiank", 3), ("histk", 2)])
def test_fused_pipeline_compiles(one_chip, name, passes, dtype, with_e):
    """The whole pipeline: one Mosaic kernel per HBM pass (DESIGN.md §8)
    and no operand copies around them."""
    g = jax.ShapeDtypeStruct((D,), dtype, sharding=one_chip)
    e = jax.ShapeDtypeStruct((D,), jnp.float32, sharding=one_chip)

    def f(g, e=None):
        return fused_compress_ef(g, e, name, D // 1000, backend="mosaic")

    args = (g, e) if with_e else (g,)
    compiled = jax.jit(f).lower(*args).compile()
    assert compiled.as_text().count("tpu_custom_call") == passes
    assert compiled.memory_analysis().temp_size_in_bytes == 0
