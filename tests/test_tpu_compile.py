"""The hot-path Pallas kernels compile for a TPU v5e chip.

Interpret mode (every other kernel test) cannot see a block that breaks
the TPU tiling, a kernel that needs more VMEM than it may use, or a load
Mosaic cannot lower. These tests compile each kernel at real widths for a
*described* v5e chip: the TPU compiler runs here and raises what the
chip's compiler would, while nothing runs. The widths are llama-350m's
stacked MLP leaf (24, 2816, 1024) and qwen2.5-32b's (1, 27648, 5120),
both at rank 256, and a GQA paged-decode shape (Hq=40, Hkv=8, hd=128).

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports this
file.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.colgather_matmul import colgather_matmul_dual
from repro.kernels.dct_project import dct_project
from repro.kernels.flash_decode import flash_decode
from repro.kernels.newton_schulz import ns_iteration
from repro.kernels.quant_ef import dequant_add_ef, quantize_ef

LEAVES = [(24, 2816, 1024), (1, 27648, 5120)]
RANK = 256
DTYPES = ["fp32", "bf16", "int8"]


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e:2x2 host. The persistent compile
    cache is off meanwhile: entries written for a described chip cannot
    be read back without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler, or the library is taken
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)


def _compile_hlo(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("dt", DTYPES)
@pytest.mark.parametrize("leaf", LEAVES, ids=["llama350m", "qwen32b"])
def test_dct_project_compiles(one_chip, leaf, dt):
    _, _, n = leaf
    hlo = _compile_hlo(
        lambda g, q: dct_project(g, q, compute_dtype=dt),
        _sds(one_chip, leaf, jnp.float32), _sds(one_chip, (n, n), jnp.float32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("dt", DTYPES)
@pytest.mark.parametrize("leaf", LEAVES, ids=["llama350m", "qwen32b"])
def test_colgather_matmul_dual_compiles(one_chip, leaf, dt):
    layers, m, n = leaf
    b = _sds(one_chip, (layers, m, RANK), jnp.float32)
    hlo = _compile_hlo(
        lambda b1, b2, qt, idx: colgather_matmul_dual(b1, b2, qt, idx,
                                                      compute_dtype=dt),
        b, b, _sds(one_chip, (n, n), jnp.float32),
        _sds(one_chip, (layers, RANK), jnp.int32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("leaf", LEAVES, ids=["llama350m", "qwen32b"])
def test_quant_ef_compiles(one_chip, leaf):
    x = _sds(one_chip, leaf, jnp.float32)
    assert "tpu_custom_call" in _compile_hlo(quantize_ef, x)
    hlo = _compile_hlo(dequant_add_ef, x, _sds(one_chip, leaf, jnp.int8),
                       _sds(one_chip, (*leaf[:-1], 1), jnp.float32))
    assert "tpu_custom_call" in hlo


def test_ns_iteration_compiles(one_chip):
    hlo = _compile_hlo(ns_iteration,
                       _sds(one_chip, (24, RANK, 2816), jnp.float32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("num_splits", [1, 4])
def test_flash_decode_compiles(one_chip, num_splits):
    b, hq, hkv, hd, bs, blocks, maxb = 8, 40, 8, 128, 16, 256, 32
    pool = _sds(one_chip, (blocks, bs, hkv, hd), jnp.bfloat16)
    hlo = _compile_hlo(
        lambda q, k, v, t, n: flash_decode(q, k, v, t, n,
                                           num_splits=num_splits),
        _sds(one_chip, (b, hq, hd), jnp.bfloat16), pool, pool,
        _sds(one_chip, (b, maxb), jnp.int32), _sds(one_chip, (b,), jnp.int32))
    assert "tpu_custom_call" in hlo
