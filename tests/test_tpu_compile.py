"""The hot-path Pallas kernels compile for a TPU v5e chip.

Interpret mode (every other kernel test) cannot see a block that breaks
the TPU tiling, a kernel that needs more VMEM than it may use, or a load
Mosaic cannot lower. These tests compile each kernel at real widths for a
*described* v5e chip: the TPU compiler runs here and raises what the
chip's compiler would, while nothing runs. The widths are llama-350m's
stacked MLP leaf (24, 2816, 1024) and qwen2.5-32b's (1, 27648, 5120),
both at rank 256, a GQA paged-decode shape (Hq=40, Hkv=8, hd=128), and
the flash attention kernels, forward and backward, at qwen2.5-32b's
(40/8 heads x 128) and phi3-mini's (32/32 x 96) widths, S=4096.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports this
file.
"""

import re

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
FLASH_KERNELS = ("flash_attention_fwd", "flash_attention_bwd_dkv",
                 "flash_attention_bwd_dq")
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


def test_scoped_train_step_keeps_its_kernels(one_chip, monkeypatch):
    """A whole dct_adamw step with its name scopes, compiled for the
    chip: the benchmark's reader still finds each Pallas kernel by its
    wrapper (``jit(<wrapper>)/pallas_call`` ends the ``op_name``), and
    each kernel lies in the scope of its phase: the optimizer's, and
    attention's for the flash kernels."""
    from bench import scopes, xtrace
    from repro.kernels import ops
    from repro.models import transformer as T
    from repro.models.config import ModelConfig
    from repro.optim.api import get_optimizer
    from repro.train.steps import TrainState, make_train_step

    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    cfg = ModelConfig(
        name="scoped-tiny", family="dense", d_model=256, n_heads=2,
        n_kv_heads=2, d_ff=512, vocab_size=512,
        schedule=((("attn",), 2),), param_dtype="float32",
        compute_dtype="bfloat16", q_chunk=128, kv_chunk=128)
    opt = get_optimizer("dct_adamw", lr=1e-3, rank=128, fused="on")
    shapes = jax.eval_shape(
        lambda: (lambda p: TrainState(jnp.zeros((), jnp.int32), p,
                                      opt.init(p)))(
            T.init_params(cfg, jax.random.PRNGKey(0))))
    state = jax.tree.map(lambda x: _sds(one_chip, x.shape, x.dtype), shapes)
    tokens = _sds(one_chip, (1, 256), jnp.int32)
    hlo = _compile_hlo(make_train_step(cfg, opt), state,
                       {"tokens": tokens, "targets": tokens})
    kernels = xtrace.hlo_kernels(hlo)
    found = {info["kernel"] for info in kernels.values()}
    assert found == {"dct_project", "colgather_matmul_dual", "quantize_ef",
                     "dequant_add_ef", *FLASH_KERNELS}
    phase = {"dct_project": "opt/select_project",
             "colgather_matmul_dual": "opt/backproject",
             "quantize_ef": "opt/ef", "dequant_add_ef": "opt/ef",
             **{k: "model/attn" for k in FLASH_KERNELS}}
    ops_ = scopes.hlo_ops(hlo)
    for instr, info in kernels.items():
        assert ops_[instr].scope == phase[info["kernel"]], instr


def _tiny_step_hlo(one_chip, cfg, seq: int) -> str:
    """A dct_adamw train step of ``cfg`` on one row of ``seq`` tokens,
    compiled for the chip."""
    from repro.models import transformer as T
    from repro.optim.api import get_optimizer
    from repro.train.steps import TrainState, make_train_step

    opt = get_optimizer("dct_adamw", lr=1e-3, rank=128, fused="on")
    shapes = jax.eval_shape(
        lambda: (lambda p: TrainState(jnp.zeros((), jnp.int32), p,
                                      opt.init(p)))(
            T.init_params(cfg, jax.random.PRNGKey(0))))
    state = jax.tree.map(lambda x: _sds(one_chip, x.shape, x.dtype), shapes)
    tokens = _sds(one_chip, (1, seq), jnp.int32)
    return _compile_hlo(make_train_step(cfg, opt), state,
                        {"tokens": tokens, "targets": tokens})


@pytest.mark.parametrize("hq,hkv,hd", [(40, 8, 128), (32, 32, 96)],
                         ids=["qwen32b", "phi3mini"])
def test_flash_attention_layer_compiles(one_chip, monkeypatch, hq, hkv, hd):
    """Forward and backward of one rematerialised attention layer at a
    cell's widths, S=4096, batch 1: the three flash kernels compile and
    each lies in ``model/attn``."""
    from bench import scopes, xtrace
    from repro.kernels import ops
    from repro.models import transformer as T
    from repro.models.config import ModelConfig

    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    cfg = ModelConfig(
        name="attn-layer", family="dense", d_model=hq * hd, n_heads=hq,
        n_kv_heads=hkv, head_dim=hd, d_ff=256, vocab_size=256,
        schedule=((("attn",), 1),), param_dtype="bfloat16",
        compute_dtype="bfloat16")
    shapes = jax.eval_shape(lambda: T.init_params(cfg, jax.random.PRNGKey(0)))
    params = jax.tree.map(lambda x: _sds(one_chip, x.shape, x.dtype), shapes)

    def loss(p, tokens):
        logits, _ = T.forward(p, {"tokens": tokens}, cfg)
        return jnp.mean(logits.astype(jnp.float32))

    hlo = _compile_hlo(jax.grad(loss), params,
                       _sds(one_chip, (1, 4096), jnp.int32))
    kernels = xtrace.hlo_kernels(hlo)
    ops_ = scopes.hlo_ops(hlo)
    found = sorted(info["kernel"] for info in kernels.values())
    assert found == sorted(["flash_attention_fwd", *FLASH_KERNELS])
    for instr in kernels:
        assert "model/attn" in ops_[instr].op_name, ops_[instr].op_name
        assert ops_[instr].scope == "model/attn", instr


def test_train_step_stores_no_score_tiles(one_chip, monkeypatch):
    """On the chip the train step keeps attention's score tiles in VMEM:
    no ``dynamic-update-slice`` writes an array ending in a
    (q_chunk, kv_chunk) tile, which the blockwise path stacks for its
    backward."""
    from repro.kernels import ops
    from repro.models.config import ModelConfig

    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    cfg = ModelConfig(
        name="tiles-tiny", family="dense", d_model=256, n_heads=4,
        n_kv_heads=2, d_ff=512, vocab_size=512,
        schedule=((("attn",), 2),), param_dtype="float32",
        compute_dtype="bfloat16", q_chunk=128, kv_chunk=256)
    hlo = _tiny_step_hlo(one_chip, cfg, 512)
    assert "tpu_custom_call" in hlo
    tiles = []
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = \w+\[([\d,]*)\]\S* "
                     r"([\w\-]+)\(", line)
        if m and "dynamic-update-slice" in m.group(1) + m.group(3) \
                and m.group(2).endswith("128,256"):
            tiles.append(line.strip()[:120])
    assert not tiles, tiles
