"""Which attention computes a train/prefill call (models/layers.py).

On a TPU, self-attention from position 0 with equal q/v head sizes, a
sequence the kernel tiles and no mesh that would partition the kernel
takes the Pallas flash kernel; every other call keeps
``blockwise_attention``. ``model_attn_calls_total{path}`` counts each
traced call by the path it took. ``on_tpu`` is patched where a test
plays the chip: the model is only traced there, never compiled for the
CPU with a TPU kernel in it.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.kernels import ops
from repro.models import layers
from repro.models import transformer as T
from repro.models.config import ModelConfig


@pytest.fixture
def calls():
    """The counter's series, read after the test's traces: obs on and
    cleared for the test, and left as it was found."""
    was_on = obs.enabled()
    obs.enable()
    obs.reset()
    counter = obs.registry().counter(
        "model_attn_calls_total", labels=("path",))
    yield lambda: {k[0]: v for k, v in counter.series.items()}
    obs.reset()
    if not was_on:
        obs.disable()


def _tiny(**kw):
    base = dict(name="dispatch-tiny", family="dense", d_model=256,
                n_heads=4, n_kv_heads=2, d_ff=512, vocab_size=512,
                schedule=((("attn", "attn"), 2),), param_dtype="float32",
                compute_dtype="bfloat16", q_chunk=128, kv_chunk=128)
    base.update(kw)
    return ModelConfig(**base)


def _trace_grad(cfg, seq):
    """Traces (never compiles) the loss gradient of a forward pass."""
    params = jax.eval_shape(lambda: T.init_params(cfg, jax.random.PRNGKey(0)))
    tokens = jax.ShapeDtypeStruct((1, seq), jnp.int32)

    def loss(p, t):
        logits, _ = T.forward(p, {"tokens": t}, cfg)
        return jnp.mean(logits.astype(jnp.float32))

    jax.eval_shape(jax.grad(loss), params, tokens)


@pytest.mark.parametrize("attn_sp", [False, True])
def test_every_layer_takes_flash_on_tpu(calls, monkeypatch, attn_sp):
    """Each traced attention call of a dense model (plain or with the
    sequence-parallel flag and no mesh, as qwen2.5-32b runs) counts
    ``flash`` on a TPU, and the same calls count ``blockwise`` off it."""
    cfg = _tiny(attn_sp=attn_sp)
    _trace_grad(cfg, 256)
    off = calls()
    assert set(off) == {"blockwise"} and off["blockwise"] >= 2
    obs.reset()
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    _trace_grad(cfg, 256)
    assert calls() == {"flash": off["blockwise"]}


def _qkv(sq=256, skv=256, hq=4, hkv=2, hd=64, vd=None):
    rng = np.random.default_rng(0)
    draw = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    return draw(1, sq, hq, hd), draw(1, skv, hkv, hd), \
        draw(1, skv, hkv, vd or hd)


def test_mla_model_keeps_blockwise(calls, monkeypatch):
    """MLA's value head (``v_head_dim``) differs from its q/k head."""
    from repro.configs.registry import get_config

    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    cfg = get_config("deepseek-v3-671b", smoke=True)
    assert any(k.startswith("mla") for k in cfg.block_kinds())
    cfg = dataclasses.replace(cfg, mtp=False)
    params = jax.eval_shape(lambda: T.init_params(cfg, jax.random.PRNGKey(0)))
    jax.eval_shape(lambda p: T.forward(
        p, {"tokens": jnp.zeros((1, 128), jnp.int32)}, cfg), params)
    assert calls().get("flash", 0) == 0 and calls()["blockwise"] >= 1


@pytest.mark.parametrize("case", ["mla", "cross", "q_offset", "mesh",
                                  "odd_length"])
def test_ineligible_calls_keep_blockwise(calls, monkeypatch, case):
    """With ``on_tpu`` true, these calls count ``blockwise`` and give
    exactly what ``blockwise_attention`` gives."""
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    kw = dict(causal=True, q_chunk=128, kv_chunk=128)
    if case == "mla":
        q, k, v = _qkv(hd=96, vd=64)
    elif case == "cross":
        q, k, v = _qkv(skv=384)
        kw["causal"] = False
    elif case == "odd_length":
        q, k, v = _qkv(sq=200, skv=200)
    else:
        q, k, v = _qkv()
    if case == "q_offset":
        kw["q_offset"] = 128
    want = layers.blockwise_attention(q, k, v, **kw)
    if case == "mesh":
        mesh = jax.make_mesh((1,), ("data",))
        with jax.set_mesh(mesh):
            got = layers.attention(q, k, v, **kw)
    else:
        got = layers.attention(q, k, v, **kw)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert calls() == {"blockwise": 1}


def test_shard_map_body_takes_flash(calls, monkeypatch):
    """Inside a ``shard_map`` every mesh axis is manual: the call is local
    to a chip and the kernel applies."""
    from jax.sharding import PartitionSpec as P

    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    q, k, v = _qkv()
    mesh = jax.make_mesh((1,), ("data",))
    spec = P("data")
    body = jax.shard_map(
        lambda q, k, v: layers.attention(q, k, v, causal=True),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    jax.eval_shape(body, q, k, v)
    assert calls() == {"flash": 1}


def _numbered_in_order(hlo_text: str) -> str:
    names: dict = {}
    return re.sub(r"%[\w.\-]+",
                  lambda m: names.setdefault(m.group(0), f"%i{len(names)}"),
                  hlo_text)


def _compiled_cpu_step(cfg) -> str:
    from repro.optim.api import get_optimizer
    from repro.train.steps import TrainState, make_train_step

    opt = get_optimizer("dct_adamw", lr=1e-3, rank=8, fused="off")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    state = TrainState(jnp.zeros((), jnp.int32), params, opt.init(params))
    batch = {"tokens": jnp.zeros((1, 256), jnp.int32),
             "targets": jnp.zeros((1, 256), jnp.int32)}
    return jax.jit(make_train_step(cfg, opt)).lower(state, batch) \
        .compile().as_text()


def test_cpu_step_unchanged_by_dispatch(monkeypatch):
    """Off the TPU the compiled train step is the one that calls
    ``blockwise_attention`` directly, metadata stripped."""
    from bench import scopes

    cfg = _tiny(schedule=((("attn",), 2),))
    via_dispatch = _compiled_cpu_step(cfg)
    monkeypatch.setattr(T, "attention", layers.blockwise_attention)
    direct = _compiled_cpu_step(cfg)
    assert _numbered_in_order(scopes.strip_metadata(via_dispatch)) == \
        _numbered_in_order(scopes.strip_metadata(direct))
