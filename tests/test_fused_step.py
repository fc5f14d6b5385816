"""Parity suite for the fused projected-Adam execution layer (DESIGN.md §3).

The fused dispatch ("on" = Pallas kernels in interpret mode off-TPU, "fft" =
Makhoul host fast path) must match the seed jnp reference path ("off") to
fp32 tolerance across every projector kind x residual mode x stacked /
unstacked / odd-dimension shape, over multiple steps (so rotation, moments
and the quantized error-feedback buffer are all exercised through the state
feedback loop).

Also verifies — by spying on the kernel entry points, not by inspection —
that scan-stacked ``(layers, m, n)`` leaves actually dispatch to the batched
Pallas kernels instead of silently falling back.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import fused_step
from repro.core.error_feedback import QuantizedBuffer, dequantize_q8
from repro.optim.common import Context
from repro.optim.projected_adam import ProjectedAdamRule

SHAPES = [
    (24, 40),       # plain 2D, projected dim last
    (3, 24, 40),    # scan-stacked layers
    (33, 17),       # odd, non-block-multiple dims (oriented: project dim 17)
]
KINDS = ["dct", "svd", "power", "random", "randperm"]
RESIDUALS = ["ef", "discard", "sign", "fira"]


def _run_steps(rule: ProjectedAdamRule, shape, n_steps=3, seed=0):
    """Drive rule.update through n_steps with synthetic gradients; return
    the per-step updates and the final state."""
    rng = np.random.default_rng(seed)
    state = rule.init(shape, jnp.float32)
    param = jnp.zeros(shape, jnp.float32)

    @functools.partial(jax.jit, static_argnames=())
    def step_fn(g, state, step):
        ctx = Context(step=step, bases={}, key=jax.random.PRNGKey(7))
        return rule.update(g, state, param, ctx)

    outs = []
    for t in range(1, n_steps + 1):
        g = jnp.asarray(rng.standard_normal(shape), jnp.float32)
        d, state = step_fn(g, state, jnp.asarray(t, jnp.int32))
        outs.append(np.asarray(d))
    return outs, state


def _assert_step_parity(ref, got, label):
    # step 1 has no state feedback -> tight; later steps accumulate the
    # +-1-unit int8 EF rounding flips that a ~1e-6 S-matmul difference can
    # cause, so the tolerance widens with step index
    for t, (a, b) in enumerate(zip(ref, got)):
        tol = 3e-4 if t == 0 else 5e-3
        np.testing.assert_allclose(b, a, atol=tol, rtol=5e-3,
                                   err_msg=f"{label} step {t + 1}")


@pytest.mark.parametrize("shape", SHAPES, ids=["2d", "stacked", "odd"])
@pytest.mark.parametrize("residual", RESIDUALS)
@pytest.mark.parametrize("kind", KINDS)
def test_fused_kernel_matches_reference(kind, residual, shape):
    base = ProjectedAdamRule(rank=8, projector=kind, rotate=(kind == "dct"),
                             residual=residual, ef_dtype="q8", fused="off")
    ref, ref_state = _run_steps(base, shape)
    got, got_state = _run_steps(dataclasses.replace(base, fused="on"), shape)
    _assert_step_parity(ref, got, f"{kind}/{residual}")
    if residual == "ef":
        a, b = ref_state.ef, got_state.ef
        assert isinstance(b, QuantizedBuffer)
        np.testing.assert_allclose(
            np.asarray(dequantize_q8(b)), np.asarray(dequantize_q8(a)),
            atol=float(np.abs(np.asarray(a.scale)).max()) * 2 + 1e-5,
            err_msg=f"{kind}/{residual} EF buffer")


@pytest.mark.parametrize("shape", SHAPES, ids=["2d", "stacked", "odd"])
@pytest.mark.parametrize("residual", RESIDUALS)
def test_fused_fft_matches_reference(residual, shape):
    """The Makhoul host fast path — dct kind only (the fft transform IS the
    shared-basis projection)."""
    base = ProjectedAdamRule(rank=8, projector="dct", residual=residual,
                             ef_dtype="q8", fused="off")
    ref, _ = _run_steps(base, shape)
    got, _ = _run_steps(dataclasses.replace(base, fused="fft"), shape)
    _assert_step_parity(ref, got, f"fft/{residual}")


@pytest.mark.parametrize("ef_dtype", ["fp32", "q8"])
def test_fused_ef_dtypes(ef_dtype):
    base = ProjectedAdamRule(rank=8, projector="dct", residual="ef",
                             ef_dtype=ef_dtype, fused="off")
    ref, _ = _run_steps(base, (3, 24, 40))
    got, _ = _run_steps(dataclasses.replace(base, fused="on"), (3, 24, 40))
    _assert_step_parity(ref, got, f"ef_dtype={ef_dtype}")


def test_fused_update_interval_keep_branch():
    """T_u > 1 exercises the lax.cond keep branch (project with stale
    indices, identity rotation) on the fused path."""
    base = ProjectedAdamRule(rank=8, projector="dct", residual="ef",
                             ef_dtype="q8", update_interval=3, fused="off")
    ref, ref_state = _run_steps(base, (3, 24, 40), n_steps=5)
    got, got_state = _run_steps(dataclasses.replace(base, fused="on"),
                                (3, 24, 40), n_steps=5)
    _assert_step_parity(ref, got, "T_u=3")
    np.testing.assert_array_equal(np.asarray(ref_state.proj),
                                  np.asarray(got_state.proj))


def test_fused_exact_rotation_matmul():
    base = ProjectedAdamRule(rank=6, projector="dct", residual="discard",
                             exact_rotation_matmul=True, fused="off")
    ref, _ = _run_steps(base, (24, 40))
    got, _ = _run_steps(dataclasses.replace(base, fused="on"), (24, 40))
    _assert_step_parity(ref, got, "exact-rotation")


def test_fused_l1_ranking_norm():
    """Kernel path re-ranks from the resident S when the ranking norm is not
    the kernel's fused squared-l2."""
    base = ProjectedAdamRule(rank=8, projector="dct", residual="ef",
                             ranking_norm="l1", fused="off")
    ref, ref_state = _run_steps(base, (24, 40))
    got, got_state = _run_steps(dataclasses.replace(base, fused="on"),
                                (24, 40))
    _assert_step_parity(ref, got, "l1")
    np.testing.assert_array_equal(np.asarray(ref_state.proj),
                                  np.asarray(got_state.proj))


def test_stacked_leaf_dispatches_to_batched_kernels(monkeypatch):
    """A (layers, m, n) leaf must reach the batched kernel entry points with
    its leading axis intact — dispatch verified by spy, not inspection."""
    calls = {}

    def spy(name, orig):
        def wrapped(*args, **kw):
            calls.setdefault(name, []).append(
                tuple(a.ndim for a in args if hasattr(a, "ndim")))
            return orig(*args, **kw)
        return wrapped

    for name in ("dct_project_op", "colgather_matmul_dual_op",
                 "quantize_ef_op", "dequant_add_ef_op"):
        monkeypatch.setattr(fused_step.ops, name,
                            spy(name, getattr(fused_step.ops, name)))

    rule = ProjectedAdamRule(rank=8, projector="dct", residual="ef",
                             ef_dtype="q8", fused="on")
    _run_steps(rule, (3, 24, 40), n_steps=2)

    # g (3, m, n) hits the fused select+project kernel with its batch axis
    assert calls["dct_project_op"], "select+project kernel never dispatched"
    assert calls["dct_project_op"][0][0] == 3
    # both back-projections go through ONE dual-gather kernel call per step
    assert calls["colgather_matmul_dual_op"]
    assert calls["colgather_matmul_dual_op"][0][0] == 3
    # EF consumed and produced by the fused int8 kernels (no fp32 temp)
    assert calls["dequant_add_ef_op"] and calls["quantize_ef_op"]


def test_select_and_project_is_single_pass(monkeypatch):
    """The fused dct path performs exactly ONE G-sized matmul pass for
    select+project: one dct_project_op call, zero separate projection
    matmuls (idx + g_low both come out of it)."""
    n_calls = {"dct": 0}
    orig = fused_step.ops.dct_project_op

    def counting(*args, **kw):
        n_calls["dct"] += 1
        return orig(*args, **kw)

    monkeypatch.setattr(fused_step.ops, "dct_project_op", counting)
    gf = jnp.asarray(np.random.default_rng(0).standard_normal((24, 40)),
                     jnp.float32)
    from repro.core.dct import dct2_matrix
    q = dct2_matrix(40)
    idx, g_low = fused_step.select_and_project(gf, q, 8, mode="on")
    assert n_calls["dct"] == 1
    # and the extraction is exact: S[:, idx] == G @ Q[:, idx]
    from repro.core.selection import gather_columns
    qr = gather_columns(q, idx)
    np.testing.assert_allclose(np.asarray(g_low),
                               np.asarray(gf @ qr), atol=2e-5, rtol=1e-5)


def test_imports_take_no_backend():
    """Importing the package, the optimizer, the kernels, the serving layer
    or the training CLI initialises no JAX backend: a parent process (the
    restart supervisor) must leave the chip to its child."""
    import os
    import subprocess
    import sys

    code = ("import repro, repro.optim, repro.kernels, repro.serve, "
            "repro.launch.train, repro.train.supervisor\n"
            "from jax._src import xla_bridge\n"
            "assert not xla_bridge.backends_are_initialized()\n")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=os.path.join(root, "src"))
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr


def test_resolve_modes():
    assert fused_step.resolve("off") == "off"
    assert fused_step.resolve("on") == "on"
    assert fused_step.resolve("fft") == "fft"
    # auto degrades to the reference path off-TPU
    expected = "on" if fused_step.ops.on_tpu() else "off"
    assert fused_step.resolve("auto") == expected
    fused_step.set_default_fused_mode("fft")
    try:
        assert fused_step.resolve("auto") == "fft"
        assert fused_step.resolve("off") == "off"   # explicit beats default
    finally:
        fused_step.set_default_fused_mode("auto")
