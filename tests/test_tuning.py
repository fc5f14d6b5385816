"""Autotuner + low-precision compute-path tests (DESIGN.md §15).

Pins the PR's two contracts:

1. **Untuned is bit-identical.** ``block=None`` with an empty TuningCache
   resolves to exactly the hardcoded defaults, per kernel family; the JSON
   file format round-trips losslessly; the roofline pruner (not wall-clock
   sweeps) is what cuts the measurement grid.
2. **Low precision is bounded.** ``compute_dtype`` in {"bf16", "int8"}
   stays inside ``LOWP_ERROR_BOUNDS`` vs fp32 across stacked / odd-shaped
   / transposed leaves, in every fused mode, and the Pallas int8 kernels
   match their jnp mirrors to float-epilogue tolerance (int32 accumulation
   is exact; XLA may reassociate the two scale multiplies, so the
   comparison is allclose at ~1e-5, not equality). The q8 scale guard
   keeps all-zero and subnormal rows NaN-free through the fused EF path.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.dct import dct2_matrix
from repro.kernels.lowp import LOWP_ERROR_BOUNDS, lowp_matmul
from repro.roofline import hw
from repro.roofline.analysis import RooflineReport
from repro.tune import (KERNELS, TuningCache, make_key, resolve_block,
                        tuning_cache)
from repro.tune.prune import candidate_blocks, prune


@pytest.fixture(autouse=True)
def _clean_global_cache():
    """Tests mutate the process-wide cache; never leak entries (a stale
    entry would change other tests' Pallas block sizes and break their
    bit-exactness pins)."""
    tuning_cache().clear()
    yield
    tuning_cache().clear()


def _rand(shape, dtype=jnp.float32, seed=0):
    x = np.random.default_rng(seed).standard_normal(shape)
    return jnp.asarray(x.astype(np.float32)).astype(dtype)


# ---------------------------------------------------------------------------
# cache: keys, counters, persistence
# ---------------------------------------------------------------------------
def test_make_key_normalizes():
    k = make_key("dct_project", [2, jnp.int32(64), 64], 0, jnp.float32,
                 "cpu")
    assert k == ("dct_project", (2, 64, 64), 0, "float32", "cpu")
    assert hash(k)  # fully hashable/static
    # platform defaults to the active jax backend
    assert make_key("quant_ef", (1, 8, 8), 0, "float32")[-1] \
        == jax.default_backend()


def test_cache_hit_miss_counters():
    c = TuningCache()
    key = make_key("dct_project", (1, 64, 64), 0, "float32", "cpu")
    assert c.lookup(key) is None and c.misses == 1 and c.hits == 0
    c.store(key, (128, 128, 128))
    assert c.lookup(key) == (128, 128, 128)
    assert (c.hits, c.misses) == (1, 1)
    assert key in c and len(c) == 1


def test_cache_json_round_trip_stable(tmp_path):
    c = TuningCache()
    c.store(make_key("dct_project", (1, 64, 64), 0, "float32", "cpu"),
            (128, 128, 128))
    c.store(make_key("quant_ef", (2, 64, 64), 0, "float32", "cpu"), 128)
    p1, p2 = tmp_path / "a.json", tmp_path / "b.json"
    c.save(str(p1))
    c2 = TuningCache()
    assert c2.load(str(p1)) == 2
    assert c2.entries() == c.entries()
    # tuple vs bare-int block values survive the round trip typed
    key_q = make_key("quant_ef", (2, 64, 64), 0, "float32", "cpu")
    assert isinstance(c2.entries()[key_q], int)
    # byte-stable: save -> load -> save is the identical file
    c2.save(str(p2))
    assert p1.read_text() == p2.read_text()


def test_cache_version_check(tmp_path):
    with pytest.raises(ValueError, match="version"):
        TuningCache().from_json({"version": 99, "entries": []})


def test_resolve_block_miss_returns_default():
    before = tuning_cache().misses
    assert resolve_block("dct_project", (1, 64, 64), 0, "float32",
                         (256, 256, 256)) == (256, 256, 256)
    assert tuning_cache().misses == before + 1


# ---------------------------------------------------------------------------
# pruning: roofline predictions drive the cut
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("kernel,shape,rank", [
    ("dct_project", (1, 1024, 1024), 0),
    ("colgather_matmul", (2, 512, 1024), 128),
    ("quant_ef", (1, 1024, 1024), 0),
    ("newton_schulz", (1, 128, 1024), 128),
])
def test_prune_uses_roofline(kernel, shape, rank):
    keep = 4
    survivors = prune(kernel, shape, rank, "float32", arch="v5e", keep=keep)
    grid = candidate_blocks(kernel, shape, rank)
    assert 1 <= len(survivors) <= keep < len(grid)  # it actually pruned
    spec = hw.get_arch("v5e")
    preds = [c.predicted_s for c in survivors]
    assert preds == sorted(preds)  # ranked by predicted step time
    for c in survivors:
        # the prediction is a real roofline report priced at the arch
        assert isinstance(c.report, RooflineReport)
        assert c.report.device_arch == "v5e"
        assert c.predicted_s == c.report.step_s
        assert c.bound in ("compute", "memory")
        assert c.vmem_bytes <= spec.vmem_bytes * 0.9  # fits the envelope
        assert c.block in grid


def test_prune_bound_classification_tracks_arch():
    # quantize/dequant streams bytes: memory-bound on any real accelerator
    assert all(c.bound == "memory"
               for c in prune("quant_ef", (2, 1024, 1024), 0, arch="v5e"))
    # a big projection matmul on the bandwidth-rich cpu-est table flips to
    # compute-bound; on v5e's HBM it stays memory-bound at this size
    big = ("dct_project", (1, 4096, 4096), 0)
    assert any(c.bound == "compute"
               for c in prune(*big, "float32", arch="cpu-est"))


def test_prune_vmem_fallback():
    # every candidate of the colgather family at n=4096 carries the full
    # (n, bn) Q^T stripe; with a deliberately tiny VMEM nothing fits and
    # the pruner must still return the smallest-footprint candidates
    survivors = prune("colgather_matmul", (1, 4096, 4096), 256,
                      arch="v5e", keep=3, vmem_frac=1e-6)
    assert len(survivors) == 3
    foots = [c.vmem_bytes for c in survivors]
    all_foots = sorted(c.vmem_bytes for c in (
        prune("colgather_matmul", (1, 4096, 4096), 256, arch="v5e",
              keep=100, vmem_frac=1e9)))
    assert max(foots) <= all_foots[2]


# ---------------------------------------------------------------------------
# block=None: bit-identical fallback + tuned-block dispatch
# ---------------------------------------------------------------------------
def test_block_none_bit_identical_untuned():
    import importlib

    from repro.kernels import (colgather_matmul, colgather_matmul_dual,
                               dct_project, dequant_add_ef, ns_iteration,
                               quantize_ef)
    # attribute access on repro.kernels returns the re-exported functions,
    # so the defining modules come via importlib
    dp_mod = importlib.import_module("repro.kernels.dct_project")
    cg_mod = importlib.import_module("repro.kernels.colgather_matmul")
    q8_mod = importlib.import_module("repro.kernels.quant_ef")
    ns_mod = importlib.import_module("repro.kernels.newton_schulz")

    g = _rand((2, 65, 48), seed=1)
    q = dct2_matrix(48)
    s0, n0 = dct_project(g, q, interpret=True)
    s1, n1 = dct_project(g, q, block=dp_mod.DEFAULT_BLOCK, interpret=True)
    assert np.array_equal(np.asarray(s0), np.asarray(s1))
    assert np.array_equal(np.asarray(n0), np.asarray(n1))

    b = _rand((2, 65, 8), seed=2)
    qt = jnp.swapaxes(q, -1, -2)
    idx = jnp.tile(jnp.arange(8, dtype=jnp.int32)[None], (2, 1))
    o0 = colgather_matmul(b, qt, idx, interpret=True)
    o1 = colgather_matmul(b, qt, idx, block=cg_mod.DEFAULT_BLOCK,
                          interpret=True)
    assert np.array_equal(np.asarray(o0), np.asarray(o1))
    d0 = colgather_matmul_dual(b, b, qt, idx, interpret=True)
    d1 = colgather_matmul_dual(b, b, qt, idx, block=cg_mod.DEFAULT_BLOCK,
                               interpret=True)
    assert all(np.array_equal(np.asarray(a), np.asarray(x))
               for a, x in zip(d0, d1))

    x = _rand((2, 33, 48), seed=3)
    qv0, sc0 = quantize_ef(x, interpret=True)
    qv1, sc1 = quantize_ef(x, bm=q8_mod.DEFAULT_BM, interpret=True)
    assert np.array_equal(np.asarray(qv0), np.asarray(qv1))
    assert np.array_equal(np.asarray(sc0), np.asarray(sc1))
    y0 = dequant_add_ef(x, qv0, sc0, interpret=True)
    y1 = dequant_add_ef(x, qv0, sc0, bm=q8_mod.DEFAULT_BM, interpret=True)
    assert np.array_equal(np.asarray(y0), np.asarray(y1))

    w = _rand((1, 16, 40), seed=4)
    z0 = ns_iteration(w, interpret=True)
    z1 = ns_iteration(w, bm=ns_mod.DEFAULT_BM, interpret=True)
    assert np.array_equal(np.asarray(z0), np.asarray(z1))


def test_tuned_block_reaches_kernel_dispatch(monkeypatch):
    """A stored cache entry must change what the jitted kernel is traced
    with — the CI tune job's dispatch-spy contract, in-tree."""
    import importlib
    dp_mod = importlib.import_module("repro.kernels.dct_project")
    from repro.kernels import dct_project

    g = _rand((1, 64, 64), seed=5)
    q = dct2_matrix(64)
    tuned = (128, 64, 64)
    tuning_cache().store(make_key("dct_project", (1, 64, 64), 0, "float32"),
                         tuned)

    seen = []
    orig = dp_mod._dct_project

    def spy(g, q, **kw):
        seen.append(kw["block"])
        return orig(g, q, **kw)

    monkeypatch.setattr(dp_mod, "_dct_project", spy)
    hits = tuning_cache().hits
    s_tuned, n_tuned = dct_project(g, q, interpret=True)
    assert seen == [tuned]
    assert tuning_cache().hits == hits + 1
    # a tuned block changes scheduling, never semantics
    s_dflt, n_dflt = dct_project(g, q, block=dp_mod.DEFAULT_BLOCK,
                                 interpret=True)
    np.testing.assert_allclose(np.asarray(s_tuned), np.asarray(s_dflt),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(n_tuned), np.asarray(n_dflt),
                               rtol=1e-5, atol=1e-5)


def test_tune_kernel_stores_winner_and_record(tmp_path):
    from repro.tune import tune_kernel

    cache = TuningCache()
    rec = tune_kernel("quant_ef", (1, 64, 64), 0, "float32", keep=2,
                      interpret=True, iters=1, warmup=1, cache=cache)
    assert len(cache) == 1
    key = make_key("quant_ef", (1, 64, 64), 0, "float32")
    assert cache.lookup(key) is not None
    for field in ("kernel", "shape", "grid_size", "survivors", "timings_s",
                  "default_block", "default_s", "best_block", "best_s",
                  "speedup", "bound", "platform"):
        assert field in rec, field
    # the default was measured even if pruned out, and the winner's timing
    # can never exceed it (ties break toward the default)
    assert rec["default_block"] in rec["timings_s"]
    assert rec["best_s"] <= rec["default_s"]
    # the record round-trips through the BENCH json layer
    (tmp_path / "rec.json").write_text(json.dumps(rec))


# ---------------------------------------------------------------------------
# low-precision compute path
# ---------------------------------------------------------------------------
LEAF_SHAPES = [
    ((3, 64, 48), 48),    # stacked
    ((33, 40), 40),       # odd, non-multiple of any block
    ((48, 64), 64),       # transposed orientation (m < n)
]


@pytest.mark.parametrize("gshape,n", LEAF_SHAPES)
@pytest.mark.parametrize("dt", ["bf16", "int8"])
def test_lowp_matmul_within_bounds(gshape, n, dt):
    g = _rand(gshape, seed=sum(gshape))
    q = dct2_matrix(n)
    ref = g @ q
    out = lowp_matmul(g, q, dt)
    rel = float(jnp.linalg.norm(out - ref) / jnp.linalg.norm(ref))
    assert rel <= LOWP_ERROR_BOUNDS[dt], (dt, rel)


@pytest.mark.parametrize("gshape,n", LEAF_SHAPES)
@pytest.mark.parametrize("mode", ["off", "on", "fft"])
@pytest.mark.parametrize("dt", ["bf16", "int8"])
def test_select_and_project_lowp_bounded_all_modes(gshape, n, mode, dt):
    from repro.core import fused_step

    g = _rand(gshape, seed=sum(gshape) + 7)
    q = dct2_matrix(n)
    r = 8
    idx_ref, low_ref = fused_step.select_and_project(g, q, r, mode=mode)
    idx_dt, low_dt = fused_step.select_and_project(g, q, r, mode=mode,
                                                   compute_dtype=dt)
    # selection overlap: the ranking statistic survives the quantization
    ref_set = set(np.asarray(idx_ref).reshape(-1).tolist())
    got_set = set(np.asarray(idx_dt).reshape(-1).tolist())
    assert len(ref_set & got_set) / len(ref_set) >= 0.75, (mode, dt)
    # projected factor error vs the fp32 transform, on the common columns
    s_ref = np.asarray(g @ q, np.float64)
    s_dt = np.asarray(lowp_matmul(g, q, dt), np.float64)
    rel = np.linalg.norm(s_dt - s_ref) / np.linalg.norm(s_ref)
    assert rel <= LOWP_ERROR_BOUNDS[dt], (mode, dt, rel)


def test_fp32_mode_paths_unchanged():
    """compute_dtype="fp32" must leave every dispatch mode's fp32 math
    untouched (the pre-PR pin): fft mode still runs the fast transform,
    off mode the reference selection."""
    from repro.core import fused_step
    from repro.core.dct import makhoul_dct2
    from repro.core.selection import dynamic_column_selection

    g = _rand((2, 32, 48), seed=11)
    q = dct2_matrix(48)
    idx, low = fused_step.select_and_project(g, q, 8, mode="fft",
                                             compute_dtype="fp32")
    s = makhoul_dct2(g)
    idx_ref, low_ref = dynamic_column_selection(s, 8)
    assert np.array_equal(np.asarray(idx), np.asarray(idx_ref))
    assert np.array_equal(np.asarray(low), np.asarray(low_ref))


@pytest.mark.parametrize("gshape,n", LEAF_SHAPES)
def test_int8_kernel_matches_mirror(gshape, n):
    """Pallas int8 dct_project vs the jnp mirror: same quantization, same
    int32 accumulation; only the float epilogue may reassociate."""
    from repro.kernels import dct_project

    g = _rand(gshape, seed=sum(gshape) + 13)
    q = dct2_matrix(n)
    s_k, norms_k = dct_project(g, q, block=(32, 32, 32), interpret=True,
                               compute_dtype="int8")
    s_m = lowp_matmul(g, q, "int8")
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_m),
                               rtol=1e-5, atol=1e-5)
    norms_m = jnp.sum(jnp.square(s_m), axis=-2)  # per-batch column energy
    np.testing.assert_allclose(np.asarray(norms_k), np.asarray(norms_m),
                               rtol=1e-4, atol=1e-4)


def test_int8_colgather_matches_mirror():
    """Matches the jnp mirror, also at a width that is not a multiple of
    the kernel's four-row int8 packing."""
    from repro.kernels import colgather_matmul, colgather_matmul_dual
    from repro.kernels.lowp import lowp_gather_matmul

    for n in (48, 50):
        b = _rand((2, 40, 8), seed=17)
        q = dct2_matrix(n)
        qt = jnp.swapaxes(q, -1, -2)
        idx = jnp.stack([jnp.arange(8), jnp.arange(8) * 3 % n + 1]
                        ).astype(jnp.int32)
        out_k = colgather_matmul(b, qt, idx, block=(32, 32), interpret=True,
                                 compute_dtype="int8")
        (out_m,) = lowp_gather_matmul((b,), qt, idx, "int8")
        np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_m),
                                   rtol=1e-5, atol=1e-5)
        b2 = _rand((2, 40, 8), seed=19)
        d_k = colgather_matmul_dual(b, b2, qt, idx, block=(32, 32),
                                    interpret=True, compute_dtype="int8")
        d_m = lowp_gather_matmul((b, b2), qt, idx, "int8")
        for got, want in zip(d_k, d_m):
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=1e-5, atol=1e-5)
        # and the fp32 back-projection ground truth stays within the int8
        # bound
        ref = jnp.einsum("bmr,brn->bmn", b, jnp.take(qt, idx, axis=0))
        rel = float(jnp.linalg.norm(out_k - ref) / jnp.linalg.norm(ref))
        assert rel <= LOWP_ERROR_BOUNDS["int8"]


@pytest.mark.parametrize("dt", ["bf16", "int8"])
def test_rule_level_lowp_close_to_fp32(dt):
    """One full ProjectedAdamRule update in low precision stays close to
    the fp32 update — the end-to-end plumbing test for compute_dtype."""
    import dataclasses

    from repro.optim.projected_adam import ProjectedAdamRule
    from repro.optim.transform import matrix_optimizer

    shape = (2, 48, 64)
    params = {"w": jnp.zeros(shape, jnp.float32)}
    grads = {"w": _rand(shape, seed=23)}
    base = ProjectedAdamRule(rank=8, projector="dct", residual="ef",
                             ef_dtype="fp32", fused="fft")
    outs = {}
    for cdt in ("fp32", dt):
        rule = dataclasses.replace(base, compute_dtype=cdt)
        opt = matrix_optimizer(rule, 1e-3)
        state = opt.init(params)
        d, _ = opt.update(grads, state, params)
        outs[cdt] = np.asarray(d["w"], np.float64)
    denom = np.linalg.norm(outs["fp32"]) or 1.0
    rel = np.linalg.norm(outs[dt] - outs["fp32"]) / denom
    # Adam normalizes per-coordinate, so amplification over the matmul
    # bound is expected; 10x the bound still separates real regressions
    # (a wrong scale fold is O(1) off) from quantization noise
    assert rel <= 10 * LOWP_ERROR_BOUNDS[dt], (dt, rel)
    # and a strictly positive difference: bit-identity to fp32 would mean
    # compute_dtype silently fell off the dispatch path
    assert rel > 0, dt


def test_lowp_refuses_reference_path():
    """A non-fp32 compute_dtype must fail loudly, never silently run fp32:
    eagerly for fused="off", at trace time when fused="auto" resolves to
    the reference path (the off-TPU default) or the projector is
    dense-basis."""
    import dataclasses

    from repro.core import fused_step
    from repro.optim.projected_adam import ProjectedAdamRule
    from repro.optim.transform import matrix_optimizer

    with pytest.raises(ValueError, match="compute_dtype"):
        ProjectedAdamRule(rank=8, fused="off", compute_dtype="int8")

    params = {"w": jnp.zeros((16, 16), jnp.float32)}
    grads = {"w": _rand((16, 16), seed=7)}
    if fused_step.resolve("auto") == "off":      # true on every CI backend
        rule = ProjectedAdamRule(rank=8, fused="auto", compute_dtype="int8")
        opt = matrix_optimizer(rule, 1e-3)
        state = opt.init(params)
        with pytest.raises(ValueError, match="fused"):
            opt.update(grads, state, params)
    # dense-basis projector: no fused dataflow regardless of mode
    rule = ProjectedAdamRule(rank=8, projector="svd", fused="fft",
                             compute_dtype="int8")
    opt = matrix_optimizer(rule, 1e-3)
    state = opt.init(params)
    with pytest.raises(ValueError, match="fused"):
        opt.update(grads, state, params)


# ---------------------------------------------------------------------------
# q8 scale guard: zero + subnormal rows through the fused EF path
# ---------------------------------------------------------------------------
def test_q8_zero_and_subnormal_rows_finite():
    from repro.core.error_feedback import dequantize_q8, quantize_q8
    from repro.kernels import quantize_ef
    from repro.kernels.lowp import F32_TINY
    from repro.kernels.ref import quantize_ef_ref

    x = np.zeros((4, 16), np.float32)
    x[1] = 2e-45            # subnormal row: amax/127 underflows to 0.0
    x[2] = np.linspace(-1, 1, 16)
    x = jnp.asarray(x)
    for name, (qv, scale) in {
            "kernel": quantize_ef(x, bm=2, interpret=True),
            "ref": quantize_ef_ref(x),
            "core": quantize_q8(x)}.items():
        qn, sn = np.asarray(qv, np.int32), np.asarray(scale)
        assert np.isfinite(sn).all(), name
        assert (sn >= F32_TINY).all(), name            # the guard
        assert np.isfinite(qn.astype(np.float32) * sn).all(), name
        # zero/subnormal rows dequantize to exactly zero payload
        assert (qn[0] == 0).all() and (qn[1] == 0).all(), name
    buf = quantize_q8(x)
    assert np.isfinite(np.asarray(dequantize_q8(buf))).all()


def test_q8_guard_through_fused_ef_rule():
    """A gradient with an all-zero row must survive a full q8-EF fused
    update without NaNs (the regression the scale guard exists for)."""
    from repro.optim.projected_adam import ProjectedAdamRule
    from repro.optim.transform import matrix_optimizer

    g = np.array(_rand((2, 32, 48), seed=29))
    g[0, 5, :] = 0.0
    g[1, 7, :] = 2e-45
    grads = {"w": jnp.asarray(g)}
    params = {"w": jnp.zeros((2, 32, 48), jnp.float32)}
    for fused in ("off", "on", "fft"):
        rule = ProjectedAdamRule(rank=8, projector="dct", residual="ef",
                                 ef_dtype="q8", fused=fused)
        opt = matrix_optimizer(rule, 1e-3)
        state = opt.init(params)
        d, new_state = opt.update(grads, state, params)
        d, new_state = opt.update(grads, new_state, params)  # EF consumed
        assert np.isfinite(np.asarray(d["w"])).all(), fused


def test_kernels_iterate_cache_families():
    """Every family the cache claims to key is a real tunable entry point
    with a default + candidate grid."""
    from repro.tune.autotune import default_block

    for k in KERNELS:
        assert candidate_blocks(k, (1, 128, 128), 32)
        assert default_block(k) is not None
