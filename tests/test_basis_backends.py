"""The pluggable orthogonal-basis backend suite (core/transforms.py).

Covers the backend property contract (orthonormality across awkward
orders, fast-path == matmul-path parity incl. the Hadamard odd-n
fallback), the registry-sourced unknown-kind errors, the process-wide
BasisCache (adaptive-rebuild hit counter), the per-backend captured-energy
telemetry invariant, the DCT update against a dense float64 reference
computed in the test, and a reduced ZeRO-1 parity check per backend (8
forced host devices — the CI multidevice job).
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import transforms as tr
from repro.core.projectors import Projector, projector_kinds, shared_basis_for
from repro.optim.common import Context
from repro.optim.projected_adam import ProjectedAdamRule

BACKENDS = tr.backend_kinds()
assert set(BACKENDS) >= {"dct", "dst", "hadamard", "randortho"}


# ---------------------------------------------------------------------------
# property suite: orthonormality + fast-path parity
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n", [8, 17, 64])
@pytest.mark.parametrize("kind", BACKENDS)
def test_backend_matrix_orthonormal(kind, n):
    q = np.asarray(tr.get_backend(kind).matrix(n), dtype=np.float64)
    np.testing.assert_allclose(q.T @ q, np.eye(n), atol=5e-6,
                               err_msg=f"{kind} Q^T Q != I at n={n}")


@pytest.mark.parametrize("kind", BACKENDS)
def test_backend_matrix_orthonormal_4096_slice(kind):
    """At n=4096 the full n^2 Gram is wasteful; a random column slice of
    Q^T Q must still be the matching identity slice (and every sampled
    column unit-norm)."""
    n, k = 4096, 24
    q = np.asarray(tr.shared_basis(kind, n), dtype=np.float64)
    cols = np.random.default_rng(0).choice(n, size=k, replace=False)
    gram = q[:, cols].T @ q[:, cols]
    np.testing.assert_allclose(gram, np.eye(k), atol=2e-5,
                               err_msg=f"{kind} 4096-slice Gram != I")


@pytest.mark.parametrize("n", [8, 33, 64, 256])
@pytest.mark.parametrize("kind", BACKENDS)
def test_apply_fast_matches_matmul(kind, n):
    """``apply_fast`` (Makhoul FFT for dct, FHT butterfly for hadamard,
    matmul fallback elsewhere — incl. hadamard at non-power-of-two n)
    equals the matmul path to fp32 tolerance."""
    be = tr.get_backend(kind)
    x = jnp.asarray(
        np.random.default_rng(n).standard_normal((5, n)), jnp.float32)
    q = be.matrix(n)
    fast = np.asarray(be.apply_fast(x, q))
    mm = np.asarray(x @ q)
    np.testing.assert_allclose(fast, mm, atol=2e-5,
                               err_msg=f"{kind} fast != matmul at n={n}")


def test_fwht_equals_sylvester_matmul():
    """The in-jit butterfly is the exact (unnormalized) Sylvester WHT."""
    n = 64
    x = jnp.asarray(np.random.default_rng(1).standard_normal((3, n)),
                    jnp.float32)
    h = np.asarray(tr.hadamard_matrix(n)) * np.sqrt(n)   # ±1 Sylvester
    np.testing.assert_allclose(np.asarray(tr.fwht(x)), np.asarray(x) @ h,
                               atol=1e-4)
    with pytest.raises(ValueError, match="power-of-two"):
        tr.fwht(jnp.zeros((2, 12)))


def test_randortho_deterministic():
    a = np.asarray(tr.random_orthogonal_matrix(32))
    b = np.asarray(tr.random_orthogonal_matrix(32))
    np.testing.assert_array_equal(a, b)
    # diag(R) sign canonicalization picked a unique representative
    assert not np.allclose(a, np.asarray(tr.random_orthogonal_matrix(32, seed=1)))


# ---------------------------------------------------------------------------
# registry + error messages
# ---------------------------------------------------------------------------
def test_unknown_kind_is_eager_and_lists_allowed():
    with pytest.raises(ValueError, match="unknown projector kind 'wavelet'"):
        Projector(kind="wavelet", r=4)
    with pytest.raises(ValueError, match="allowed:.*dct.*svd"):
        Projector(kind="wavelet", r=4)
    with pytest.raises(ValueError, match="unknown projector"):
        ProjectedAdamRule(projector="wavelet")


def test_dispatch_paths_carry_registry_message(monkeypatch):
    """The defensive raises inside update/project/backproject must carry
    the same registry-sourced message as the eager validation — not the
    historical bare ``ValueError(self.kind)`` (a backend deregistered
    after construction is the only way to reach them)."""
    p = Projector(kind="dst", r=4)
    g = jnp.ones((6, 8), jnp.float32)
    state = p.init(g.shape)
    monkeypatch.delitem(tr._REGISTRY, "dst")
    for call in (lambda: p.update(g, state),
                 lambda: p.project(g, state),
                 lambda: p.backproject(jnp.ones((6, 4)), state, n=8),
                 lambda: p.basis_matrix(state, 8),
                 lambda: p.init(g.shape)):
        with pytest.raises(ValueError, match="unknown projector kind 'dst'"):
            call()
        with pytest.raises(ValueError, match="allowed:"):
            call()


def test_dense_projector_requests_no_shared_basis():
    """A dense-projector rule left at the default needs_shared_basis=True
    must not request a (nonexistent) 'svd' shared basis — stored-basis
    init worked for this configuration pre-refactor and must keep
    working."""
    from repro.optim.transform import as_optimizer, lowrank_project

    rule = ProjectedAdamRule(rank=4, projector="svd", residual="discard")
    assert rule.needs_shared_basis          # the default, deliberately
    assert rule.basis_sizes((12, 8)) == ()
    params = {"w": jnp.zeros((12, 8), jnp.float32)}
    state = as_optimizer(lowrank_project(rule)).init(params)   # no raise
    assert state.bases == {}


def test_register_backend_refuses_silent_overwrite():
    with pytest.raises(ValueError, match="already registered"):
        tr.register_backend(tr.DCTBackend())


def test_projector_kinds_tracks_registry():
    class _Stub(tr.BasisBackend):
        kind = "stub_basis"

        def matrix(self, n, dtype=jnp.float32):
            return jnp.eye(n, dtype=dtype)

    tr.register_backend(_Stub())
    try:
        assert "stub_basis" in projector_kinds()
        p = Projector(kind="stub_basis", r=2)          # eager check passes
        assert p.needs_shared_basis
    finally:
        del tr._REGISTRY["stub_basis"]


# ---------------------------------------------------------------------------
# projector roundtrip through every backend
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("kind", BACKENDS)
def test_backend_projector_roundtrip(kind):
    m, n, r = 24, 16, 6
    p = Projector(kind=kind, r=r)
    g = jnp.asarray(np.random.default_rng(0).standard_normal((m, n)),
                    jnp.float32)
    q = shared_basis_for(kind, n)
    assert q is not None and q.shape == (n, n)
    state = p.update(g, p.init(g.shape), shared_q=q)
    assert state.dtype == jnp.int32 and state.shape == (r,)  # paper: r ints
    low = p.project(g, state, shared_q=q)
    rec = p.backproject(low, state, shared_q=q, n=n)
    assert rec.shape == (m, n)
    low2 = p.project(rec, state, shared_q=q)                 # P^2 = P
    np.testing.assert_allclose(np.asarray(low2), np.asarray(low), atol=1e-4)


# ---------------------------------------------------------------------------
# captured-energy telemetry invariant, per backend
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("fused", ["off", "on", "fft"])
@pytest.mark.parametrize("kind", BACKENDS)
def test_captured_energy_at_least_r_over_n(kind, fused):
    """Top-r selection of n orthogonal directions captures at least the
    mean share r/n of ||G||_F^2 (§4.1) — for *any* orthogonal basis."""
    from repro.telemetry.stats import collect

    shape, r = (3, 24, 40), 8
    n = min(shape[-2:])
    rule = ProjectedAdamRule(rank=r, projector=kind, residual="ef",
                             ef_dtype="q8", fused=fused,
                             needs_shared_basis=True)
    state = rule.init(shape, jnp.float32)
    g = jnp.asarray(np.random.default_rng(5).standard_normal(shape),
                    jnp.float32)

    with collect() as col:
        @jax.jit
        def step(g, state):
            ctx = Context(step=jnp.int32(1), bases={},
                          key=jax.random.PRNGKey(0),
                          stats=col.scope("w"))
            d, s = rule.update(g, state, jnp.zeros(shape, jnp.float32), ctx)
            return d, s, col.tree()          # stats ride out as jit outputs

        _, _, tel = step(g, state)
    stats = jax.device_get(tel)["w"]
    cap = np.asarray(stats.captured_energy)
    assert cap.shape == shape[:-2]
    assert np.all(cap >= r / n - 1e-5), (kind, fused, cap, r / n)
    assert np.all(cap <= 1.0 + 1e-5)


# ---------------------------------------------------------------------------
# fused execution parity for the non-dct backends
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("shape", [(24, 40), (3, 24, 40), (33, 17)],
                         ids=["2d", "stacked", "odd"])
@pytest.mark.parametrize("kind", ["dst", "hadamard", "randortho"])
def test_fused_matches_reference_new_backends(kind, shape):
    """"on" (Pallas interpret) and "fft" (backend fast transform) must
    match the "off" reference through the state feedback loop — the same
    contract tests/test_fused_step.py pins for dct."""
    def run(rule, n_steps=3, seed=0):
        rng = np.random.default_rng(seed)
        state = rule.init(shape, jnp.float32)
        param = jnp.zeros(shape, jnp.float32)

        @functools.partial(jax.jit)
        def step_fn(g, state, step):
            ctx = Context(step=step, bases={}, key=jax.random.PRNGKey(7))
            return rule.update(g, state, param, ctx)

        outs = []
        for t in range(1, n_steps + 1):
            g = jnp.asarray(rng.standard_normal(shape), jnp.float32)
            d, state = step_fn(g, state, jnp.asarray(t, jnp.int32))
            outs.append(np.asarray(d))
        return outs

    base = ProjectedAdamRule(rank=8, projector=kind, residual="ef",
                             ef_dtype="q8", fused="off",
                             needs_shared_basis=True)
    ref = run(base)
    for mode in ("on", "fft"):
        got = run(dataclasses.replace(base, fused=mode))
        for t, (a, b) in enumerate(zip(ref, got)):
            tol = 3e-4 if t == 0 else 5e-3
            np.testing.assert_allclose(
                b, a, atol=tol, rtol=5e-3,
                err_msg=f"{kind}/{mode} step {t + 1}")


# ---------------------------------------------------------------------------
# DCT parity against a dense float64 reference computed in the test
# ---------------------------------------------------------------------------
_PIN_SHAPES = {"2d": (24, 40), "stacked": (3, 24, 40), "odd": (33, 17),
               "transposed": (16, 48)}
# float32 update vs the float64 reference: the projections round at about
# 1e-7 relative and Adam's normalisation keeps update entries near unit
# size, so 1e-4 covers the rounding with room and still catches a wrong
# column, a missed rotation or a stale error-feedback buffer
_DCT_REF_RTOL, _DCT_REF_ATOL = 1e-4, 1e-4


def _dense_dct_adamw(grads, *, rank, interval, b1=0.9, b2=0.999, eps=1e-8):
    """DCT-AdamW with int8 error feedback, written from the algorithm in
    optim/projected_adam.py with a dense orthonormal DCT-II matrix in
    float64. Returns the update of every step."""
    shape = grads[0].shape
    transposed = shape[-1] > shape[-2]
    rows, n = max(shape[-2:]), min(shape[-2:])
    batch = shape[:-2]
    k = np.arange(n)[:, None]
    j = np.arange(n)[None, :]
    q = np.sqrt(2.0 / n) * np.cos(np.pi * (2 * k + 1) * j / (2 * n))
    q[:, 0] /= np.sqrt(2.0)                  # column j = frequency j
    r = min(rank, n)
    m = np.zeros((*batch, rows, r))
    v = np.zeros_like(m)
    idx = np.broadcast_to(np.arange(r), (*batch, r))
    ef = np.zeros((*batch, rows, n))         # dequantised int8 EF buffer
    tiny = np.finfo(np.float32).tiny
    outs = []
    for t, g in enumerate(grads, start=1):
        gf = np.asarray(g, np.float64)
        gf = (np.swapaxes(gf, -1, -2) if transposed else gf) + ef
        if t == 1 or t % interval == 1:      # refresh: top-r column energy
            norms = ((gf @ q) ** 2).sum(axis=-2)
            new = np.sort(np.argsort(-norms, axis=-1)[..., :r], axis=-1)
            rot = (idx[..., :, None] == new[..., None, :]).astype(np.float64)
            m, v, idx = m @ rot, np.abs(v @ rot), new
        qr = np.swapaxes(q.T[idx], -1, -2)   # (..., n, r) selected columns
        g_low = gf @ qr
        m = b1 * m + (1 - b1) * g_low
        v = b2 * v + (1 - b2) * g_low ** 2
        u = (m / (1 - b1 ** t)) / (np.sqrt(v / (1 - b2 ** t)) + eps)
        d = u @ np.swapaxes(qr, -1, -2)
        resid = gf - g_low @ np.swapaxes(qr, -1, -2)
        scale = np.maximum(np.abs(resid).max(axis=-1, keepdims=True) / 127,
                           tiny)
        ef = np.clip(np.round(resid / scale), -127, 127) * scale
        outs.append(np.swapaxes(d, -1, -2) if transposed else d)
    return outs


@pytest.mark.parametrize("mode", ["off", "on", "fft"])
@pytest.mark.parametrize("shape_id", list(_PIN_SHAPES))
def test_dct_bit_identical_to_pre_refactor(mode, shape_id):
    """The rank-8, int8-EF, T_u=2 DCT update of every fused mode matches
    the dense float64 reference over three steps (refresh, keep,
    refresh)."""
    shape = _PIN_SHAPES[shape_id]
    rule = ProjectedAdamRule(rank=8, projector="dct", residual="ef",
                             ef_dtype="q8", update_interval=2, fused=mode)
    rng = np.random.default_rng(0)
    state = rule.init(shape, jnp.float32)
    param = jnp.zeros(shape, jnp.float32)

    @jax.jit
    def step_fn(g, state, step):
        ctx = Context(step=step, bases={}, key=jax.random.PRNGKey(7))
        return rule.update(g, state, param, ctx)

    grads = [rng.standard_normal(shape).astype(np.float32) for _ in range(3)]
    want = _dense_dct_adamw(grads, rank=8, interval=2)
    for t, g in enumerate(grads, start=1):
        d, state = step_fn(jnp.asarray(g), state, jnp.asarray(t, jnp.int32))
        np.testing.assert_allclose(
            np.asarray(d), want[t - 1], rtol=_DCT_REF_RTOL,
            atol=_DCT_REF_ATOL,
            err_msg=f"dct update differs from the dense reference "
                    f"({mode}/{shape_id}, step {t})")


# ---------------------------------------------------------------------------
# BasisCache: adaptive rebuilds must hit, not recompute
# ---------------------------------------------------------------------------
def test_basis_cache_hit_on_adaptive_rebuild():
    """telemetry/adaptive.py rebuilds the optimizer via
    ``lowrank_project(overrides=...)`` + ``optimizer.init``; the second
    init must serve every shared basis from the cache (counter-observable)
    instead of recomputing the n×n matrices."""
    from repro.optim.api import get_optimizer

    params = {"w": jnp.zeros((48, 32), jnp.float32),
              "w2": jnp.zeros((48, 24), jnp.float32)}
    cache = tr.basis_cache()
    cache.clear()

    def make_optimizer(overrides=None):
        return get_optimizer("dct_adamw", lr=1e-2, rank=8,
                             overrides=overrides)

    opt = make_optimizer()
    opt.init(params)
    first = cache.stats()
    assert first["misses"] >= 2 and first["entries"] >= 2   # 32 and 24

    # the adaptive-controller cycle: new overrides -> rebuilt optimizer ->
    # fresh init for state migration (adaptive.AdaptiveOptimizerManager)
    opt2 = make_optimizer({"w": {"rank": 12}})
    opt2.init(params)
    second = cache.stats()
    assert second["misses"] == first["misses"], \
        "adaptive rebuild recomputed a shared basis (cache miss)"
    assert second["hits"] >= first["hits"] + 2, \
        "adaptive rebuild did not hit the BasisCache"


def test_basis_cache_serves_all_kinds():
    cache = tr.basis_cache()
    for kind in BACKENDS:
        a = tr.shared_basis(kind, 16)
        b = tr.shared_basis(kind, 16)
        # value-identical but a *fresh* device buffer per get — entries
        # land in donated optimizer state, so sharing one buffer would
        # leave the cache deleted after the first donating step
        assert a is not b
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert cache.stats()["hits"] >= len(BACKENDS)


# ---------------------------------------------------------------------------
# reduced ZeRO-1 parity per backend (CI multidevice job)
# ---------------------------------------------------------------------------
@pytest.mark.skipif(jax.device_count() < 8,
                    reason="needs 8 devices (CI multidevice job forces "
                           "8 host devices via XLA_FLAGS)")
@pytest.mark.parametrize("kind", ["dst", "hadamard", "randortho"])
def test_zero_parity_new_backends_multidevice(kind):
    """Sharded vs replicated updates equal to fp32 rounding for every
    non-dct backend — the reduced companion of tests/test_zero_parity.py
    (which pins dct exhaustively)."""
    from repro.launch.mesh import make_mesh
    from repro.optim.transform import matrix_optimizer
    from repro.parallel.zero import ZeroConfig

    rule = ProjectedAdamRule(rank=8, projector=kind, residual="ef",
                             ef_dtype="q8", fused="off",
                             needs_shared_basis=True)
    assert rule.zero_shardable
    params = {"w": jnp.zeros((64, 32), jnp.float32)}
    grads = {"w": jnp.asarray(
        np.random.default_rng(0).standard_normal((64, 32)), jnp.float32)}
    rep = matrix_optimizer(rule, 1e-2)
    zo = matrix_optimizer(rule, 1e-2, zero=ZeroConfig(mode="1",
                                                      axes=("data",)))
    u_rep, _ = jax.jit(rep.update)(grads, rep.init(params), params)
    with jax.set_mesh(make_mesh((8,), ("data",))):
        u_z, _ = jax.jit(zo.update)(grads, zo.init(params), params)
    # fp32 rounding: the row blocks' reductions run in another order
    np.testing.assert_allclose(
        np.asarray(jax.device_get(u_z["w"])), np.asarray(u_rep["w"]),
        rtol=1e-5, atol=1e-7,
        err_msg=f"{kind}: sharded update differs from replicated")
