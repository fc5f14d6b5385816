"""Per-kernel allclose tests: Pallas (interpret mode) vs pure-jnp oracle,
swept over shapes and dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.dct import dct2_matrix
from repro.kernels import ref
from repro.kernels.colgather_matmul import colgather_matmul
from repro.kernels.dct_project import dct_project
from repro.kernels.newton_schulz import newton_schulz_pallas, ns_iteration
from repro.kernels.quant_ef import dequant_add_ef, quantize_ef


def _rand(shape, dtype, seed=0, scale=1.0):
    x = np.random.default_rng(seed).standard_normal(shape) * scale
    return jnp.asarray(x.astype(np.float32)).astype(dtype)


# ---------------------------------------------------------------------------
# dct_project: S = G @ Q fused with column norms
# ---------------------------------------------------------------------------
DCT_SHAPES = [(32, 64), (128, 128), (100, 96), (257, 130), (64, 512)]


@pytest.mark.parametrize("shape", DCT_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_dct_project_matches_ref(shape, dtype):
    m, n = shape
    g = _rand((m, n), dtype, seed=m + n)
    q = dct2_matrix(n, dtype)
    s, norms = dct_project(g, q, block=(32, 64, 32), interpret=True)
    s_ref, norms_ref = ref.dct_project_ref(g, q)
    tol = 1e-5 if dtype == jnp.float32 else 0.05
    np.testing.assert_allclose(np.asarray(s, np.float32),
                               np.asarray(s_ref, np.float32),
                               atol=tol * np.sqrt(n), rtol=tol)
    np.testing.assert_allclose(np.asarray(norms), np.asarray(norms_ref),
                               rtol=2e-5 if dtype == jnp.float32 else 0.1,
                               atol=1e-4)


def test_dct_project_padded_columns_rank_last():
    """Zero-padded columns must produce zero norms (never selected)."""
    g = _rand((40, 48), jnp.float32, seed=7)
    q = dct2_matrix(48)
    _, norms = dct_project(g, q, block=(32, 64, 32), interpret=True)
    assert norms.shape == (48,)
    assert float(norms.min()) > 0  # all real columns have positive energy


# ---------------------------------------------------------------------------
# colgather_matmul: O = b @ Q^T[idx, :]
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("m,n,r", [(64, 64, 8), (128, 96, 16), (50, 130, 10)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_colgather_matmul_matches_ref(m, n, r, dtype):
    b = _rand((m, r), dtype, seed=m)
    qt = jnp.asarray(np.asarray(dct2_matrix(n)).T).astype(dtype)
    idx = jnp.asarray(np.sort(np.random.default_rng(r).choice(n, r, replace=False))
                      ).astype(jnp.int32)
    out = colgather_matmul(b, qt, idx, block=(32, 64), interpret=True)
    out_ref = ref.colgather_matmul_ref(b, qt, idx)
    tol = 1e-5 if dtype == jnp.float32 else 0.05
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(out_ref, np.float32),
                               atol=tol * r, rtol=tol)


# ---------------------------------------------------------------------------
# batched (stacked-layer) kernel paths + fused dual back-projection
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("batch", [(3,), (2, 2)])
def test_dct_project_batched_matches_per_layer(batch):
    g = _rand((*batch, 40, 48), jnp.float32, seed=11)
    q = dct2_matrix(48)
    s, norms = dct_project(g, q, block=(32, 32, 32), interpret=True)
    assert s.shape == g.shape and norms.shape == (*batch, 48)
    gs = g.reshape((-1, 40, 48))
    for li in range(gs.shape[0]):
        s_l, n_l = dct_project(gs[li], q, block=(32, 32, 32), interpret=True)
        np.testing.assert_allclose(np.asarray(s.reshape((-1, 40, 48))[li]),
                                   np.asarray(s_l), atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(norms.reshape((-1, 48))[li]),
                                   np.asarray(n_l), rtol=2e-5, atol=1e-4)


def test_colgather_matmul_batched_per_layer_indices():
    L, m, n, r = 3, 50, 64, 8
    b = _rand((L, m, r), jnp.float32, seed=5)
    qt = jnp.asarray(np.asarray(dct2_matrix(n)).T)
    rng = np.random.default_rng(0)
    idx = jnp.asarray(np.stack([np.sort(rng.choice(n, r, replace=False))
                                for _ in range(L)])).astype(jnp.int32)
    out = colgather_matmul(b, qt, idx, block=(32, 32), interpret=True)
    out_ref = ref.colgather_matmul_ref(b, qt, idx)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_ref),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("shape", [(64, 96), (3, 50, 96)])
def test_colgather_matmul_dual_matches_two_singles(shape):
    from repro.kernels.colgather_matmul import colgather_matmul_dual

    *batch, m, n = shape
    r = 8
    b1 = _rand((*batch, m, r), jnp.float32, seed=1)
    b2 = _rand((*batch, m, r), jnp.float32, seed=2)
    qt = jnp.asarray(np.asarray(dct2_matrix(n)).T)
    rng = np.random.default_rng(3)
    idx = jnp.asarray(np.sort(rng.choice(n, (*batch, r), replace=True),
                              axis=-1)).astype(jnp.int32)
    o1, o2 = colgather_matmul_dual(b1, b2, qt, idx, block=(32, 32),
                                   interpret=True)
    s1 = colgather_matmul(b1, qt, idx, block=(32, 32), interpret=True)
    s2 = colgather_matmul(b2, qt, idx, block=(32, 32), interpret=True)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(s1), atol=1e-6)
    np.testing.assert_allclose(np.asarray(o2), np.asarray(s2), atol=1e-6)


def test_quant_ef_batched_roundtrip():
    x = _rand((3, 40, 32), jnp.float32, seed=13, scale=4.0)
    q, scale = quantize_ef(x, bm=16, interpret=True)
    assert q.shape == x.shape and scale.shape == (3, 40, 1)
    q_ref, scale_ref = ref.quantize_ef_ref(x)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(q_ref))
    np.testing.assert_allclose(np.asarray(scale), np.asarray(scale_ref),
                               rtol=1e-6)
    g = _rand((3, 40, 32), jnp.float32, seed=14)
    out = dequant_add_ef(g, q, scale, bm=16, interpret=True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref.dequant_add_ef_ref(g, q, scale)),
                               atol=1e-6)


# ---------------------------------------------------------------------------
# newton_schulz: fused iteration + full orthogonalization
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("r,m", [(8, 64), (16, 128), (16, 100)])
def test_ns_iteration_matches_ref(r, m):
    x = _rand((r, m), jnp.float32, seed=r * m, scale=0.1)
    y = ns_iteration(x, bm=32, interpret=True)
    y_ref = ref.ns_iteration_ref(x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("shape", [(64, 8), (8, 64), (100, 12)])
def test_newton_schulz_pallas_matches_ref(shape):
    x = _rand(shape, jnp.float32, seed=sum(shape))
    y = newton_schulz_pallas(x, steps=5, bm=32, interpret=True)
    y_ref = ref.newton_schulz_ref(x, steps=5)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               atol=1e-3, rtol=1e-3)


def test_newton_schulz_pallas_orthogonalizes():
    x = _rand((128, 16), jnp.float32, seed=3)
    y = np.asarray(newton_schulz_pallas(x, steps=10, bm=64, interpret=True),
                   dtype=np.float64)
    sv = np.linalg.svd(y, compute_uv=False)
    assert sv.max() < 1.35 and sv.min() > 0.3


# ---------------------------------------------------------------------------
# quant_ef
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("shape", [(32, 64), (100, 48), (257, 16)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_quantize_ef_matches_ref(shape, dtype):
    x = _rand(shape, dtype, seed=shape[0], scale=3.0)
    q, scale = quantize_ef(x, bm=32, interpret=True)
    q_ref, scale_ref = ref.quantize_ef_ref(x)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(q_ref))
    np.testing.assert_allclose(np.asarray(scale), np.asarray(scale_ref),
                               rtol=1e-6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_dequant_add_matches_ref(dtype):
    g = _rand((64, 32), dtype, seed=1)
    resid = _rand((64, 32), jnp.float32, seed=2, scale=0.5)
    q, scale = ref.quantize_ef_ref(resid)
    out = dequant_add_ef(g, q, scale, bm=32, interpret=True)
    out_ref = ref.dequant_add_ef_ref(g, q, scale)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(out_ref, np.float32),
                               atol=1e-2 if dtype == jnp.bfloat16 else 1e-6)


def test_quant_roundtrip_bound():
    x = _rand((48, 96), jnp.float32, seed=9, scale=10.0)
    q, scale = quantize_ef(x, bm=16, interpret=True)
    y = np.asarray(q, np.float32) * np.asarray(scale)
    bound = np.abs(np.asarray(x)).max(axis=1, keepdims=True) / 254.0
    assert (np.abs(np.asarray(x) - y) <= bound * 1.01).all()


# ---------------------------------------------------------------------------
# integration: pallas pipeline == optimizer-core pipeline
# ---------------------------------------------------------------------------
def test_kernel_pipeline_matches_core_trion_math():
    """dct_project + top-r + colgather == core dct2/selection/back_project."""
    from repro.core.selection import back_project, dynamic_column_selection

    m, n, r = 96, 64, 8
    g = _rand((m, n), jnp.float32, seed=42)
    q = dct2_matrix(n)

    s_k, norms_k = dct_project(g, q, block=(32, 32, 32), interpret=True)
    idx_k = jnp.sort(jax.lax.top_k(norms_k, r)[1]).astype(jnp.int32)
    b_k = jnp.take(s_k, idx_k, axis=1)
    out_k = colgather_matmul(b_k, q.T, idx_k, block=(32, 32), interpret=True)

    s = g @ q
    idx, b = dynamic_column_selection(s, r)
    out = back_project(b, q, idx)

    np.testing.assert_array_equal(np.asarray(idx_k), np.asarray(idx))
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out),
                               atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# flash attention kernel (GQA / causal / sliding-window)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("b,s,hq,hkv,hd,causal,window,dtype", [
    (2, 256, 4, 2, 64, True, None, jnp.float32),
    (1, 512, 8, 8, 128, True, None, jnp.bfloat16),
    (2, 256, 4, 1, 64, False, None, jnp.float32),
    (1, 512, 4, 2, 64, True, 128, jnp.float32),
    (1, 256, 2, 2, 32, True, 64, jnp.bfloat16),
    (3, 128, 6, 3, 64, True, None, jnp.float32),
])
def test_flash_attention_matches_ref(b, s, hq, hkv, hd, causal, window,
                                     dtype):
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.ref import flash_attention_ref

    rng = np.random.default_rng(hash((b, s, hq)) % 2**31)
    q = jnp.asarray(rng.standard_normal((b, s, hq, hd)), dtype)
    k = jnp.asarray(rng.standard_normal((b, s, hkv, hd)), dtype)
    v = jnp.asarray(rng.standard_normal((b, s, hkv, hd)), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=128, block_k=128, interpret=True)
    ref = flash_attention_ref(q, k, v, causal=causal, window=window)
    tol = 2e-2 if dtype == jnp.bfloat16 else 3e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol)


def test_flash_attention_matches_blockwise_model_path():
    """The kernel agrees with the pure-JAX model attention (same oracle)."""
    from repro.kernels.flash_attention import flash_attention
    from repro.models.layers import blockwise_attention

    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.standard_normal((2, 256, 4, 64)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, 256, 2, 64)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, 256, 2, 64)), jnp.float32)
    a = flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                        interpret=True)
    b = blockwise_attention(q, k, v, causal=True, q_chunk=64, kv_chunk=64)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-5)


@pytest.mark.parametrize("b,s,hq,hkv,hd,causal,window,dtype,bq,bk", [
    (1, 256, 5, 1, 128, True, None, jnp.float32, 128, 128),    # GQA 5/1
    (1, 256, 4, 2, 96, True, None, jnp.bfloat16, 128, 128),    # GQA 4/2
    (2, 256, 2, 2, 128, False, None, jnp.float32, 128, 128),   # MHA
    (1, 256, 4, 4, 96, True, None, jnp.bfloat16, 128, 128),    # MHA
    (1, 512, 4, 2, 128, True, 200, jnp.float32, 128, 128),     # window
    (1, 512, 2, 1, 96, False, 150, jnp.float32, 128, 128),
    (1, 512, 4, 4, 96, True, None, jnp.bfloat16, 256, 128),    # q block
    (1, 512, 4, 2, 128, True, 192, jnp.float32, 128, 256),     # straddles
])
def test_flash_attention_grads_match_ref(b, s, hq, hkv, hd, causal, window,
                                         dtype, bq, bk):
    """dq, dk, dv through the kernel's custom VJP against autodiff of the
    dense oracle and of the model's blockwise path; the forward's
    logsumexp against the oracle's scores."""
    from repro.kernels.flash_attention import (_Cfg, flash_attention,
                                               flash_attention_fwd)
    from repro.kernels.ref import flash_attention_ref
    from repro.models.layers import blockwise_attention

    rng = np.random.default_rng(s + 10 * hq + hkv + hd)
    q = jnp.asarray(rng.standard_normal((b, s, hq, hd)), dtype)
    k = jnp.asarray(rng.standard_normal((b, s, hkv, hd)), dtype)
    v = jnp.asarray(rng.standard_normal((b, s, hkv, hd)), dtype)
    w = jnp.asarray(rng.standard_normal((b, s, hq, hd)), jnp.float32)

    def grads(attn):
        loss = lambda q, k, v: jnp.sum(attn(q, k, v).astype(jnp.float32) * w)
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    got = grads(lambda q, k, v: flash_attention(
        q, k, v, causal=causal, window=window, block_q=bq, block_k=bk,
        interpret=True))
    want = grads(lambda q, k, v: flash_attention_ref(
        q, k, v, causal=causal, window=window))
    model = grads(lambda q, k, v: blockwise_attention(
        q, k, v, causal=causal, window=window, q_chunk=bq, kv_chunk=bk))
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    for name, g, r, m in zip("qkv", got, want, model):
        assert g.dtype == r.dtype == dtype, name
        g, r, m = (np.asarray(x, np.float32) for x in (g, r, m))
        scale = np.abs(r).max()
        np.testing.assert_allclose(g, r, atol=tol * scale, err_msg=name)
        np.testing.assert_allclose(g, m, atol=tol * scale, err_msg=name)

    heads = lambda x: x.transpose(0, 2, 1, 3).reshape(-1, s, hd)
    c = _Cfg(causal, window, bq, bk, True)
    _, lse = flash_attention_fwd(heads(q), heads(k), heads(v),
                                 group=hq // hkv, c=c)
    kx = jnp.repeat(k, hq // hkv, axis=2).astype(jnp.float32)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), kx,
                        precision="highest") / np.sqrt(hd)
    pos = np.arange(s)
    keep = np.ones((s, s), bool)
    if causal:
        keep &= pos[:, None] >= pos[None, :]
    if window is not None:
        keep &= pos[:, None] - pos[None, :] < window
    lse_ref = jax.nn.logsumexp(jnp.where(keep, scores, -jnp.inf), axis=-1)
    assert lse.shape == (b * hq, 1, s) and lse.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(lse[:, 0]),
                               np.asarray(lse_ref.reshape(b * hq, s)),
                               atol=1e-4, rtol=1e-5)
