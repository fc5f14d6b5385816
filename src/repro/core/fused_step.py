"""Fused execution layer for the projected-Adam hot path (DESIGN.md §3).

The reference ``ProjectedAdamRule`` path performs, per predefined-basis
(DCT/DST/Hadamard/random-orthogonal) leaf and step:

    S = G @ Q          (refresh: ranking statistic, O(m n^2))
    g_low = G @ Q_r    (projection, O(m n r))       <- duplicated pass over G
    d     = u @ Q_r^T  (back-projection)            <- gathers Q_r^T
    recon = g_low @ Q_r^T                           <- gathers Q_r^T AGAIN
    EF    = dequant(q8) -> full fp32 (m, n) temp    <- materialized in HBM

This module is the fused dispatch that removes every redundancy: the
low-rank factor is extracted from ``S`` directly (paper Alg. 1 line 8 — no
second projection matmul), both back-projections share one ``Q_r^T`` gather,
and the int8 error-feedback buffer is consumed/produced by fused quantize
kernels so the fp32 EF temporary never exists.

Three concrete modes (``resolve`` maps a rule's ``fused`` field to one):

  ``"on"``   — Pallas kernel path (``kernels.ops``): TPU production;
               interpret mode off-TPU, which is how the parity tests run it.
  ``"fft"``  — pure-jnp fused dataflow with the forward transform computed by
               the basis backend's fast path (``BasisBackend.apply_fast``:
               Makhoul's N-point FFT for DCT, the FHT butterfly for
               Hadamard, a matmul for backends without one): the host/GPU
               fast path. ``S`` costs O(m n log n) instead of the
               O(m n^2) matmul; back-projection stays a (shared-gather)
               matmul, which at r << n is cheaper than an inverse
               transform.
  ``"off"``  — the seed jnp reference path, bit-identical to the seed repo.

``"auto"`` resolves to the kernel path on TPU and degrades to the reference
path elsewhere; benchmarks/tests opt into "on"/"fft" explicitly.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.dct import makhoul_dct2
from repro.core.error_feedback import QuantizedBuffer, dequantize_q8, quantize_q8
from repro.core.newton_schulz import newton_schulz
from repro.core.selection import (
    allgather_rows,
    allsum,
    back_project,
    column_norms,
    dual_back_project,
    dynamic_column_selection,
    gather_columns,
    local_row_block,
    select_top_r,
)
from repro.kernels import lowp, ops
from repro.kernels.lowp import COMPUTE_DTYPES, LOWP_ERROR_BOUNDS  # noqa: F401

FUSED_MODES = ("auto", "off", "on", "fft")

# process-wide default consulted by rules whose ``fused`` field is "auto";
# itself "auto" = kernels on TPU, reference elsewhere.
_DEFAULT_MODE = "auto"


def set_default_fused_mode(mode: str) -> None:
    """Override the process-wide dispatch default (benchmarks/experiments)."""
    global _DEFAULT_MODE
    assert mode in FUSED_MODES, mode
    _DEFAULT_MODE = mode


def default_fused_mode() -> str:
    return _DEFAULT_MODE


def resolve(mode: str) -> str:
    """Rule-level mode -> concrete mode in {"off", "on", "fft"}."""
    if mode not in FUSED_MODES:
        raise ValueError(f"unknown fused mode {mode!r}; expected one of "
                         f"{FUSED_MODES}")
    if mode == "auto":
        mode = _DEFAULT_MODE
    if mode == "auto":
        return "on" if ops.on_tpu() else "off"
    return mode


# ---------------------------------------------------------------------------
# select + project: ONE pass over G
# ---------------------------------------------------------------------------
def select_and_project(gf: jax.Array, q: jax.Array, r: int, *,
                       norm: str = "l2", mode: str,
                       return_norms: bool = False, psum_axes=None,
                       backend=None, compute_dtype: str = "fp32"):
    """Dynamic column selection + low-rank extraction in one ``G``-sized pass.

    Returns ``(idx (..., r), g_low (..., m, r))``. The kernel path fuses the
    column-norm accumulation into the ``S = G @ Q`` matmul — the kernel is
    parameterized by the basis matrix ``q``, so every predefined-basis
    backend reaches it; the fft path computes ``S`` row-wise by the
    backend's fast transform (``backend.apply_fast``; default: Makhoul
    FFT, the DCT backend's). Either way ``g_low`` is sliced out of ``S``
    (``S[:, idx] == G @ Q[:, idx]`` exactly), so the reference path's
    second projection matmul never runs.

    ``return_norms=True`` appends the *squared-l2* column norms of ``S``
    (..., n) — the §4.1 energy statistic the telemetry layer feeds on. The
    kernel already accumulates them for ranking, so this is free on the
    "on" path and one reduction over the resident ``S`` on the fft path.

    ``psum_axes``: mesh axes the rows of ``gf`` are sharded over (inside a
    ZeRO-1 shard_map). The kernels see only the local row block; the
    column statistic is completed by one ``(n,)``-sized psum, so every
    shard selects the same indices.

    ``compute_dtype`` in {"fp32", "bf16", "int8"} selects the matmul
    precision (DESIGN.md §15): the kernel path passes it to dct_project;
    the off/fft paths run the jnp mirror (``lowp.lowp_matmul``) instead of
    the fast transform — there is no int8 FFT, and the mirror's exact
    int32 accumulation keeps the two dispatch modes in lockstep. The
    documented error bounds vs fp32 are ``LOWP_ERROR_BOUNDS``, gated on a
    real gradient stream in benchmarks/projection_errors.py.
    """
    lowp.check_compute_dtype(compute_dtype)
    if mode == "on":
        s, norms_sq = ops.dct_project_op(gf, q, compute_dtype=compute_dtype)
        norms_sq = allsum(norms_sq, psum_axes)
        rank_norms = (norms_sq if norm == "l2"
                      else allsum(column_norms(s, norm), psum_axes))
        idx = select_top_r(rank_norms, r)
        g_low = jnp.take_along_axis(s, idx[..., None, :], axis=-1)
        return (idx, g_low, norms_sq) if return_norms else (idx, g_low)
    if compute_dtype != "fp32":
        s = lowp.lowp_matmul(gf, q, compute_dtype)
    else:
        s = backend.apply_fast(gf, q) if backend is not None \
            else makhoul_dct2(gf)
    if not return_norms and psum_axes is None:
        return dynamic_column_selection(s, r, ord=norm)
    norms_sq = allsum(column_norms(s, "l2"), psum_axes)
    rank_norms = (norms_sq if norm == "l2"
                  else allsum(column_norms(s, norm), psum_axes))
    idx = select_top_r(rank_norms, r)
    g_low = jnp.take_along_axis(s, idx[..., None, :], axis=-1)
    return (idx, g_low, norms_sq) if return_norms else (idx, g_low)


def project_with_indices(gf: jax.Array, q: jax.Array, idx: jax.Array, *,
                         compute_dtype: str = "fp32") -> jax.Array:
    """Keep-branch projection ``G @ Q[:, idx]`` for non-refresh steps
    (T_u > 1). A gather + skinny matmul — no full-width ``S`` pass."""
    qr = gather_columns(q, idx)
    if compute_dtype != "fp32":
        return lowp.lowp_matmul(gf, qr.astype(jnp.float32), compute_dtype)
    return jnp.einsum("...mn,...nr->...mr", gf, qr.astype(gf.dtype))


# ---------------------------------------------------------------------------
# back-projection: both outputs from ONE Q_r^T gather
# ---------------------------------------------------------------------------
def fused_dual_backproject(u_low: jax.Array, g_low: jax.Array, q: jax.Array,
                           idx: jax.Array, *, mode: str,
                           compute_dtype: str = "fp32"
                           ) -> tuple[jax.Array, jax.Array]:
    """``(u_low @ Q_r^T, g_low @ Q_r^T)`` sharing one ``Q_r^T`` gather."""
    if mode == "on":
        qt = jnp.swapaxes(q, -1, -2)
        return ops.colgather_matmul_dual_op(u_low, g_low, qt, idx,
                                            compute_dtype=compute_dtype)
    if compute_dtype != "fp32":
        d, recon = lowp.lowp_gather_matmul(
            (u_low, g_low), jnp.swapaxes(q, -1, -2), idx, compute_dtype)
        return d.astype(u_low.dtype), recon.astype(g_low.dtype)
    return dual_back_project(u_low, g_low, q, idx)


def fused_backproject(u_low: jax.Array, q: jax.Array, idx: jax.Array, *,
                      mode: str, compute_dtype: str = "fp32") -> jax.Array:
    if mode == "on":
        return ops.colgather_matmul_op(u_low, jnp.swapaxes(q, -1, -2), idx,
                                       compute_dtype=compute_dtype)
    if compute_dtype != "fp32":
        (d,) = lowp.lowp_gather_matmul(
            (u_low,), jnp.swapaxes(q, -1, -2), idx, compute_dtype)
        return d.astype(u_low.dtype)
    return back_project(u_low, q, idx)


# ---------------------------------------------------------------------------
# Newton-Schulz on the low-rank factor (muon/trion subspace orthogonalization)
# ---------------------------------------------------------------------------

# The Pallas NS kernel keeps an (r, r) Gram scratch and the (r, r)
# polynomial block resident in VMEM, with r = min of the factor's trailing
# dims — its documented envelope is r <= 512 (1 MB fp32 each). Rank-sized
# factors always fit; full-space moments at production shapes (e.g.
# 4096x4096 -> 64 MB) do not and would fail to compile on TPU, so past
# this threshold dispatch degrades to the jnp iteration, whose full-size
# matmuls XLA tiles fine.
NS_PALLAS_MAX_RANK = 512


def fused_newton_schulz(b: jax.Array, *, steps: int, mode: str,
                        gather_axes=None) -> jax.Array:
    """Orthogonalize ``b`` via Newton-Schulz — Pallas kernel on the "on"
    path, the seed jnp iteration otherwise (DESIGN.md §14).

    ``b`` is the wide-or-tall factor the caller wants orthogonalized: the
    (..., m, r) low-rank momentum factor on the subspace path (the kernel
    runs r-sized Gram matrices — the paper's rank-sized NS claim), or the
    full (..., m, n) moment for full-space muon. The kernel handles
    factors whose short side fits its VMEM envelope
    (``NS_PALLAS_MAX_RANK``); larger full-space moments fall back to the
    jnp iteration even when ``mode == "on"``.

    ``gather_axes``: mesh axes the rows (dim -2) are sharded over inside a
    ZeRO-1 shard_map. NS mixes *rows* through the Gram matrix, so unlike
    the column statistic it cannot be completed by a psum — a psum of
    per-shard partial Grams would round differently than the replicated
    single-pass matmul and break the bit-exact sharded/replicated
    contract. Instead the factor is all-gathered, every shard runs the
    identical whole-matrix iteration, and each keeps only its own rows
    (row-blocked consumers make the slice exact). The gathered factor is
    (m, r) — r-sized, so the ZeRO communication term stays rank-sized
    too.
    """
    block = b.shape[-2]
    bf = allgather_rows(b, gather_axes)
    if mode == "on" and min(bf.shape[-2:]) <= NS_PALLAS_MAX_RANK:
        o = ops.newton_schulz_op(bf, steps=steps)
    else:
        o = newton_schulz(bf, steps=steps)
    return local_row_block(o, gather_axes, block)


# ---------------------------------------------------------------------------
# int8 error feedback: no fp32 (m, n) temporary
# ---------------------------------------------------------------------------
def ef_add(gf: jax.Array, ef, *, mode: str) -> jax.Array:
    """``G + EF`` — fused dequant-add on the kernel path, so the dequantized
    fp32 buffer never hits HBM."""
    if isinstance(ef, QuantizedBuffer):
        if mode == "on":
            return ops.dequant_add_ef_op(gf, ef.q, ef.scale)
        return gf + dequantize_q8(ef)
    return gf + ef


def ef_store(resid: jax.Array, ef_dtype: str, *, mode: str):
    """Residual -> EF buffer (int8 payload written in one pass)."""
    if ef_dtype == "q8":
        if mode == "on":
            qv, scale = ops.quantize_ef_op(resid)
            return QuantizedBuffer(q=qv, scale=scale)
        return quantize_q8(resid)
    return resid
