"""Bandwidth-bound int8 error-feedback kernels.

Two fused passes used by DCT-AdamW's quantized EF (paper §2.4):
  * ``quantize_ef``     — residual (..., m, n) fp -> (int8 payload, per-row
    fp32 scale) in a single HBM read + int8 write (4x HBM write reduction vs
    fp32).
  * ``dequant_add_ef``  — ``G + q * scale`` fused so the dequantized fp32 EF
    buffer never exists in HBM (the projected-Adam step reads the EF payload
    straight into the gradient accumulation, DESIGN.md §3).

Leading stacked-layer axes are collapsed into a leading batch grid dimension
(scan-stacked ``(layers, m, n)`` leaves run in one launch). Rows are
processed in full width per grid step so the per-row amax reduction and the
scaling stay in registers/VMEM. Full-width rows make a block's VMEM grow with
``n``, so the row count per step is capped by ``_VMEM_BUDGET`` (a wide leaf
such as 27648 x 5120 takes fewer rows per step, never partial rows).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.tune.cache import resolve_block

from .lowp import q8_scale

DEFAULT_BM = 256  # rows per grid step

# Scoped VMEM (16 MiB by default on TPU v5e) holds every block twice
# (double-buffering): dequant_add_ef moves an fp32 G block, an int8 payload
# block and an fp32 output block, 9 bytes per element, 18 double-buffered.
# 12 MiB leaves room for the kernels' temporaries.
_VMEM_BUDGET = 12 * 2**20
_BYTES_PER_ELEM = 18
_ROW_ALIGN = 32       # int8 sublane tile: the payload block's row multiple


def _quant_kernel(x_ref, q_ref, scale_ref):
    x = x_ref[...].astype(jnp.float32)
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    # max(amax/127, tiny): an all-zero row quantizes to zeros under any
    # positive scale, but a *subnormal* row underflows amax/127 to 0.0 and
    # x / 0 would poison the int8 payload with NaNs (kernels/lowp.py; the
    # jnp quantizers in kernels/ref.py + core/error_feedback.py match)
    scale = q8_scale(amax)
    q_ref[...] = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    scale_ref[...] = scale


def _dequant_add_kernel(g_ref, q_ref, scale_ref, out_ref):
    out_ref[...] = (
        g_ref[...].astype(jnp.float32)
        + q_ref[...].astype(jnp.float32) * scale_ref[...]
    ).astype(out_ref.dtype)


def _batch_rows(x, bm):
    """(..., m, n) -> row-padded (nb, mm, n) + original dims."""
    *batch, m, n = x.shape
    xb = x.reshape((-1, m, n))
    pad = -m % bm
    if pad:
        xb = jnp.pad(xb, ((0, 0), (0, pad), (0, 0)))
    return xb, tuple(batch), m, m + pad, n


def _resolve_bm(x: jax.Array, bm):
    """``bm=None`` -> TuningCache -> ``DEFAULT_BM`` (both EF kernels share
    the one "quant_ef" cache family), capped so a block fits the VMEM
    budget at this row width."""
    if bm is not None:
        return int(bm)
    *batch, m, n = x.shape
    bm = int(resolve_block("quant_ef", (math.prod(batch), m, n), 0,
                           x.dtype, DEFAULT_BM))
    cap = _VMEM_BUDGET // (_BYTES_PER_ELEM * n) // _ROW_ALIGN * _ROW_ALIGN
    return min(bm, max(cap, _ROW_ALIGN))


@functools.partial(jax.jit, static_argnames=("bm", "interpret"))
def _quantize_ef(x: jax.Array, *, bm: int,
                 interpret: bool) -> tuple[jax.Array, jax.Array]:
    xp, batch, m, mm, n = _batch_rows(x, bm)
    nb = xp.shape[0]
    q, scale = pl.pallas_call(
        _quant_kernel,
        grid=(nb, mm // bm),
        in_specs=[pl.BlockSpec((1, bm, n), lambda b, i: (b, i, 0))],
        out_specs=[
            pl.BlockSpec((1, bm, n), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, bm, 1), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nb, mm, n), jnp.int8),
            jax.ShapeDtypeStruct((nb, mm, 1), jnp.float32),
        ],
        interpret=interpret,
    )(xp)
    return (q[:, :m].reshape((*batch, m, n)),
            scale[:, :m].reshape((*batch, m, 1)))


def quantize_ef(x: jax.Array, *, bm: int | None = None,
                interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """(..., m, n) fp -> ((..., m, n) int8, (..., m, 1) fp32 row scales).
    ``bm=None`` resolves TuningCache -> ``DEFAULT_BM``."""
    return _quantize_ef(x, bm=_resolve_bm(x, bm), interpret=interpret)


@functools.partial(jax.jit, static_argnames=("bm", "interpret"))
def _dequant_add_ef(g: jax.Array, q: jax.Array, scale: jax.Array, *,
                    bm: int, interpret: bool) -> jax.Array:
    gp, batch, m, mm, n = _batch_rows(g, bm)
    qp, *_ = _batch_rows(q, bm)
    sp, *_ = _batch_rows(scale, bm)
    nb = gp.shape[0]
    out = pl.pallas_call(
        _dequant_add_kernel,
        grid=(nb, mm // bm),
        in_specs=[
            pl.BlockSpec((1, bm, n), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, bm, n), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, bm, 1), lambda b, i: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, bm, n), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, mm, n), g.dtype),
        interpret=interpret,
    )(gp, qp, sp)
    return out[:, :m].reshape((*batch, m, n))


def dequant_add_ef(g: jax.Array, q: jax.Array, scale: jax.Array, *,
                   bm: int | None = None, interpret: bool = False
                   ) -> jax.Array:
    """``G + dequant(q, scale)`` fused; output dtype follows ``G``.
    ``bm=None`` resolves TuningCache -> ``DEFAULT_BM``."""
    return _dequant_add_ef(g, q, scale, bm=_resolve_bm(g, bm),
                           interpret=interpret)
