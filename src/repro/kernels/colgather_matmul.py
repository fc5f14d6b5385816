"""Fused column-gather back-projection kernel: ``O = b @ Q[:, idx]^T``.

Trion / DCT-AdamW back-project the low-rank factor ``b (m, r)`` through the
selected DCT columns: ``O = b @ Q_r^T`` where ``Q_r^T = Q^T[idx, :] (r, n)``
is a *row* gather of the transposed shared basis. This kernel never
materializes the gathered matrix in HBM: the selected rows are gathered
VMEM->VMEM from a resident column stripe of ``Q^T``, driven by the
scalar-prefetched index vector.

Two entry points (DESIGN.md §3):

  * ``colgather_matmul(b, qt, idx)``            — one back-projection.
  * ``colgather_matmul_dual(b1, b2, qt, idx)``  — the projected-Adam step's
    descent direction ``u @ Q_r^T`` AND residual reconstruction
    ``g_low @ Q_r^T`` from ONE gather: the ``(r, bn)`` scratch is built once
    per column stripe and feeds both matmuls, so ``Q`` is read once instead
    of twice.

Both accept leading stacked-layer axes on ``b``/``idx`` — collapsed into a
leading batch grid dimension with per-layer index vectors (the shapes every
scan-stacked config produces).

Grid ``(nb, nj, ni)`` — ``j`` after batch so the ``(n, bn)`` stripe of
``Q^T`` and its gathered ``(r, bn)`` scratch are built once per ``(b, j)``
and reused across all row blocks ``i``.

``block=None`` (the default) resolves through the process-wide
:class:`~repro.tune.cache.TuningCache` — tuned block on a hit, the
hardcoded ``DEFAULT_BLOCK`` on a miss (the bit-identical untuned path).

``compute_dtype`` in {"fp32", "bf16", "int8"} selects the matmul precision
(DESIGN.md §15). Because the gather selects *rows* of ``Q^T``, a
per-column scale of the gathered matrix would depend on ``idx``; instead
``Q^T`` is int8-quantized per-row pre-gather and those row scales are
folded into ``b`` before ``b``'s own per-row quantization (kernels/lowp.py
derivation), leaving one per-row epilogue scale. The int8 ``Q^T`` stripe
travels packed four rows to an int32 word (``_pack_rows_i8``), so it keeps
the int8 footprint while the gather moves 32-bit rows, which Mosaic can
address at any row offset.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.tune.cache import resolve_block

from .lowp import check_compute_dtype, quant_rows

DEFAULT_BLOCK = (512, 256)  # (bm rows of b, bn output columns)


def _build_gather(idx_ref, bi, qt_ref, gather_ref, r: int):
    def body(k, _):
        row = idx_ref[bi, k]
        gather_ref[pl.ds(k, 1), :] = qt_ref[pl.ds(row, 1), :]
        return ()

    jax.lax.fori_loop(0, r, body, ())


def _pack_rows_i8(x: jax.Array) -> jax.Array:
    """(n, c) int8 -> (ceil(n/4), c) int32, byte ``s`` of word ``i`` holding
    row ``4i+s``. Mosaic cannot load one int8 row at a dynamic offset (the
    int8 tile is 32 rows), but it can load one 32-bit row; the packed
    stripe keeps the int8 footprint in HBM and VMEM."""
    pad = -x.shape[0] % 4
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    b = (x.astype(jnp.int32) & 0xFF).reshape(-1, 4, x.shape[1])
    return (b[:, 0] | (b[:, 1] << 8) | (b[:, 2] << 16) | (b[:, 3] << 24))


def _build_gather_packed(idx_ref, bi, qt_ref, gather_ref, r: int):
    """Row gather out of a ``_pack_rows_i8`` stripe into an int32 scratch:
    shift byte ``row % 4`` of word row ``row // 4`` to the top, then an
    arithmetic shift back sign-extends it."""
    def body(k, _):
        row = idx_ref[bi, k]
        word = qt_ref[pl.ds(row // 4, 1), :]
        gather_ref[pl.ds(k, 1), :] = (word << (24 - 8 * (row % 4))) >> 24
        return ()

    jax.lax.fori_loop(0, r, body, ())


def _kernel(idx_ref, b_ref, qt_ref, out_ref, gather_ref, *, r: int,
            cast=jnp.float32):
    bi = pl.program_id(0)
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _gather():
        _build_gather(idx_ref, bi, qt_ref, gather_ref, r)

    qr = gather_ref[...].astype(cast)
    out_ref[0] = jnp.dot(
        b_ref[0].astype(cast), qr, preferred_element_type=jnp.float32
    ).astype(out_ref.dtype)


def _kernel_dual(idx_ref, b1_ref, b2_ref, qt_ref, o1_ref, o2_ref, gather_ref,
                 *, r: int, cast=jnp.float32):
    bi = pl.program_id(0)
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _gather():
        _build_gather(idx_ref, bi, qt_ref, gather_ref, r)

    qr = gather_ref[...].astype(cast)
    o1_ref[0] = jnp.dot(
        b1_ref[0].astype(cast), qr, preferred_element_type=jnp.float32
    ).astype(o1_ref.dtype)
    o2_ref[0] = jnp.dot(
        b2_ref[0].astype(cast), qr, preferred_element_type=jnp.float32
    ).astype(o2_ref.dtype)


def _kernel_q8(idx_ref, b_ref, sb_ref, qt_ref, out_ref, gather_ref, *,
               r: int):
    """int8: rows gathered from the packed stripe, exact int32 dot, per-row
    epilogue."""
    bi = pl.program_id(0)
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _gather():
        _build_gather_packed(idx_ref, bi, qt_ref, gather_ref, r)

    acc = jnp.dot(b_ref[0], gather_ref[...].astype(jnp.int8),
                  preferred_element_type=jnp.int32)
    out_ref[0] = (acc.astype(jnp.float32) * sb_ref[0]).astype(out_ref.dtype)


def _kernel_dual_q8(idx_ref, b1_ref, s1_ref, b2_ref, s2_ref, qt_ref,
                    o1_ref, o2_ref, gather_ref, *, r: int):
    bi = pl.program_id(0)
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _gather():
        _build_gather_packed(idx_ref, bi, qt_ref, gather_ref, r)

    qr = gather_ref[...].astype(jnp.int8)
    a1 = jnp.dot(b1_ref[0], qr, preferred_element_type=jnp.int32)
    o1_ref[0] = (a1.astype(jnp.float32) * s1_ref[0]).astype(o1_ref.dtype)
    a2 = jnp.dot(b2_ref[0], qr, preferred_element_type=jnp.int32)
    o2_ref[0] = (a2.astype(jnp.float32) * s2_ref[0]).astype(o2_ref.dtype)


def _norm_operands(bs: tuple[jax.Array, ...], qt: jax.Array, idx: jax.Array):
    """Collapse leading axes; validate shapes. Returns (batched bs, idx2d,
    batch_shape, m, r, n)."""
    *batch, m, r = bs[0].shape
    n = qt.shape[1]
    assert qt.shape[0] == n, (qt.shape,)
    for b in bs[1:]:
        assert b.shape == bs[0].shape, (b.shape, bs[0].shape)
    assert idx.shape == (*batch, r), (idx.shape, bs[0].shape)
    bb = tuple(b.reshape((-1, m, r)) for b in bs)
    idx2 = idx.reshape((-1, r)).astype(jnp.int32)
    return bb, idx2, tuple(batch), m, r, n


def _call(bs, qt, idx, *, block, interpret, out_dtype, compute_dtype):
    bb, idx2, batch, m, r, n = _norm_operands(bs, qt, idx)
    nb = bb[0].shape[0]
    out_dtype = out_dtype or bs[0].dtype
    bm, bn = block
    mp, np_ = (-m % bm), (-n % bn)
    mm, nn = m + mp, n + np_
    ni, nj = mm // bm, nn // bn
    nops = len(bs)
    out_shape = [jax.ShapeDtypeStruct((nb, mm, nn), out_dtype)] * nops
    out_specs = [
        pl.BlockSpec((1, bm, bn), lambda b, j, i, idx_ref: (b, i, j))
    ] * nops

    if compute_dtype == "int8":
        qt_q, s_qt = quant_rows(qt)                   # (n, n) i8, (n, 1)
        s_sel = jnp.take(s_qt[:, 0], idx2, axis=0)    # (nb, r)
        ops_in, in_specs = [], []
        for b in bb:
            bq, sb = quant_rows(b.astype(jnp.float32) * s_sel[:, None, :])
            if mp:
                bq = jnp.pad(bq, ((0, 0), (0, mp), (0, 0)))
                sb = jnp.pad(sb, ((0, 0), (0, mp), (0, 0)),
                             constant_values=1.0)
            ops_in += [bq, sb]
            in_specs += [
                pl.BlockSpec((1, bm, r), lambda b, j, i, idx_ref: (b, i, 0)),
                pl.BlockSpec((1, bm, 1), lambda b, j, i, idx_ref: (b, i, 0)),
            ]
        qtp = _pack_rows_i8(
            jnp.pad(qt_q, ((0, 0), (0, np_))) if np_ else qt_q)
        in_specs.append(
            pl.BlockSpec((qtp.shape[0], bn), lambda b, j, i, idx_ref: (0, j)))
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nb, nj, ni),
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=[pltpu.VMEM((r, bn), jnp.int32)],
        )
        kernel = _kernel_q8 if nops == 1 else _kernel_dual_q8
        outs = pl.pallas_call(
            functools.partial(kernel, r=r),
            grid_spec=grid_spec,
            out_shape=out_shape,
            interpret=interpret,
        )(idx2, *ops_in, qtp)
    else:
        cast = jnp.float32 if compute_dtype == "fp32" else jnp.bfloat16
        bp = tuple(jnp.pad(b, ((0, 0), (0, mp), (0, 0))) if mp else b
                   for b in bb)
        qtp = jnp.pad(qt, ((0, 0), (0, np_))) if np_ else qt
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nb, nj, ni),
            in_specs=[
                *([pl.BlockSpec((1, bm, r),
                                lambda b, j, i, idx_ref: (b, i, 0))] * nops),
                pl.BlockSpec((qt.shape[0], bn),
                             lambda b, j, i, idx_ref: (0, j)),
            ],
            out_specs=out_specs,
            scratch_shapes=[pltpu.VMEM((r, bn), qt.dtype)],
        )
        kernel = _kernel if nops == 1 else _kernel_dual
        outs = pl.pallas_call(
            functools.partial(kernel, r=r, cast=cast),
            grid_spec=grid_spec,
            out_shape=out_shape,
            interpret=interpret,
        )(idx2, *bp, qtp)
    return tuple(o[:, :m, :n].reshape((*batch, m, n)) for o in outs)


def _resolve(kernel: str, b: jax.Array, n: int, block):
    if block is not None:
        return tuple(block)
    *batch, m, r = b.shape
    return tuple(resolve_block(kernel, (math.prod(batch), m, n), r,
                               b.dtype, DEFAULT_BLOCK))


@functools.partial(jax.jit, static_argnames=("block", "interpret", "out_dtype",
                                             "compute_dtype"))
def _colgather_matmul(b, qt, idx, *, block, interpret, out_dtype,
                      compute_dtype):
    (out,) = _call((b,), qt, idx, block=block, interpret=interpret,
                   out_dtype=out_dtype, compute_dtype=compute_dtype)
    return out


@functools.partial(jax.jit, static_argnames=("block", "interpret", "out_dtype",
                                             "compute_dtype"))
def _colgather_matmul_dual(b1, b2, qt, idx, *, block, interpret, out_dtype,
                           compute_dtype):
    return _call((b1, b2), qt, idx, block=block, interpret=interpret,
                 out_dtype=out_dtype, compute_dtype=compute_dtype)


def colgather_matmul(
    b: jax.Array,
    qt: jax.Array,
    idx: jax.Array,
    *,
    block: tuple[int, int] | None = None,
    interpret: bool = False,
    out_dtype=None,
    compute_dtype: str = "fp32",
) -> jax.Array:
    """``O[..., m, n] = b[..., m, r] @ qt[idx, :]``; ``qt`` is ``Q^T`` (n, n),
    ``idx`` (..., r) int32 per-layer. Output dtype defaults to ``b.dtype``.
    ``block=None`` resolves TuningCache -> ``DEFAULT_BLOCK``;
    ``compute_dtype`` in {"fp32", "bf16", "int8"}."""
    check_compute_dtype(compute_dtype)
    block = _resolve("colgather_matmul", b, qt.shape[1], block)
    return _colgather_matmul(b, qt, idx, block=block, interpret=interpret,
                             out_dtype=out_dtype, compute_dtype=compute_dtype)


def colgather_matmul_dual(
    b1: jax.Array,
    b2: jax.Array,
    qt: jax.Array,
    idx: jax.Array,
    *,
    block: tuple[int, int] | None = None,
    interpret: bool = False,
    out_dtype=None,
    compute_dtype: str = "fp32",
) -> tuple[jax.Array, jax.Array]:
    """``(b1 @ qt[idx, :], b2 @ qt[idx, :])`` sharing one index gather."""
    check_compute_dtype(compute_dtype)
    block = _resolve("colgather_matmul_dual", b1, qt.shape[1], block)
    return _colgather_matmul_dual(b1, b2, qt, idx, block=block,
                                  interpret=interpret, out_dtype=out_dtype,
                                  compute_dtype=compute_dtype)
