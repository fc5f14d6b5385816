"""Jit'd public wrappers over the Pallas kernels.

``interpret`` defaults to True off-TPU so the same call sites work in CPU
tests and on real hardware (:func:`on_tpu`). The backend is asked at the
first kernel call, never at import: importing the optimizer must not take
the chip, so that a parent process can still hand it to a child.

The ``**kw`` passthrough is load-bearing for DESIGN.md §15: callers
(fused_step) forward ``compute_dtype`` here, and an omitted ``block``
leaves the kernels' ``block=None`` default in place, which resolves
against the process-wide TuningCache at trace time (repro.tune).
"""
from __future__ import annotations

import functools

import jax

from .colgather_matmul import colgather_matmul, colgather_matmul_dual
from .dct_project import dct_project
from .flash_attention import flash_attention
from .flash_decode import flash_decode
from .newton_schulz import newton_schulz_pallas, ns_iteration
from .quant_ef import dequant_add_ef, quantize_ef


@functools.cache
def on_tpu() -> bool:
    """True when JAX's default backend is a TPU (initialises the backend)."""
    return jax.default_backend() == "tpu"


def dct_project_op(g, q, **kw):
    kw.setdefault("interpret", not on_tpu())
    return dct_project(g, q, **kw)


def colgather_matmul_op(b, qt, idx, **kw):
    kw.setdefault("interpret", not on_tpu())
    return colgather_matmul(b, qt, idx, **kw)


def colgather_matmul_dual_op(b1, b2, qt, idx, **kw):
    kw.setdefault("interpret", not on_tpu())
    return colgather_matmul_dual(b1, b2, qt, idx, **kw)


def newton_schulz_op(x, **kw):
    kw.setdefault("interpret", not on_tpu())
    return newton_schulz_pallas(x, **kw)


def ns_iteration_op(x, **kw):
    kw.setdefault("interpret", not on_tpu())
    return ns_iteration(x, **kw)


def flash_attention_op(q, k, v, **kw):
    kw.setdefault("interpret", not on_tpu())
    return flash_attention(q, k, v, **kw)


def flash_decode_op(q, k_pool, v_pool, block_table, lengths, **kw):
    kw.setdefault("interpret", not on_tpu())
    return flash_decode(q, k_pool, v_pool, block_table, lengths, **kw)


def quantize_ef_op(x, **kw):
    kw.setdefault("interpret", not on_tpu())
    return quantize_ef(x, **kw)


def dequant_add_ef_op(g, q, scale, **kw):
    kw.setdefault("interpret", not on_tpu())
    return dequant_add_ef(g, q, scale, **kw)
