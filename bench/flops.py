"""Operations and bytes of the optimizer's kernels, from shapes alone, so
that they are the same whatever implements them. A model's FLOPs per
token are its reference module's ``model_flops_per_token``.
"""
from __future__ import annotations

F32 = 4


def dct_project(batch: int, m: int, n: int) -> tuple[float, float]:
    """``S = G Q`` over ``batch`` stacked (m, n) fp32 leaves: ``2 m n^2``
    FLOPs each; bytes read G and Q, write S."""
    flops = 2.0 * batch * m * n * n
    nbytes = F32 * (batch * m * n + n * n + batch * m * n)
    return flops, nbytes


def colgather_dual(batch: int, m: int, r: int, n: int) -> tuple[float, float]:
    """``(u Q_r^T, g Q_r^T)``: ``4 m r n`` FLOPs each leaf; bytes read the
    two (m, r) factors and the r gathered rows of ``Q^T``, write the two
    (m, n) results."""
    flops = 4.0 * batch * m * r * n
    nbytes = F32 * (2 * batch * m * r + batch * r * n + 2 * batch * m * n)
    return flops, nbytes


def roofline_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: the larger of the compute and
    the memory bound. The compute bound is at the bf16 peak: the kernels'
    "fp32" dots run as bf16 MXU passes on the chip."""
    return max(flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"])
