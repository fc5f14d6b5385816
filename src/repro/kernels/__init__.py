"""Pallas TPU kernels for the paper's compute hot-spots.

  dct_project      — fused S = G @ Q + column-norm ranking statistic
  colgather_matmul — fused back-projection b @ Q[:, idx]^T (scalar-prefetch
                     driven gather, never materializes Q_r); the _dual
                     variant back-projects two factors from one gather
  newton_schulz    — NS5 on the low-rank factor (r-sized Gram in VMEM)
  quant_ef         — int8 error-feedback quantize / fused dequant-add
  flash_attention  — train/prefill attention, GQA/causal/window, with a
                     fused FlashAttention-2 backward (custom VJP): score
                     tiles stay in VMEM, residuals are q, k, v, o and a
                     per-row logsumexp; models/layers.py:attention takes
                     it on a TPU
  flash_decode     — paged single-query decode attention: K/V gathered
                     through the serve block table via scalar-prefetched
                     index maps, split-KV parallel over cache blocks with
                     an online-softmax merge (serve/kv_cache.py is the
                     pool; DESIGN.md §12)

dct_project / colgather_matmul / quant_ef accept leading stacked-layer axes
(collapsed into a batch grid dimension), so the scan-stacked ``(layers, m,
n)`` leaves every production config emits run on the kernel path; the fused
projected-Adam step that drives them is core/fused_step.py (DESIGN.md §3).

Each has a pure-jnp oracle in ref.py; tests sweep shapes/dtypes against it in
interpret mode (this container is CPU-only; TPU v5e is the target).
"""
from . import ops, ref
from .colgather_matmul import colgather_matmul, colgather_matmul_dual
from .dct_project import dct_project
from .flash_attention import flash_attention
from .flash_decode import flash_decode
from .newton_schulz import newton_schulz_pallas, ns_iteration
from .quant_ef import dequant_add_ef, quantize_ef

__all__ = [
    "ops", "ref", "colgather_matmul", "colgather_matmul_dual", "dct_project",
    "flash_attention", "flash_decode", "newton_schulz_pallas", "ns_iteration",
    "dequant_add_ef", "quantize_ef",
]
