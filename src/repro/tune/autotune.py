"""Compile+run measurement harness over the roofline-pruned survivors.

``tune_kernel`` is one cache entry's worth of work: prune the candidate
grid with :mod:`repro.tune.prune` (roofline predictions at the active
arch), time each survivor plus the kernel's hardcoded default with the
real jitted entry points (interpret mode off-TPU, so CI tuning runs are
hermetic), and store the winner in the process-wide
:class:`~repro.tune.cache.TuningCache` under the
``(kernel, shape, rank, dtype, platform)`` key the kernels resolve
``block=None`` against. ``tune_all`` sweeps a spec list and returns
JSON-able records (benchmarks/tuned_kernels.py persists them).

The default block is always measured alongside the survivors and wins
ties: a tuned cache can only match or beat the untuned defaults on the
machine that produced it (the BENCH_tuned_kernels.json gate).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from . import prune as prune_mod
from .cache import TuningCache, make_key, tuning_cache

#: kernel family -> (defining module, DEFAULT_* constant name); resolved
#: lazily so kernel imports stay out of module scope
_DEFAULT_BLOCKS = {
    "dct_project": ("repro.kernels.dct_project", "DEFAULT_BLOCK"),
    "colgather_matmul": ("repro.kernels.colgather_matmul", "DEFAULT_BLOCK"),
    "colgather_matmul_dual": ("repro.kernels.colgather_matmul",
                              "DEFAULT_BLOCK"),
    "quant_ef": ("repro.kernels.quant_ef", "DEFAULT_BM"),
    "newton_schulz": ("repro.kernels.newton_schulz", "DEFAULT_BM"),
}


def default_block(kernel: str):
    """The kernel's hardcoded untuned default block."""
    import importlib
    module, name = _DEFAULT_BLOCKS[kernel]
    return getattr(importlib.import_module(module), name)


def _operands(kernel: str, shape, rank: int, dtype):
    """Deterministic operands for one measurement (seed 0)."""
    key = jax.random.PRNGKey(0)
    if kernel == "dct_project":
        nb, m, n = shape
        k1, k2 = jax.random.split(key)
        return (jax.random.normal(k1, (nb, m, n), dtype),
                jax.random.normal(k2, (n, n), dtype))
    if kernel in ("colgather_matmul", "colgather_matmul_dual"):
        nb, m, n = shape
        r = rank or min(n, 64)
        k1, k2, k3 = jax.random.split(key, 3)
        b1 = jax.random.normal(k1, (nb, m, r), dtype)
        qt = jax.random.normal(k2, (n, n), dtype)
        idx = jnp.argsort(jax.random.uniform(k3, (nb, n)), axis=-1)
        idx = idx[:, :r].astype(jnp.int32)
        if kernel.endswith("_dual"):
            b2 = jax.random.normal(jax.random.fold_in(k1, 1), (nb, m, r),
                                   dtype)
            return b1, b2, qt, idx
        return b1, qt, idx
    if kernel == "quant_ef":
        nb, m, n = shape
        return (jax.random.normal(key, (nb, m, n), dtype),)
    if kernel == "newton_schulz":
        nb, r, m = shape
        return (jax.random.normal(key, (nb, r, m), dtype),)
    raise ValueError(f"unknown kernel family {kernel!r}")


def _runner(kernel: str, operands, block, interpret: bool):
    """A zero-arg thunk running one launch of ``kernel`` at ``block``."""
    from repro.kernels import (colgather_matmul, colgather_matmul_dual,
                               dct_project, dequant_add_ef, ns_iteration,
                               quantize_ef)
    if kernel == "dct_project":
        g, q = operands
        return lambda: dct_project(g, q, block=block, interpret=interpret)
    if kernel == "colgather_matmul":
        b, qt, idx = operands
        return lambda: colgather_matmul(b, qt, idx, block=block,
                                        interpret=interpret)
    if kernel == "colgather_matmul_dual":
        b1, b2, qt, idx = operands
        return lambda: colgather_matmul_dual(b1, b2, qt, idx, block=block,
                                             interpret=interpret)
    if kernel == "quant_ef":
        (x,) = operands

        def run():
            qv, scale = quantize_ef(x, bm=block, interpret=interpret)
            return dequant_add_ef(x, qv, scale, bm=block, interpret=interpret)
        return run
    if kernel == "newton_schulz":
        (x,) = operands
        return lambda: ns_iteration(x, bm=block, interpret=interpret)
    raise ValueError(f"unknown kernel family {kernel!r}")


def measure(kernel: str, shape, rank: int, dtype, block, *,
            interpret: bool | None = None, iters: int = 3,
            warmup: int = 1, operands=None) -> float:
    """Best-of-``iters`` wall seconds for one launch (after ``warmup``
    compile+run calls)."""
    if interpret is None:
        from repro.kernels import ops
        interpret = not ops.on_tpu()
    if operands is None:
        operands = _operands(kernel, shape, rank, dtype)
    run = _runner(kernel, operands, block, interpret)
    for _ in range(max(1, warmup)):
        jax.block_until_ready(run())
    best = float("inf")
    for _ in range(max(1, iters)):
        t0 = time.perf_counter()
        jax.block_until_ready(run())
        best = min(best, time.perf_counter() - t0)
    return best


def tune_kernel(kernel: str, shape, rank: int = 0, dtype="float32", *,
                arch: str | None = None, keep: int = 4,
                interpret: bool | None = None, iters: int = 3,
                warmup: int = 1, cache: TuningCache | None = None,
                platform: str | None = None) -> dict:
    """Tune one cache entry; stores the winner and returns a record::

        {"kernel", "shape", "rank", "dtype", "platform", "grid_size",
         "survivors", "timings_s": {str(block): s}, "predicted_s": {...},
         "default_block", "default_s", "best_block", "best_s", "speedup"}
    """
    cache = cache if cache is not None else tuning_cache()
    dtype = str(jnp.dtype(dtype))
    survivors = prune_mod.prune(kernel, shape, rank, dtype, arch=arch,
                                keep=keep)
    dflt = default_block(kernel)
    blocks = [c.block for c in survivors]
    if dflt not in blocks:
        blocks.append(dflt)
    operands = _operands(kernel, shape, rank, dtype)
    timings = {}
    for b in blocks:
        timings[str(b)] = measure(kernel, shape, rank, dtype, b,
                                  interpret=interpret, iters=iters,
                                  warmup=warmup, operands=operands)
    default_s = timings[str(dflt)]
    # default wins ties: the cache can only match-or-beat the untuned path
    best_block = min(blocks, key=lambda b: (timings[str(b)], b != dflt))
    key = make_key(kernel, shape, rank, dtype, platform)
    cache.store(key, best_block)
    return {
        "kernel": kernel, "shape": list(shape), "rank": rank, "dtype": dtype,
        "platform": key[-1],
        "grid_size": prune_mod.grid_size(kernel, shape, rank),
        "survivors": [str(c.block) for c in survivors],
        "predicted_s": {str(c.block): c.predicted_s for c in survivors},
        "bound": survivors[0].bound if survivors else None,
        "timings_s": timings,
        "default_block": str(dflt), "default_s": default_s,
        "best_block": str(best_block), "best_s": timings[str(best_block)],
        "speedup": default_s / max(timings[str(best_block)], 1e-12),
    }


#: the reduced grid the CI ``tune`` job sweeps (small shapes, interpret mode)
REDUCED_SPECS = (
    ("dct_project", (1, 128, 128), 0),
    ("colgather_matmul", (1, 128, 128), 32),
    ("colgather_matmul_dual", (2, 64, 128), 32),
    ("quant_ef", (1, 128, 128), 0),
    ("newton_schulz", (1, 32, 128), 32),
)

#: a production-shaped sweep (one stacked transformer leaf per family)
FULL_SPECS = (
    ("dct_project", (2, 1024, 1024), 0),
    ("colgather_matmul", (2, 1024, 1024), 256),
    ("colgather_matmul_dual", (2, 1024, 1024), 256),
    ("quant_ef", (2, 1024, 1024), 0),
    ("newton_schulz", (2, 256, 1024), 256),
)


def tune_all(specs=REDUCED_SPECS, *, dtype="float32",
             arch: str | None = None, keep: int = 4,
             interpret: bool | None = None, iters: int = 3,
             warmup: int = 1, cache: TuningCache | None = None,
             platform: str | None = None, verbose: bool = False
             ) -> list[dict]:
    """Sweep ``(kernel, shape, rank)`` specs; returns one record each."""
    out = []
    for kernel, shape, rank in specs:
        rec = tune_kernel(kernel, shape, rank, dtype, arch=arch, keep=keep,
                          interpret=interpret, iters=iters, warmup=warmup,
                          cache=cache, platform=platform)
        if verbose:
            print(f"[tune] {kernel} {tuple(shape)} r={rank}: "
                  f"{rec['best_block']} ({rec['best_s'] * 1e3:.2f}ms, "
                  f"default {rec['default_s'] * 1e3:.2f}ms, "
                  f"x{rec['speedup']:.2f})")
        out.append(rec)
    return out
