"""Anomaly-guard overhead gate (DESIGN.md §11): the in-jit guard must be free.

Times the fused projected-Adam optimizer step on the production-shaped
stacked leaf — (2, 4096, 4096) rank 256, the same subject as
``BENCH_optimizer_step.json`` / ``BENCH_telemetry_overhead.json`` — with
and without the resilience guard tail appended (``all_finite_tree`` over
the produced updates + the ``select_tree`` commit/reject point on the
optimizer state, exactly the extra work ``make_train_step(...,
guard=True)`` adds per step).

The acceptance invariant is *"the HLO is unchanged except the
finite-flag select"*, gated at 1 %:

- **flops**: raw compiled flop count, ≤ ``threshold`` (the guard adds a
  handful of scalar ANDs — any real extra pass shows up here).
- **bytes beyond the select**: the select and the finite check cannot
  avoid reading their own operands (old + new value of every state leaf
  at the commit point; the updates tree for the check) — that traffic is
  the criterion's named exception. The gate subtracts an *analytic upper
  bound* on those operand bytes (computed from the abstract state /
  updates trees; ``select(p, x, x)`` on untouched leaves folds to zero,
  so the bound is slightly generous) and requires everything **else** to
  be ≤ ``threshold``: if the guard ever breaks a fusion of the main
  dataflow, duplicates projection work, or forces extra full-size
  copies, this trips.
- **wall**: min-estimator over interleaved samples, ≤ ``wall_threshold``
  (default 3 % — same noise floor the telemetry gate uses on shared CI
  boxes; in practice the select fuses and the wall delta is ~the operand
  reads, well under it).

Both variants are compiled up front and the timed steps *interleave* them
(off, on, off, on, ...), so slow drift in machine load hits both equally.
Raw overhead fractions are all reported in the JSON for transparency.
Fails (non-zero exit / raise) on any gate, or when the fused execution
layer stops being reached with the guard on (dispatch-spy regression).

  PYTHONPATH=src python -m benchmarks.resilience_overhead \
      [--dim 4096] [--rank 256] [--threshold 0.01] [--out ...]
"""
from __future__ import annotations

import time

import jax

from .common import compile_opt_step


def _tree_bytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree)
               if hasattr(x, "size"))


def guard_operand_bytes(state, updates_like) -> dict:
    """Analytic upper bound on the guard's own unavoidable memory traffic.

    ``select_tree`` at the commit point reads the old and the new value of
    every optimizer-state leaf (untouched leaves are the same tensor in
    both trees and fold away — counting them anyway makes this a slightly
    generous bound, never an underestimate of what is allowed).
    ``all_finite_tree`` reads every inexact updates leaf once and its
    1-byte finiteness predicate once."""
    select_b = 2 * _tree_bytes(state)
    check_b = sum(x.size * x.dtype.itemsize + x.size
                  for x in jax.tree.leaves(updates_like)
                  if hasattr(x, "size")
                  and jax.numpy.issubdtype(x.dtype, jax.numpy.inexact))
    return {"select_bytes": int(select_b), "check_bytes": int(check_b),
            "total": int(select_b + check_b)}


def run(*, layers: int = 2, dim: int = 4096, rank: int = 256,
        steps: int = 9, warmup: int = 1, threshold: float = 0.01,
        wall_threshold: float = 0.03,
        out_path: str | None = "BENCH_resilience_overhead.json") -> dict:
    from repro.kernels import ops as kops
    from repro.optim.projected_adam import ProjectedAdamRule

    fused_mode = "on" if kops.on_tpu() else "fft"
    shape = (layers, dim, dim)
    rule = ProjectedAdamRule(rank=rank, projector="dct", residual="ef",
                             ef_dtype="q8", fused=fused_mode)
    result = {
        "bench": "resilience_overhead",
        "leaf_shape": list(shape),
        "rank": rank,
        "fused_mode": fused_mode,
        "steps_timed": steps,
        "threshold": threshold,
        "wall_threshold": wall_threshold,
        "backend": jax.default_backend(),
        "modes": {},
    }
    variants = {}
    for label, guard in (("guard_off", False), ("guard_on", True)):
        compiled, (grads, params), init, spy, peak = compile_opt_step(
            rule, shape, guard=guard)
        # the guard must not knock the step off the fused execution layer
        spy.check(fused_mode)
        ca = compiled.cost_analysis() or {}
        variants[label] = {"compiled": compiled, "grads": grads,
                           "params": params, "state": init(),
                           "peak": peak, "dispatch": dict(spy.counts),
                           "flops": float(ca.get("flops", 0.0)),
                           "bytes": float(ca.get("bytes accessed", 0.0)),
                           "times": []}
    # the guard's allowed traffic: select over this state, check over
    # updates shaped like the grads tree
    allowance = guard_operand_bytes(variants["guard_on"]["state"],
                                    variants["guard_on"]["grads"])
    result["guard_operand_bytes"] = allowance

    def one_step(v, record: bool):
        tic = time.perf_counter()
        out = v["compiled"](v["grads"], v["state"], v["params"])
        v["state"] = out[1]
        jax.block_until_ready(out[0])
        if record:
            v["times"].append(time.perf_counter() - tic)

    labels = list(variants)
    for k in range(warmup + steps):                 # interleaved, with the
        order = labels if k % 2 == 0 else labels[::-1]   # order alternating
        for label in order:                              # per round
            one_step(variants[label], record=k >= warmup)

    for label, v in variants.items():
        ts = sorted(v["times"])
        result["modes"][label] = {
            "s_per_step": sum(ts) / len(ts),
            "s_per_step_median": ts[len(ts) // 2],
            "s_per_step_min": ts[0],
            "flops": v["flops"],
            "bytes_accessed": v["bytes"],
            "peak_live_bytes": v["peak"],
            "dispatch": v["dispatch"],
        }
        row = result["modes"][label]
        print(f"[resilience_overhead] {label:9s} "
              f"median {row['s_per_step_median'] * 1e3:9.1f} ms/step "
              f"min {row['s_per_step_min'] * 1e3:9.1f} ms/step "
              f"flops {row['flops']:.3e} bytes {row['bytes_accessed']:.3e} "
              f"dispatch={row['dispatch']}")

    off, on = result["modes"]["guard_off"], result["modes"]["guard_on"]

    def frac(key):
        return (on[key] - off[key]) / max(off[key], 1e-30)

    # raw fractions (reported); the deterministic gates below subtract the
    # guard's own operand traffic from the bytes delta — the criterion's
    # named exception — and use the min estimator (classic noise-robust
    # choice) over interleaved samples for the wall gate
    result["overhead_frac"] = frac("s_per_step_median")
    result["overhead_frac_min"] = frac("s_per_step_min")
    result["overhead_frac_flops"] = frac("flops")
    result["overhead_frac_bytes"] = frac("bytes_accessed")
    extra_beyond = (on["bytes_accessed"] - off["bytes_accessed"]
                    - allowance["total"])
    result["overhead_frac_bytes_beyond_select"] = (
        extra_beyond / max(off["bytes_accessed"], 1e-30))
    print(f"[resilience_overhead] overhead: median "
          f"{result['overhead_frac'] * 100:+.2f}% "
          f"min {result['overhead_frac_min'] * 100:+.2f}% "
          f"flops {result['overhead_frac_flops'] * 100:+.2f}% "
          f"bytes {result['overhead_frac_bytes'] * 100:+.2f}% "
          f"(select operands {allowance['total'] / 1e6:.0f} MB -> beyond "
          f"{result['overhead_frac_bytes_beyond_select'] * 100:+.2f}%; "
          f"gates: {threshold * 100:.0f}% flops/bytes, "
          f"{wall_threshold * 100:.0f}% wall)")
    if out_path:
        from benchmarks.common import write_bench_json
        write_bench_json(out_path, result)
        print(f"[resilience_overhead] wrote {out_path}")
    failures = [k for k, gate in (
        ("overhead_frac_flops", threshold),
        ("overhead_frac_bytes_beyond_select", threshold),
        ("overhead_frac_min", wall_threshold),
    ) if result[k] > gate]
    if failures:
        raise RuntimeError(
            f"the in-jit anomaly guard regressed the fused step at {shape} "
            f"r={rank} beyond the gate: "
            + ", ".join(f"{k}={result[k] * 100:+.2f}%" for k in failures))
    return result


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--dim", type=int, default=4096)
    ap.add_argument("--rank", type=int, default=256)
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--warmup", type=int, default=2)
    ap.add_argument("--threshold", type=float, default=0.01)
    ap.add_argument("--wall-threshold", type=float, default=0.03)
    ap.add_argument("--out", default="BENCH_resilience_overhead.json")
    args = ap.parse_args()
    run(layers=args.layers, dim=args.dim, rank=args.rank, steps=args.steps,
        warmup=args.warmup, threshold=args.threshold,
        wall_threshold=args.wall_threshold, out_path=args.out)
