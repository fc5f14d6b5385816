"""Telemetry overhead gate (DESIGN.md §8): stats collection must be ≤3 %.

Times the fused projected-Adam optimizer step on the production-shaped
stacked leaf — (2, 4096, 4096) rank 256, the same subject as
``BENCH_optimizer_step.json`` — with and without a stats collector
installed, through the full chain API. Fails (non-zero exit / raise) when
enabling SubspaceStats collection regresses the fused median step time by
more than ``threshold`` (default 3 %), or when the fused execution layer
stops being reached with telemetry on (dispatch-spy regression).

Both variants are compiled up front and the timed steps *interleave* them
(off, on, off, on, ...), so slow drift in machine load hits both equally;
medians gate, means are reported — single-step outliers on shared CI
boxes must not flap a 3 % comparison.

  PYTHONPATH=src python -m benchmarks.telemetry_overhead \
      [--dim 4096] [--rank 256] [--threshold 0.03] [--out ...]
"""
from __future__ import annotations

import time

import jax

from .common import compile_opt_step


def run(*, layers: int = 2, dim: int = 4096, rank: int = 256,
        steps: int = 9, warmup: int = 1, threshold: float = 0.03,
        out_path: str | None = "BENCH_telemetry_overhead.json") -> dict:
    from repro.kernels import ops as kops
    from repro.optim.projected_adam import ProjectedAdamRule

    fused_mode = "on" if kops.on_tpu() else "fft"
    shape = (layers, dim, dim)
    rule = ProjectedAdamRule(rank=rank, projector="dct", residual="ef",
                             ef_dtype="q8", fused=fused_mode)
    result = {
        "bench": "telemetry_overhead",
        "leaf_shape": list(shape),
        "rank": rank,
        "fused_mode": fused_mode,
        "steps_timed": steps,
        "threshold": threshold,
        "backend": jax.default_backend(),
        "modes": {},
    }
    variants = {}
    for label, telemetry in (("stats_off", False), ("stats_on", True)):
        compiled, (grads, params), init, spy, peak = compile_opt_step(
            rule, shape, telemetry=telemetry)
        # telemetry must not knock the step off the fused execution layer
        spy.check(fused_mode)
        ca = compiled.cost_analysis() or {}
        variants[label] = {"compiled": compiled, "grads": grads,
                           "params": params, "state": init(),
                           "peak": peak, "dispatch": dict(spy.counts),
                           "flops": float(ca.get("flops", 0.0)),
                           "bytes": float(ca.get("bytes accessed", 0.0)),
                           "times": []}

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
        print(f"[telemetry_overhead] {label:9s} "
              f"median {row['s_per_step_median'] * 1e3:9.1f} ms/step "
              f"min {row['s_per_step_min'] * 1e3:9.1f} ms/step "
              f"flops {row['flops']:.3e} bytes {row['bytes_accessed']:.3e} "
              f"dispatch={row['dispatch']}")

    off, on = result["modes"]["stats_off"], result["modes"]["stats_on"]

    def frac(key):
        return (on[key] - off[key]) / max(off[key], 1e-30)

    # the deterministic gates: compiled flop/byte counts catch any real
    # extra pass regardless of machine noise; the wall gate uses the min
    # estimator (classic noise-robust choice) over interleaved samples
    result["overhead_frac"] = frac("s_per_step_median")
    result["overhead_frac_min"] = frac("s_per_step_min")
    result["overhead_frac_flops"] = frac("flops")
    result["overhead_frac_bytes"] = frac("bytes_accessed")
    print(f"[telemetry_overhead] overhead: median "
          f"{result['overhead_frac'] * 100:+.2f}% "
          f"min {result['overhead_frac_min'] * 100:+.2f}% "
          f"flops {result['overhead_frac_flops'] * 100:+.2f}% "
          f"bytes {result['overhead_frac_bytes'] * 100:+.2f}% "
          f"(gate: {threshold * 100:.0f}%)")
    if out_path:
        from benchmarks.common import write_bench_json
        write_bench_json(out_path, result)
        print(f"[telemetry_overhead] wrote {out_path}")
    failures = [k for k in ("overhead_frac_min", "overhead_frac_flops",
                            "overhead_frac_bytes")
                if result[k] > threshold]
    if failures:
        raise RuntimeError(
            f"enabling SubspaceStats collection regressed the fused step "
            f"beyond {threshold * 100:.0f}% at {shape} r={rank}: "
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
    ap.add_argument("--threshold", type=float, default=0.03)
    ap.add_argument("--out", default="BENCH_telemetry_overhead.json")
    args = ap.parse_args()
    run(layers=args.layers, dim=args.dim, rank=args.rank, steps=args.steps,
        warmup=args.warmup, threshold=args.threshold, out_path=args.out)
