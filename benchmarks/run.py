"""Benchmark driver: one section per paper table/figure.

  python -m benchmarks.run [--fast] [--only trion_vs_dion,...]

Sections:
  trion_vs_dion        Table 1 / Fig 3   Trion vs Dion pre-training
  dct_adamw            Table 2 / Fig 2   AdamW vs LDAdamW vs DCT-AdamW
  makhoul              Tables 4-5        FFT-DCT vs matmul timing
  frugal_fira          Table 6           projection swap in FRUGAL/FIRA
  projection_errors    Fig 1 / App F     factorization error Trion vs Dion
  finetune             Tables 7-8        fine-tune proxy across optimizers
  optimizer_step       DESIGN.md §3      fused vs reference projected-Adam
                                         step -> BENCH_optimizer_step.json
  telemetry_overhead   DESIGN.md §8      stats-on vs stats-off fused step
                                         (≤3% gate) ->
                                         BENCH_telemetry_overhead.json
  basis_transforms     DESIGN.md §10     fast-vs-matmul per basis backend
                                         -> BENCH_basis_transforms.json
  basis_errors         DESIGN.md §10     per-basis selection error vs the
                                         rank-r SVD optimum
  serve_decode         DESIGN.md §12     paged continuous-batching decode:
                                         paged-vs-dense cache bytes, tok/s
                                         static vs churn, flash-decode
                                         dispatch gate -> BENCH_serve.json
  obs_overhead         DESIGN.md §13     obs-on vs obs-off serving tok/s
                                         (≤2% gate) and train-loop wall
                                         (≤1% gate) ->
                                         BENCH_obs_overhead.json
  tuned_kernels        DESIGN.md §15     roofline-pruned autotuner sweep:
                                         tuned-vs-default block ratio per
                                         kernel family (gate: tuned >=
                                         default within noise) ->
                                         BENCH_tuned_kernels.json
  lowp_errors          DESIGN.md §15     bf16/int8 projection-matmul error
                                         + selection overlap vs fp32 on
                                         the App. F gradient stream (gate:
                                         LOWP_ERROR_BOUNDS)

``--tune-cache PATH`` preloads autotuned block sizes into the process-wide
TuningCache before any section jits, so every kernel launched with
``block=None`` resolves its tuned block (repro.tune; docs/tuning.md).
"""
from __future__ import annotations

import argparse
import sys
import time


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="fewer steps (CI smoke)")
    ap.add_argument("--only", default=None)
    ap.add_argument("--tune-cache", default=None, metavar="PATH",
                    help="autotuned block-size cache JSON to preload "
                         "(repro.tune; must load before the first jit)")
    args = ap.parse_args(argv)
    steps = 15 if args.fast else 40

    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()

    if args.tune_cache:
        from repro.tune import tuning_cache
        tuning_cache().load(args.tune_cache)
        print(f"[bench] loaded tuning cache {args.tune_cache} "
              f"({len(tuning_cache())} entries)")

    from . import (dct_adamw_vs_ldadamw, finetune, frugal_fira,
                   makhoul_vs_matmul, obs_overhead, projection_errors,
                   serve_decode, telemetry_overhead, trion_vs_dion,
                   tuned_kernels)

    sections = {
        "trion_vs_dion": lambda: trion_vs_dion.run(steps=steps),
        "dct_adamw": lambda: dct_adamw_vs_ldadamw.run(steps=steps),
        "makhoul": lambda: makhoul_vs_matmul.run(
            sizes=((512, 512), (2048, 512), (512, 2048)) if args.fast
            else ((1024, 1024), (4096, 1024), (1024, 4096))),
        "frugal_fira": lambda: frugal_fira.run(steps=steps),
        "projection_errors": lambda: projection_errors.run(
            steps=10 if args.fast else 30),
        "finetune": lambda: finetune.run(
            pretrain_steps=10 if args.fast else 30,
            ft_steps=10 if args.fast else 25),
        # fast mode writes to a scratch path so it never clobbers the
        # committed production-shape perf record
        "optimizer_step": lambda: dct_adamw_vs_ldadamw.run_step_bench(
            dim=1024 if args.fast else 4096,
            rank=64 if args.fast else 256,
            out_path=("BENCH_optimizer_step_fast.json" if args.fast
                      else "BENCH_optimizer_step.json")),
        # fast mode: tiny (~65ms) steps can't resolve a 3% wall gate on a
        # noisy box, so the scratch variant loosens the threshold; the
        # committed production-shape gate stays at 3% (CI runs that one)
        "telemetry_overhead": lambda: telemetry_overhead.run(
            dim=1024 if args.fast else 4096,
            rank=64 if args.fast else 256,
            threshold=0.15 if args.fast else 0.03,
            out_path=("BENCH_telemetry_overhead_fast.json" if args.fast
                      else "BENCH_telemetry_overhead.json")),
        # per-backend fast-vs-matmul (fast mode: scratch path + reduced
        # size so the committed production-shape record never gets
        # clobbered; n stays >= 2048 because the FHT-beats-matmul assert
        # needs a decisive margin on a noisy CI box)
        "basis_transforms": lambda: makhoul_vs_matmul.run_transforms(
            rows=128 if args.fast else 512,
            n=2048 if args.fast else 4096,
            out_path=("BENCH_basis_transforms_fast.json" if args.fast
                      else "BENCH_basis_transforms.json")),
        "basis_errors": lambda: projection_errors.run_basis_errors(
            steps=4 if args.fast else 10),
        # paged serving decode; the memory assert and the flash-decode
        # dispatch gate hard-fail in both modes (fast mode: fewer tokens,
        # scratch path so the committed record isn't clobbered)
        "serve_decode": lambda: serve_decode.run(
            new_tokens=8 if args.fast else 32,
            out_path=("BENCH_serve_fast.json" if args.fast
                      else "BENCH_serve.json")),
        # obs-on vs obs-off hot-path gates (fast mode: fewer/shorter waves
        # can't resolve a 1-2% wall gate on a noisy box, so the scratch
        # variant loosens the thresholds — same precedent as
        # telemetry_overhead; CI's obs job runs the full gates)
        "obs_overhead": lambda: obs_overhead.run(
            waves=2 if args.fast else 6,
            serve_new_tokens=8 if args.fast else 24,
            train_steps_per_wave=10 if args.fast else 25,
            serve_threshold=0.15 if args.fast else 0.02,
            train_threshold=0.10 if args.fast else 0.01,
            out_path=("BENCH_obs_overhead_fast.json" if args.fast
                      else "BENCH_obs_overhead.json")),
        # autotuner sweep (fast mode: reduced CI grid + scratch path so the
        # committed production-shape record isn't clobbered)
        "tuned_kernels": lambda: tuned_kernels.run(
            fast=args.fast,
            iters=1 if args.fast else 3,
            out_path=("BENCH_tuned_kernels_fast.json" if args.fast
                      else "BENCH_tuned_kernels.json")),
        "lowp_errors": lambda: projection_errors.run_lowp_errors(
            steps=4 if args.fast else 10),
    }
    chosen = (args.only.split(",") if args.only else list(sections))
    failures = 0
    for name in chosen:
        print(f"\n===== {name} =====")
        t0 = time.perf_counter()
        try:
            sections[name]()
        except Exception as e:                       # noqa: BLE001
            import traceback
            traceback.print_exc()
            print(f"[bench] {name} FAILED: {e}")
            failures += 1
        print(f"[bench] {name} done in {time.perf_counter() - t0:.1f}s")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
