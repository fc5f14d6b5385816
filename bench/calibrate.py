"""Readings from which a cell's check limits are set, all in one process:

- the program against the reference, on each of ``--seeds`` (the lower
  readings);
- the control, the reference computed with float8 matmul operands, put
  in the program's place, on ``--control-seeds`` (the upper readings);
- the faults planted in the reference (one token altered, half of each
  batch left out), on ``--fault-seeds``.

    python3 bench/calibrate.py --workload qwen32b_ft4k --seeds 101-112 \\
        --control-seeds 101-104 --fault-seeds 101-103 --out readings.jsonl

Each reading is one JSON line in ``--out`` and on standard output.
"""
import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import check, harness, program  # noqa: E402


def seeds(text: str) -> list:
    out = []
    for part in filter(None, text.split(",")):
        lo, _, hi = part.partition("-")
        out += list(range(int(lo), int(hi or lo) + 1))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    found = harness.find_cell(ROOT, args.workload)
    spec, job = found["spec"], found["job"]
    harness.check_device(found["cell"]["chips"])
    harness.enable_compile_cache()
    n = job["check_steps"]
    out = open(args.out, "a")

    def emit(rec):
        line = json.dumps(harness.clean(rec))
        print(line, flush=True)
        out.write(line + "\n")
        out.flush()

    todo = sorted(set(seeds(args.seeds) + seeds(args.control_seeds)
                      + seeds(args.fault_seeds)))
    for seed in todo:
        t = time.time()
        ref = harness.reference(spec, job).run(seed, n)
        ref_s = time.time() - t
        if seed in seeds(args.seeds):
            prog = program.Program(spec, job, seed)
            run = prog.start()
            got = {"losses": run.losses, "grad_norms": run.grad_norms,
                   "delta_norms": run.delta_norms}
            del run
            prog.release()
            del prog
            free()
            emit({"workload": args.workload, "seed": seed, "side": "program",
                  "numbers": check.numbers(got, ref), "losses": got["losses"],
                  "leaves": check.leaves(got, ref),
                  "reference_losses": ref["losses"], "reference_s": ref_s})
        runs = []
        if seed in seeds(args.control_seeds):
            runs.append(("control_fp8_fwd_bwd", dict(mode="fp8")))
        if seed in seeds(args.fault_seeds):
            runs += [("fault_token", dict(fault="token")),
                     ("fault_half_batch", dict(fault="half_batch"))]
        for side, kw in runs:
            free()
            other = harness.reference(spec, job, **kw).run(seed, n)
            emit({"workload": args.workload, "seed": seed, "side": side,
                  "numbers": check.numbers(other, ref),
                  "losses": other["losses"],
                  "leaves": check.leaves(other, ref),
                  "reference_losses": ref["losses"]})
        free()
    return 0


def free():
    """Drops unreferenced arrays and every loaded program: a loaded TPU
    program keeps HBM reserved for its temporaries."""
    import jax

    gc.collect()
    jax.clear_caches()


if __name__ == "__main__":
    sys.exit(main())
