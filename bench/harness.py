"""One run of one cell: set-up, a measured window, the metrics, and the
check that decides ``correct``.

Everything that belongs to one configuration, traffic mix or per-layer
metric is found by name under the benchmark's root:

- ``BENCHMARK.json``: the cells, metrics and configuration files;
- ``bench/traffic/<traffic>.json``: the job a cell runs;
- ``bench/limits/<workload>.json``: the limits of the cell's check;
- ``bench/metrics/<metric>.py``: a reader ``read(ctx) -> float | None``;
- ``bench/reference/<name>.py``: the plain reference a configuration
  file names.

A configuration's own files define its model; the harness knows no
architecture. A configuration is added by these files and entries alone:

- ``bench/configs/<name>.json``: ``registry`` (the program's config it
  cuts), ``reference`` (the module's name), ``model`` (the keys put into
  the registry's config, its layers as ``schedule``, a list of ``[pattern,
  repeats]`` whose segments are the registry's in order, each repeated 1
  to the registry's times; ``n_layers`` alone means ``[[["attn"],
  n_layers]]``), a top-level ``init`` (``{leaf-path suffix: "fan_in" |
  std}`` for the leaves that ``weights.py``'s common rules do not know),
  ``published``, ``reduced``, ``assumed``, ``deployment``, ``precision``;
- ``bench/reference/<name>.py``: ``Reference(model, job, init=...)``, whose
  ``run(seed, steps)`` returns ``losses``, ``grad_norms``,
  ``true_grad_norms`` and ``delta_norms``; ``param_specs(model)``, laid
  out as the program's parameters; ``model_flops_per_token(model,
  seq_len)``, which ``mfu`` reads as ``ctx.flops_per_token``;
- ``bench/traffic/<job>.json`` and ``bench/limits/<cell>.json``;
- any new ``bench/metrics/<name>.py``;
- the entries in ``BENCHMARK.json``.

The last line on standard output is the result's JSON object.
"""
from __future__ import annotations

import argparse
import gc
import importlib
import importlib.util
import json
import math
import os
import shutil
import sys
import time
import types

from . import check, xtrace

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "peaks.json")


class NoChip(RuntimeError):
    """No accelerator, too few chips, or a device kind without peaks."""


# ---------------------------------------------------------------------------
# discovery
# ---------------------------------------------------------------------------
def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def find_cell(root: str, workload: str) -> dict:
    """The cell's entry with its configuration, job, limits and metric
    entries resolved from files under ``root``."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; have {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    entry = configs[cell["config"]]
    spec = load_json(os.path.join(root, entry["file"]))

    def applies(metric):
        return workload in metric.get("workloads", [workload])

    return {
        "cell": cell,
        "spec": spec,
        "job": load_json(os.path.join(root, "bench", "traffic",
                                      cell["traffic"] + ".json")),
        "limits": load_json(os.path.join(root, "bench", "limits",
                                         workload + ".json"))["limits"],
        "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
        "per_layer": [m for m in bench["per_layer"] if applies(m)],
    }


def metric_reader(root: str, name: str):
    path = os.path.join(root, "bench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def model_of(spec: dict) -> dict:
    """The file's ``model``, with ``n_layers`` summed from its ``schedule``
    where the file states none: what the reference and the readers see."""
    model = dict(spec["model"])
    if "n_layers" not in model:
        model["n_layers"] = sum(len(p) * r for p, r in model["schedule"])
    return model


def reference_module(spec: dict):
    return importlib.import_module("bench.reference." + spec["reference"])


def reference(spec: dict, job: dict, **kw):
    """The file's plain reference, drawing its weights by the file's
    ``init`` rules as the program does."""
    return reference_module(spec).Reference(
        model_of(spec), job, init=spec.get("init", {}), **kw)


# ---------------------------------------------------------------------------
# the device
# ---------------------------------------------------------------------------
def check_device(chips: int, *, require_chip: bool = True):
    """``(devices, peak entry)``; raises :class:`NoChip` on the CPU, with
    fewer chips than the cell asks for, or on a device kind the peaks
    table does not know."""
    import jax

    devs = jax.devices()
    kind = devs[0].device_kind
    peaks = load_json(PEAKS)["devices"]
    if require_chip:
        if devs[0].platform != "tpu":
            raise NoChip(f"no accelerator: JAX runs on {devs[0].platform}")
        if kind not in peaks:
            raise NoChip(f"device kind {kind!r} is not in {PEAKS}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    if kind not in peaks:
        # only the CPU tests get here: they never report a device metric
        kind = next(iter(peaks))
    return devs[:chips], peaks[kind]


STATS = ("bytes_in_use", "peak_bytes_in_use", "bytes_reserved",
         "peak_bytes_reserved", "bytes_limit")


def memory_reading(devs) -> list:
    """Each chip's ``memory_stats()``, the keys of ``STATS`` only."""
    return [{k: int((d.memory_stats() or {}).get(k, 0)) for k in STATS}
            for d in devs]


def step_memory(compiled) -> dict:
    """The compiled step's own account of its memory, per chip."""
    ma = compiled.memory_analysis()
    keys = ("argument_size_in_bytes", "output_size_in_bytes",
            "alias_size_in_bytes", "temp_size_in_bytes",
            "generated_code_size_in_bytes")
    return {k: int(getattr(ma, k, 0) or 0) for k in keys}


def device_block(devs, peak_bytes: int) -> dict:
    """The device as JAX reports it, with the step's peak on the fullest
    chip."""
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak_bytes}


def enable_compile_cache() -> str:
    """The program's fixed cache directory in the checkout (or
    ``$JAX_COMPILATION_CACHE_DIR``), holding every program: nothing but
    the first run of a cell in a checkout compiles."""
    import jax
    from repro.launch.cache import enable_compile_cache as program_cache

    path = program_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts backend compilations while armed."""

    def __init__(self):
        import jax.monitoring

        self.armed, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, duration, **kw):
        if self.armed and name.endswith("backend_compile_duration"):
            self.count += 1


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------
def run_cell(root: str, workload: str, seed: int, seconds: float,
             trace: bool, t0: float, *, require_chip: bool = True,
             step_wrapper=None) -> dict:
    """Runs the cell once and returns the result object (not printed)."""
    import jax

    from . import program

    found = find_cell(root, workload)
    cell, spec, job = found["cell"], found["spec"], found["job"]
    devs, peak = check_device(cell["chips"], require_chip=require_chip)
    if require_chip:
        enable_compile_cache()
    compiles = CompileCounter()

    prog = program.Program(spec, job, seed, step_wrapper=step_wrapper)
    run = prog.start()
    n_steps = program.window_steps(run.step_seconds, seconds)
    tokens_per_step, opt_bytes = run.tokens_per_step, run.opt_state_bytes
    prog_nums = {"losses": run.losses, "grad_norms": run.grad_norms,
                 "delta_norms": run.delta_norms}
    setup_s = time.time() - t0

    trace_dir = os.path.join(root, ".bench_out", "trace")
    if trace:
        from repro import obs

        shutil.rmtree(trace_dir, ignore_errors=True)
        obs.enable()
        obs.reset()
        jax.profiler.start_trace(trace_dir)
    compiles.armed = True
    with jax.profiler.TraceAnnotation(xtrace.WINDOW):
        window_s, state = prog.window(run, n_steps)
    compiles.armed = False
    if trace:
        jax.profiler.stop_trace()
    # The step's peak: what it holds between steps (the train state and
    # the fed batches, read now, before anything else is made) and the
    # temporaries its compiled program allocates while it runs
    memory = memory_reading(devs)
    t_c = time.time()
    compiled = prog.step_fn.lower(state, run.batch_fn(0)).compile()
    step_mem = dict(step_memory(compiled), load_s=time.time() - t_c)
    peak_bytes = (max(m["bytes_in_use"] for m in memory)
                  + step_mem["temp_size_in_bytes"])
    device = device_block(devs, peak_bytes)

    metrics = {}
    breakdown = None
    if not trace:
        rate = n_steps * tokens_per_step / window_s
        values = {"train_tokens_per_s": rate,
                  "peak_hbm_gib": peak_bytes / 2**30,
                  "setup_s": setup_s}
        for m in found["end_to_end"]:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    else:
        from repro import obs

        hlo = compiled.as_text()
        with open(os.path.join(trace_dir, "step.hlo.txt"), "w") as f:
            f.write(hlo)
        tr = xtrace.load(trace_dir)
        kernels = xtrace.hlo_kernels(hlo)
        ids = sorted(tr.devices)[:cell["chips"]]
        busy = [xtrace.busy_seconds(tr, d) for d in ids]
        model = model_of(spec)
        ctx = types.SimpleNamespace(
            trace=tr, kernels=kernels, devices=ids, steps=n_steps,
            window_s=xtrace.window_seconds(tr), chips=cell["chips"],
            peak=peak, model=model, job=job,
            flops_per_token=reference_module(spec).model_flops_per_token(
                model, job["seq_len"]),
            spans=[r for r in obs.tracer().records() if r],
            facts={"opt_state_bytes": opt_bytes,
                   "tokens_per_step": tokens_per_step})
        for m in found["per_layer"]:
            value = metric_reader(root, m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = sum(busy) / max(len(busy), 1)
        device["window_s"] = ctx.window_s
        breakdown = xtrace.breakdown(tr, kernels)
        obs.disable()

    # the program's state goes before the reference runs on the device
    del state, run, compiled
    prog.release()
    del prog
    gc.collect()
    jax.clear_caches()

    t_ref = time.time()
    ref = reference(spec, job).run(seed, job["check_steps"])
    ref_s = time.time() - t_ref
    nums = check.numbers(prog_nums, ref)
    ok, detail = check.verdict(nums, found["limits"])
    result = {"correct": ok, "attempted": n_steps, "failed": 0 if ok else 1,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["facts"] = {"window_steps": n_steps, "window_s": window_s,
                       "compiles_in_window": compiles.count,
                       "reference_s": ref_s,
                       "memory_after_window": memory[0],
                       "step_memory": step_mem,
                       "losses": prog_nums["losses"],
                       "reference_losses": ref["losses"],
                       "leaves": check.leaves(prog_nums, ref)}
    result["check"] = detail
    return result


def report(result: dict) -> None:
    """Each compared number beside its limit as the last lines on
    standard error, then the result's line on standard output."""
    for name, d in result["check"].items():
        print(f"check {name} {d['value']} limit {d['limit']}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(clean(result)), flush=True)


def main(argv, root: str, t0: float) -> int:
    ap = argparse.ArgumentParser(prog="bench/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be >= 0")
    try:
        result = run_cell(root, args.workload, args.seed, args.seconds,
                          bool(args.trace), t0)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    report(result)
    return 0


def clean(x):
    """JSON has no NaN or infinity: such a number is written as null."""
    if isinstance(x, float) and not math.isfinite(x):
        return None
    if isinstance(x, dict):
        return {k: clean(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [clean(v) for v in x]
    return x
