"""Autogenerate docs/api/ markdown from docstrings (and keep it fresh).

  PYTHONPATH=src python scripts/gen_api_docs.py              # rewrite docs/api/
  PYTHONPATH=src python scripts/gen_api_docs.py --check      # CI freshness gate
  PYTHONPATH=src python scripts/gen_api_docs.py --check-links  # docs/ + README

The page spec below is the single source of truth for what is public API
documentation-wise: ``repro.core`` (basis backends, cache, projectors),
``repro.optim`` (transform combinators, primitives, runtime, presets,
rules), ``repro.telemetry`` (stats, sink, controllers, adaptive glue),
the ``repro.train`` resilience surface, ``repro.serve`` and
``repro.obs`` (metrics, tracing, exporters). ``--check`` regenerates in
memory and fails
when the committed files drift from the source docstrings/signatures —
wired as the CI docs job, so the reference cannot rot silently.
``--check-links`` resolves every relative markdown link under ``docs/``
and ``README.md``.
"""
from __future__ import annotations

import argparse
import importlib
import inspect
import os
import re
import sys
import textwrap

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
API_DIR = os.path.join(ROOT, "docs", "api")
HEADER = "<!-- generated by scripts/gen_api_docs.py — do not edit by hand -->"

# (filename, page title, intro, [(module, [public names])])
PAGES = [
    ("core.md", "repro.core — basis backends & projection core", """\
The pluggable orthogonal-basis layer (DESIGN.md §10) and the projector
family built on it. See [docs/transforms.md](../transforms.md) for the
guide (backend protocol, when Hadamard beats DCT, cache semantics).
""", [
        ("repro.core.transforms", [
            "BasisBackend", "register_backend", "get_backend",
            "backend_kinds", "is_backend", "BasisCache", "basis_cache",
            "shared_basis", "dst2_matrix", "hadamard_matrix", "fwht",
            "random_orthogonal_matrix",
        ]),
        ("repro.core.projectors", [
            "Projector", "projector_kinds", "shared_basis_for",
            "rotation_matrix",
        ]),
    ]),
    ("optim.md", "repro.optim — composable optimizer API", """\
The transform-chain building blocks (DESIGN.md §4), the runtime that
closes them into an `Optimizer`, and every preset the paper compares.
See [docs/architecture.md](../architecture.md) for how the layers stack.
""", [
        ("repro.optim.transform", [
            "GradientTransform", "chain", "partition", "inject_hyperparams",
            "stateless", "clip_global_norm", "scale_by_schedule",
            "scale_by_learning_rate", "add_decayed_weights", "scale_by_adam",
            "lowrank_project", "as_optimizer", "matrix_optimizer",
        ]),
        ("repro.optim.api", ["get_optimizer", "get_transform"]),
        ("repro.optim.common", ["Optimizer", "Context", "MatrixRule",
                                "default_label_fn", "apply_updates"]),
        ("repro.optim.projected_adam", [
            "ProjectedAdamRule", "dct_adamw", "ldadamw", "galore", "frugal",
            "fira", "projected_adam_transform", "dct_adamw_transform",
        ]),
        ("repro.optim.muon", ["MuonRule", "muon", "muon_transform"]),
        ("repro.optim.trion", ["TrionRule", "trion", "trion_transform"]),
        ("repro.optim.dion", ["DionRule", "dion", "dion_transform"]),
        ("repro.parallel.zero", [
            "ZeroConfig", "resolve", "eligible", "state_specs",
            "sharded_leaf_update",
        ]),
    ]),
    ("telemetry.md", "repro.telemetry — subspace telemetry & control", """\
The in-jit stats layer, the host sink, and the closed-loop controllers
(DESIGN.md §8). See [docs/telemetry.md](../telemetry.md) for the guide.
""", [
        ("repro.telemetry.stats", [
            "SubspaceStats", "StatsCollector", "collect", "active_collector",
            "summarize", "captured_energy", "rank_utilization",
        ]),
        ("repro.telemetry.sink", ["TelemetrySink"]),
        ("repro.telemetry.controllers", [
            "RankAllocatorConfig", "RankAllocator", "RefreshSchedulerConfig",
            "RefreshScheduler", "leaf_inventory", "merge_overrides",
            "migrate_opt_state",
        ]),
        ("repro.telemetry.adaptive", ["AdaptiveOptimizerManager"]),
    ]),
    ("resilience.md", "repro.train — resilient training", """\
The in-jit anomaly guard, the host-side escalation ladder, verified
checkpoints with rollback, the crash-loop-aware supervisor, and the
deterministic fault-injection harness (DESIGN.md §11). See
[docs/resilience.md](../resilience.md) for the guide.
""", [
        ("repro.train.resilience", [
            "TrainingHalted", "all_finite_tree",
            "select_tree", "scale_hyperparam", "ResilienceConfig",
            "Action", "ResilienceManager",
        ]),
        ("repro.train.checkpoint", [
            "CheckpointCorruptError", "CheckpointManager",
        ]),
        ("repro.train.supervisor", ["checkpoint_progress_fn", "supervise"]),
        ("repro.train.chaos", [
            "Fault", "ChaosPlan", "corrupt_file", "strip_chaos_key",
        ]),
    ]),
    ("serve.md", "repro.serve — paged serving engine", """\
The production serving stack (DESIGN.md §12): paged KV cache +
flash-decode kernel + continuous-batching scheduler + sessions. See
[docs/serving.md](../serving.md) for the guide.
""", [
        ("repro.serve.kv_cache", [
            "blocks_for", "OutOfBlocksError", "BlockAllocator",
            "PagedCacheConfig", "PagedKVCache",
        ]),
        ("repro.serve.scheduler", ["SlotLanes", "Scheduler"]),
        ("repro.serve.session", [
            "SamplingParams", "sample_tokens", "fold_keys", "Request",
            "GenerationHandle", "Session",
        ]),
        ("repro.serve.engine", [
            "ServeEngine", "PagedServeEngine", "make_serve_step",
        ]),
        ("repro.kernels.flash_decode", ["flash_decode"]),
    ]),
    ("obs.md", "repro.obs — runtime observability", """\
The host-side metrics registry, span tracer and exporters (DESIGN.md
§13). See [docs/observability.md](../observability.md) for the guide
(metric catalog, overhead contract, artifact formats).
""", [
        ("repro.obs", [
            "enable", "disable", "enabled", "reset",
            "write_prometheus", "write_chrome_trace",
        ]),
        ("repro.obs.metrics", [
            "MetricsRegistry", "Counter", "Gauge", "Histogram",
            "DEFAULT_BUCKETS", "registry",
        ]),
        ("repro.obs.tracing", ["SpanTracer", "tracer"]),
        ("repro.obs.exporters", [
            "prometheus_exposition", "PrometheusExporter",
        ]),
    ]),
    ("tune.md", "repro.tune — kernel autotuner & low-precision compute", """\
The roofline-seeded block-size autotuner and the tuned-block cache every
Pallas kernel resolves `block=None` against, plus the bf16/int8
projection-matmul compute path (DESIGN.md §15). See
[docs/tuning.md](../tuning.md) for the guide (resolution order, cache
file format, jit-staleness rule, error bounds).
""", [
        ("repro.tune.cache", [
            "TuningCache", "tuning_cache", "make_key", "resolve_block",
            "default_platform", "KERNELS",
        ]),
        ("repro.tune.prune", [
            "Candidate", "candidate_blocks", "kernel_costs",
            "roofline_report", "prune", "predicted_bound", "grid_size",
            "describe",
        ]),
        ("repro.tune.autotune", [
            "measure", "tune_kernel", "tune_all", "default_block",
            "REDUCED_SPECS", "FULL_SPECS",
        ]),
        ("repro.kernels.lowp", [
            "COMPUTE_DTYPES", "LOWP_ERROR_BOUNDS", "check_compute_dtype",
            "q8_scale", "quant_rows", "quant_cols", "lowp_matmul",
            "lowp_gather_matmul",
        ]),
        ("repro.roofline.hw", [
            "ArchSpec", "arch_names", "get_arch", "set_arch", "current",
        ]),
    ]),
]


_ADDR = re.compile(r"<(?:function|bound method|built-in [^>]*?|class) "
                   r"([\w.<>]+)(?: at 0x[0-9a-fA-F]+)?>")


def _signature(obj) -> str:
    try:
        sig = str(inspect.signature(obj))
    except (TypeError, ValueError):
        return ""
    # default values that are functions/objects repr with memory addresses;
    # strip them so generation is deterministic (the --check gate diffs)
    return _ADDR.sub(r"\1", sig)


def _doc(obj) -> str:
    d = inspect.getdoc(obj)
    return d.strip() if d else "*(undocumented)*"


def _entry(mod_name: str, name: str) -> str:
    mod = importlib.import_module(mod_name)
    obj = getattr(mod, name)
    kind = "class" if inspect.isclass(obj) else "def"
    sig = _signature(obj)
    lines = [f"### `{mod_name}.{name}`", ""]
    if sig or kind == "class":
        lines += ["```python", f"{kind} {name}{sig}", "```", ""]
    lines += [_doc(obj), ""]
    if inspect.isclass(obj):
        # documented public methods (declared on the class itself)
        for mname, meth in sorted(vars(obj).items()):
            if mname.startswith("_") or not callable(meth):
                continue
            if not getattr(meth, "__doc__", None):
                continue
            lines += [f"**`.{mname}{_signature(meth)}`** — "
                      f"{_doc(meth)}", ""]
    return "\n".join(lines)


def render_page(title: str, intro: str, sections) -> str:
    parts = [HEADER, "", f"# {title}", "", textwrap.dedent(intro).strip(), ""]
    for mod_name, names in sections:
        mod = importlib.import_module(mod_name)
        parts += [f"## `{mod_name}`", ""]
        mdoc = inspect.getdoc(mod)
        if mdoc:
            parts += [mdoc.strip(), ""]
        for name in names:
            parts.append(_entry(mod_name, name))
    return "\n".join(parts).rstrip() + "\n"


def render_index() -> str:
    lines = [HEADER, "", "# API reference", "",
             "Generated from source docstrings by "
             "`scripts/gen_api_docs.py`; the CI docs job fails when these "
             "files drift from the source.", ""]
    for fname, title, _, _ in PAGES:
        lines.append(f"- [{title}]({fname})")
    return "\n".join(lines) + "\n"


def generate() -> dict[str, str]:
    out = {"index.md": render_index()}
    for fname, title, intro, sections in PAGES:
        out[fname] = render_page(title, intro, sections)
    return out


def write(files: dict[str, str]) -> None:
    os.makedirs(API_DIR, exist_ok=True)
    for fname, content in files.items():
        with open(os.path.join(API_DIR, fname), "w") as f:
            f.write(content)
        print(f"wrote docs/api/{fname}")


def check(files: dict[str, str]) -> int:
    stale = []
    for fname, content in files.items():
        path = os.path.join(API_DIR, fname)
        on_disk = open(path).read() if os.path.exists(path) else None
        if on_disk != content:
            stale.append(fname)
    if stale:
        print("docs/api/ is stale (docstrings or signatures changed): "
              + ", ".join(sorted(stale)))
        print("regenerate with: PYTHONPATH=src python scripts/gen_api_docs.py")
        return 1
    print(f"docs/api/ is fresh ({len(files)} files)")
    return 0


_LINK = re.compile(r"\[[^\]]*\]\(([^)\s]+)\)")


def check_links() -> int:
    md_files = [os.path.join(ROOT, "README.md")]
    for dirpath, _, names in os.walk(os.path.join(ROOT, "docs")):
        md_files += [os.path.join(dirpath, n) for n in names
                     if n.endswith(".md")]
    broken = []
    for path in sorted(md_files):
        text = open(path).read()
        for target in _LINK.findall(text):
            if target.startswith(("http://", "https://", "mailto:", "#")):
                continue
            rel = target.split("#", 1)[0]
            if not rel:
                continue
            resolved = os.path.normpath(
                os.path.join(os.path.dirname(path), rel))
            if not os.path.exists(resolved):
                broken.append(f"{os.path.relpath(path, ROOT)} -> {target}")
    if broken:
        print("broken relative links:\n  " + "\n  ".join(broken))
        return 1
    print(f"links OK across {len(md_files)} markdown files")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--check", action="store_true",
                    help="fail if committed docs/api/ drifts from source")
    ap.add_argument("--check-links", action="store_true",
                    help="verify relative links in docs/ and README.md")
    args = ap.parse_args(argv)
    if args.check_links and not args.check:
        return check_links()
    files = generate()
    if args.check:
        rc = check(files)
        if args.check_links:
            rc = rc or check_links()
        return rc
    write(files)
    return 0


if __name__ == "__main__":
    sys.exit(main())
