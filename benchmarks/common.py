"""Shared harness for the paper-table benchmarks.

All benches run CPU-sized stand-ins of the paper's Llama models (the full
sizes are exercised via the dry-run): same family, same optimizer code
paths, deterministic synthetic C4 stand-in data. Reported columns:
final train loss, optimizer-state bytes (the paper's memory claim at
exact ratio), and wall-clock per step (CPU; relative ordering only —
absolute GPU times live in the paper).

``bench_projected_step`` isolates the projected-Adam *optimizer step* itself
at production leaf shape (stacked ``(layers, 4096, 4096)``, rank 256) and
times the fused execution layer against the seed reference path — the
numbers behind ``BENCH_optimizer_step.json`` (DESIGN.md §3).
"""
from __future__ import annotations

import json
import time

import jax
import jax.numpy as jnp

from repro.core.dct import dct2_matrix
from repro.data.synthetic import SyntheticLM
from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.optim.api import get_optimizer
from repro.train.steps import TrainState, make_train_step


def platform_info() -> dict:
    """Host/accelerator identity block stamped into every BENCH json
    (DESIGN.md §15): perf records are only comparable within a platform,
    so the schema carries which backend produced the numbers."""
    import jaxlib
    dev = jax.devices()[0]
    return {
        "jax_backend": jax.default_backend(),
        "device_kind": getattr(dev, "device_kind", "unknown"),
        "device_count": jax.device_count(),
        "jax_version": jax.__version__,
        "jaxlib_version": jaxlib.__version__,
    }


def write_bench_json(path: str, result: dict) -> None:
    """Stamp the ``platform`` block and persist one BENCH record."""
    result.setdefault("platform", platform_info())
    with open(path, "w") as f:
        json.dump(result, f, indent=2)


def tiny_llama(d: int = 128, layers: int = 4, heads: int = 4,
               d_ff: int = 344, vocab: int = 512) -> ModelConfig:
    return ModelConfig(
        name=f"llama-tiny-d{d}", family="dense", d_model=d, n_heads=heads,
        n_kv_heads=heads, d_ff=d_ff, vocab_size=vocab,
        schedule=((("attn",), layers),), param_dtype="float32",
        compute_dtype="float32", remat=False, q_chunk=64, kv_chunk=64)


def state_bytes(opt_state) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(opt_state)
               if hasattr(x, "size"))


def lowrank_state_bytes(opt_state) -> int:
    """Bytes of the low-rank leaves only (excludes the AdamW fallback for
    embeddings/norms, which is identical across the compared optimizers)."""
    total = 0
    for leaf in jax.tree.leaves(opt_state.leaves,
                                is_leaf=lambda x: hasattr(x, "_fields")):
        if type(leaf).__name__ != "FullAdamLeaf":
            total += state_bytes(leaf)
    return total


def shared_basis_bytes(opt_state) -> int:
    return sum(v.size * v.dtype.itemsize for v in opt_state.bases.values())


def train(cfg, optimizer_name: str, steps: int = 40, *, seq: int = 64,
          batch: int = 8, lr: float = 3e-3, seed: int = 0,
          **opt_kw) -> dict:
    """Train `steps` steps; return loss trajectory + memory + timing."""
    opt = get_optimizer(optimizer_name, lr=lr, **opt_kw)
    params = T.init_params(cfg, jax.random.PRNGKey(seed))
    state = TrainState(jnp.zeros((), jnp.int32), params, opt.init(params))
    ds = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=seq,
                     global_batch=batch, seed=seed)
    step_fn = jax.jit(make_train_step(cfg, opt))

    losses = []
    t_steps = []
    for i in range(steps):
        b = ds.batch(jnp.int32(i))
        t0 = time.perf_counter()
        state, metrics = step_fn(state, b)
        jax.block_until_ready(metrics["loss"])
        t_steps.append(time.perf_counter() - t0)
        losses.append(float(metrics["ce"]))
    return {
        "optimizer": optimizer_name,
        "losses": losses,
        "final_loss": sum(losses[-5:]) / 5,
        "opt_state_bytes": state_bytes(state.opt_state),
        "lowrank_state_bytes": lowrank_state_bytes(state.opt_state),
        "shared_basis_bytes": shared_basis_bytes(state.opt_state),
        # skip compile step for timing
        "s_per_step": sum(t_steps[2:]) / max(len(t_steps) - 2, 1),
        "opt_kw": opt_kw,
    }


# ---------------------------------------------------------------------------
# optimizer-step microbench: fused execution layer vs seed reference path
# ---------------------------------------------------------------------------
class _DispatchSpy:
    """Counts fused-execution entry points reached while *tracing* the step.

    The bench drives the full chain API (partition -> lowrank_project ->
    rule), so if a refactor breaks dispatch — fused kernels no longer
    reached through ``partition`` — the counters stay zero and
    ``check`` raises, failing the CI bench job."""

    def __init__(self):
        self.counts = {"select_and_project": 0, "kernel": 0,
                       "newton_schulz": 0}
        self.ns_shapes = []

    def __enter__(self):
        from repro.core import fused_step
        from repro.kernels import ops as kops

        self._fs, self._kops = fused_step, kops
        self._orig_sp = fused_step.select_and_project
        self._orig_op = kops.dct_project_op
        self._orig_ns = kops.newton_schulz_op

        def sp(*a, **kw):
            self.counts["select_and_project"] += 1
            return self._orig_sp(*a, **kw)

        def op(*a, **kw):
            self.counts["kernel"] += 1
            return self._orig_op(*a, **kw)

        def ns(x, **kw):
            self.counts["newton_schulz"] += 1
            self.ns_shapes.append(tuple(x.shape))
            return self._orig_ns(x, **kw)

        fused_step.select_and_project = sp
        kops.dct_project_op = op
        kops.newton_schulz_op = ns
        return self

    def __exit__(self, *exc):
        self._fs.select_and_project = self._orig_sp
        self._kops.dct_project_op = self._orig_op
        self._kops.newton_schulz_op = self._orig_ns
        return False

    def check(self, mode: str):
        if mode != "off" and not self.counts["select_and_project"]:
            raise RuntimeError(
                f"fused mode {mode!r} never reached select_and_project "
                f"through the chain API — dispatch regression")
        if mode == "on" and not self.counts["kernel"]:
            raise RuntimeError(
                "fused mode 'on' never reached the Pallas dct_project "
                "kernel through the chain API — dispatch regression")

    def check_momentum(self, mode: str, rank, *, expect_select: bool = True):
        """Gate for the NS families: the one-pass select must be reached
        in any fused mode (when a subspace rank is set), and the Pallas
        NS kernel under mode "on" — on rank-sized blocks only.
        ``expect_select=False`` for dion, which has no column selection."""
        if mode != "off" and rank is not None and expect_select \
                and not self.counts["select_and_project"]:
            raise RuntimeError(
                f"fused mode {mode!r} never reached select_and_project "
                f"through the chain API — dispatch regression")
        if mode == "on":
            if not self.counts["newton_schulz"]:
                raise RuntimeError(
                    "fused mode 'on' never reached the Pallas newton_schulz "
                    "kernel through the chain API — dispatch regression")
            if rank is not None:
                for shape in self.ns_shapes:
                    if min(shape[-2:]) != rank:
                        raise RuntimeError(
                            f"subspace NS ran on {shape}, not a "
                            f"rank-{rank} block — fusion regression")


def compile_opt_step(rule, shape, *, seed: int = 0, telemetry: bool = False,
                     guard: bool = False):
    """Compile one full ``optimizer.update`` on a stacked lowrank leaf
    through the chain API (partition -> lowrank_project(rule)), under the
    dispatch spy. ``telemetry=True`` installs a stats collector around the
    traced update (the SubspaceStats pytree becomes a jit output) —
    exactly what enabling telemetry costs, benchmarks/telemetry_overhead.py
    gates it. ``guard=True`` appends the in-jit anomaly guard tail from
    ``make_train_step(..., guard=True)`` — ``all_finite_tree`` over the
    produced updates plus the ``select_tree`` commit/reject point on the
    optimizer state — exactly what ``--resilient`` costs per step,
    benchmarks/resilience_overhead.py gates it.
    Returns (compiled, inputs, fresh_state_fn, spy, peak_bytes)."""
    from repro.optim.transform import matrix_optimizer

    params = {"w": jnp.zeros(shape, jnp.float32)}
    grads = {"w": jax.random.normal(jax.random.PRNGKey(seed), shape,
                                    jnp.float32)}
    opt = matrix_optimizer(rule, 1e-3)
    state = opt.init(params)

    if telemetry:
        from repro.telemetry.stats import collect

        def update(grads, state, params):
            with collect() as col:
                d, new_state = opt.update(grads, state, params)
            return d, new_state, col.tree()
    else:
        update = opt.update

    if guard:
        from repro.train.resilience import all_finite_tree, select_tree

        inner = update

        def update(grads, state, params):
            out = inner(grads, state, params)
            d, new_state = out[0], out[1]
            flag = all_finite_tree(d)
            new_state = select_tree(flag, new_state, state)
            return (d, new_state, flag) + tuple(out[2:])

    with _DispatchSpy() as spy:
        compiled = jax.jit(update, donate_argnums=1).lower(
            grads, state, params).compile()
    mem = compiled.memory_analysis()
    peak = None
    if mem is not None:
        peak = int(mem.argument_size_in_bytes + mem.output_size_in_bytes
                   + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    return compiled, (grads, params), (lambda: opt.init(params)), spy, peak


def _time_opt_step(rule, shape, *, steps: int, warmup: int, seed: int = 0,
                   telemetry: bool = False):
    """Wall-time per full ``optimizer.update`` (see ``compile_opt_step``)."""
    compiled, (grads, params), init, spy, peak = compile_opt_step(
        rule, shape, seed=seed, telemetry=telemetry)
    state = init()
    times = []
    for _ in range(warmup + steps):
        tic = time.perf_counter()
        out = compiled(grads, state, params)
        state = out[1]
        jax.block_until_ready(out[0])
        times.append(time.perf_counter() - tic)
    timed = sorted(times[warmup:])
    return {
        "s_per_step": sum(times[warmup:]) / max(steps, 1),
        "s_per_step_median": timed[len(timed) // 2],
        "peak_live_bytes": peak,
        "dispatch": dict(spy.counts),
    }, spy


def bench_projected_step(*, layers: int = 2, dim: int = 4096, rank: int = 256,
                         steps: int = 3, warmup: int = 1,
                         out_path: str | None = "BENCH_optimizer_step.json",
                         ) -> dict:
    """Fused vs reference DCT-AdamW step on a stacked (layers, dim, dim)
    leaf, driven end-to-end through the chain API. The fused mode is the
    host-appropriate one: Pallas kernels on TPU, the Makhoul fft dataflow
    elsewhere (DESIGN.md §3). Raises if the fused execution layer is no
    longer reached through ``partition`` (dispatch regression)."""
    import dataclasses

    from repro.kernels import ops as kops
    from repro.optim.projected_adam import ProjectedAdamRule

    shape = (layers, dim, dim)
    base = ProjectedAdamRule(rank=rank, projector="dct", residual="ef",
                             ef_dtype="q8", fused="off")
    fused_mode = "on" if kops.on_tpu() else "fft"
    result = {
        "bench": "optimizer_step",
        "api": "chain",
        "leaf_shape": list(shape),
        "rank": rank,
        "steps_timed": steps,
        "backend": jax.default_backend(),
        "modes": {},
        "dispatch_gate": basis_dispatch_gate(),
    }
    for label, mode in (("reference", "off"), ("fused", fused_mode)):
        rule = dataclasses.replace(base, fused=mode)
        row, spy = _time_opt_step(rule, shape, steps=steps, warmup=warmup)
        spy.check(mode)
        row["fused_mode"] = mode
        result["modes"][label] = row
        print(f"[optimizer_step] {label:10s} ({mode:3s}) "
              f"{row['s_per_step'] * 1e3:9.1f} ms/step "
              f"peak={row['peak_live_bytes'] / 1e9 if row['peak_live_bytes'] else 0:.2f} GB")
    ref = result["modes"]["reference"]["s_per_step"]
    fus = result["modes"]["fused"]["s_per_step"]
    result["speedup_fused_vs_reference"] = ref / fus if fus > 0 else None
    print(f"[optimizer_step] speedup fused/reference = "
          f"{result['speedup_fused_vs_reference']:.2f}x")
    result["momentum"] = bench_momentum_step(layers=layers, dim=dim,
                                             rank=rank, steps=steps,
                                             warmup=warmup)
    result["momentum_dispatch_gate"] = momentum_dispatch_gate()
    if out_path:
        write_bench_json(out_path, result)
        print(f"[optimizer_step] wrote {out_path}")
    return result


def bench_momentum_step(*, layers: int = 2, dim: int = 4096, rank: int = 256,
                        steps: int = 3, warmup: int = 1) -> dict:
    """Subspace-fused muon/trion vs their seed paths (DESIGN.md §14).

    muon's seed path is *full-space* Newton–Schulz on the (dim, dim)
    momentum; the fused column projects into the selected rank-``rank``
    subspace first, so NS runs on (dim, rank) blocks — the tentpole
    speedup this record pins (>= 1.5x at the production shape). trion's
    seed is already subspace, so its column isolates the one-pass
    select + shared-gather fusion alone."""
    from repro.kernels import ops as kops
    from repro.optim.muon import MuonRule
    from repro.optim.trion import TrionRule

    shape = (layers, dim, dim)
    fused_mode = "on" if kops.on_tpu() else "fft"
    out = {"leaf_shape": list(shape), "rank": rank,
           "fused_mode": fused_mode, "families": {}}
    cases = (
        ("muon", MuonRule(fused="off"),
         MuonRule(rank=rank, fused=fused_mode)),
        ("trion", TrionRule(rank=rank, fused="off"),
         TrionRule(rank=rank, fused=fused_mode)),
    )
    for name, seed_rule, fused_rule in cases:
        row_seed, _ = _time_opt_step(seed_rule, shape, steps=steps,
                                     warmup=warmup)
        row_fused, spy = _time_opt_step(fused_rule, shape, steps=steps,
                                        warmup=warmup)
        spy.check_momentum(fused_mode, rank)
        sp = (row_seed["s_per_step"] / row_fused["s_per_step"]
              if row_fused["s_per_step"] > 0 else None)
        out["families"][name] = {"seed": row_seed, "fused": row_fused,
                                 "speedup_fused_vs_seed": sp}
        print(f"[optimizer_step] {name:10s} seed "
              f"{row_seed['s_per_step'] * 1e3:9.1f} ms/step  fused "
              f"{row_fused['s_per_step'] * 1e3:9.1f} ms/step  "
              f"speedup {sp:.2f}x")
    return out


def momentum_dispatch_gate(shape=(2, 128, 128), rank: int = 16) -> dict:
    """Hard-fail if muon/trion/dion stop reaching the fused kernels
    through the chain API under mode "on" — and if the Newton–Schulz
    they reach is no longer on rank-sized blocks (the tentpole shape
    pin; tests/test_subspace_fusion.py holds the same line in-tree)."""
    from repro.optim.dion import DionRule
    from repro.optim.muon import MuonRule
    from repro.optim.trion import TrionRule

    counts = {}
    for name, rule, expect_select in (
            ("muon", MuonRule(rank=rank, fused="on"), True),
            ("trion", TrionRule(rank=rank, fused="on"), True),
            ("dion", DionRule(rank=rank, fused="on"), False)):
        _, _, _, spy, _ = compile_opt_step(rule, shape)
        try:
            spy.check_momentum("on", rank, expect_select=expect_select)
        except RuntimeError as e:
            raise RuntimeError(
                f"momentum family {name!r} no longer reaches the fused "
                f"kernel path: {e}") from e
        counts[name] = dict(spy.counts)
        print(f"[optimizer_step] dispatch gate {name:10s} "
              f"newton_schulz={spy.counts['newton_schulz']} "
              f"select_and_project={spy.counts['select_and_project']}")
    return counts


def basis_dispatch_gate(kinds=("dct", "dst", "hadamard"),
                        shape=(2, 128, 128), rank: int = 16) -> dict:
    """Hard-fail if any predefined-basis kind stops reaching the fused
    kernel path through the chain API.

    The projection kernel is parameterized by the basis matrix (DESIGN.md
    §10), so every registered backend must dispatch to the same
    ``pallas_call`` under fused mode "on". Compiles one tiny step per kind
    under the spy; a zero kernel counter raises (the CI bench job runs
    this via ``bench_projected_step``). Returns the per-kind counters for
    the JSON record.
    """
    from repro.optim.projected_adam import ProjectedAdamRule

    counts = {}
    for kind in kinds:
        rule = ProjectedAdamRule(rank=rank, projector=kind, residual="ef",
                                 ef_dtype="q8", fused="on",
                                 needs_shared_basis=True)
        _, _, _, spy, _ = compile_opt_step(rule, shape)
        try:
            spy.check("on")
        except RuntimeError as e:
            raise RuntimeError(
                f"basis kind {kind!r} no longer reaches the fused kernel "
                f"path: {e}") from e
        counts[kind] = dict(spy.counts)
        print(f"[optimizer_step] dispatch gate {kind:10s} "
              f"kernel={spy.counts['kernel']} "
              f"select_and_project={spy.counts['select_and_project']}")
    return counts


def fmt_row(name: str, r: dict, extra: str = "") -> str:
    return (f"{name:28s} loss={r['final_loss']:.4f} "
            f"state={r['opt_state_bytes'] / 1e6:8.2f}MB "
            f"lowrank={r['lowrank_state_bytes'] / 1e6:8.2f}MB "
            f"basis={r['shared_basis_bytes'] / 1e6:6.2f}MB "
            f"{r['s_per_step'] * 1e3:7.1f}ms/step {extra}")
