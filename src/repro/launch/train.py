"""Training driver.

  python -m repro.launch.train --arch llama-350m --optimizer trion \
      --rank 256 --steps 300 --seq-len 512 --batch 64 \
      --ckpt-dir /tmp/ckpt [--supervise] [--smoke]

On a TPU host this binary runs once per host; with ``JAX_PLATFORMS=cpu``
it runs the same path with the Pallas kernels in interpret mode: config ->
data pipeline -> jit'd train_step with the paper's optimizer -> checkpoint
manager -> supervisor restarts. ``--supervise`` wraps the run in the
restart supervisor (crash -> resume from the latest checkpoint with
backoff); the parent touches no JAX backend, so the child can take the
chip.

:func:`train` is the run itself and returns what it did (final state,
per-step metrics, the jitted step); :func:`main` is the command line
around it.
"""
from __future__ import annotations

import argparse
import dataclasses
import sys
from typing import Any, Callable

import jax
import jax.numpy as jnp

# the presets built on ProjectedAdamRule — the ones the adaptive
# controllers apply to
PROJECTED_ADAM_FAMILY = ("dct_adamw", "ldadamw", "galore", "frugal", "fira")
# presets with a fused-step dispatch field (DESIGN.md §3/§14): the
# projected-Adam family plus the momentum-orthogonalization rules
FUSED_FAMILY = PROJECTED_ADAM_FAMILY + ("muon", "trion", "dion")
# presets whose rule is unconditionally zero_shardable (DESIGN.md §9/§14);
# galore/frugal join when --basis swaps their dense svd projector for a
# registered basis backend
ZERO_ALWAYS = ("dct_adamw", "muon", "trion", "dion")


def build(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama-350m")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-sized)")
    ap.add_argument("--optimizer", default="trion")
    ap.add_argument("--rank", type=int, default=None,
                    help="subspace rank for the low-rank families "
                         "(default 128); for muon the default is full-space "
                         "Newton-Schulz and --rank opts into subspace "
                         "orthogonalization (DESIGN.md §14)")
    ap.add_argument("--lr", type=float, default=0.01)
    ap.add_argument("--weight-decay", type=float, default=0.01)
    ap.add_argument("--fused", default=None,
                    choices=["auto", "on", "fft", "off"],
                    help="fused-step dispatch for the projected-Adam family "
                         "and muon/trion/dion (Pallas Newton-Schulz on the "
                         "rank-sized subspace factor)")
    ap.add_argument("--basis", default=None,
                    choices=["dct", "dst", "hadamard", "randortho"],
                    help="predefined orthogonal basis backend for "
                         "dct_adamw (or the projector for galore/frugal/"
                         "fira) — the whole fused/ZeRO/telemetry stack is "
                         "basis-agnostic (docs/transforms.md)")
    ap.add_argument("--compute-dtype", default=None,
                    choices=["fp32", "bf16", "int8"],
                    help="projection-matmul precision for dct_adamw "
                         "(DESIGN.md §15): int8 = quantized operands with "
                         "exact int32 accumulation; error bounds gated in "
                         "benchmarks/projection_errors.py")
    ap.add_argument("--tune-cache", default=None, metavar="PATH",
                    help="autotuned kernel block-size cache JSON "
                         "(repro.tune, docs/tuning.md); loaded into the "
                         "process-wide TuningCache before the step jits so "
                         "block=None kernel launches resolve tuned blocks")
    ap.add_argument("--zero", default="off", choices=["off", "1"],
                    help="ZeRO-1 partitioning of the low-rank optimizer "
                         "state across the data axes; the fused step runs "
                         "per-shard inside shard_map and updates are "
                         "all-gathered (dct_adamw/muon/trion/dion, or "
                         "galore/frugal with --basis; >1 device; see "
                         "docs/distributed.md)")
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--seq-len", type=int, default=512)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--supervise", action="store_true")
    # telemetry + adaptive control (DESIGN.md §8)
    ap.add_argument("--telemetry", default="off",
                    choices=["off", "jsonl", "csv"],
                    help="collect per-leaf SubspaceStats in-jit and stream "
                         "step-bucketed rows to --telemetry-path")
    ap.add_argument("--telemetry-path", default=None,
                    help="output file (default telemetry.<fmt> next to "
                         "--ckpt-dir, else ./telemetry.<fmt>)")
    ap.add_argument("--telemetry-every", type=int, default=10,
                    help="steps aggregated per telemetry row")
    ap.add_argument("--adaptive-rank", action="store_true",
                    help="closed-loop per-layer rank reallocation from "
                         "captured energy (projected-Adam family only)")
    ap.add_argument("--adaptive-refresh", action="store_true",
                    help="closed-loop per-layer refresh-interval control "
                         "from index-overlap drift")
    ap.add_argument("--control-every", type=int, default=50,
                    help="steps between controller decisions")
    # runtime observability (DESIGN.md §13, docs/observability.md)
    ap.add_argument("--obs-dir", default=None, metavar="DIR",
                    help="enable the obs layer (host-side metrics + phase "
                         "spans) and write DIR/metrics.prom + "
                         "DIR/trace.json at the end of the run (halted "
                         "runs included)")
    ap.add_argument("--obs-sync-every", type=int, default=0,
                    help="with --obs-dir: every N steps also "
                         "block_until_ready the full train state into "
                         "train_full_sync_seconds (0 = off; see the "
                         "timing note in train/loop.py)")
    # resilience + fault injection (DESIGN.md §11, docs/resilience.md)
    ap.add_argument("--resilient", action="store_true",
                    help="arm the in-jit anomaly guard and the host-side "
                         "escalation ladder (skip -> rollback -> rollback+"
                         "LR-cut -> halt); builds the optimizer with the "
                         "lr_scale injected hyperparameter")
    ap.add_argument("--max-skips", type=int, default=2,
                    help="consecutive non-finite steps skipped before the "
                         "ladder escalates to a rollback")
    ap.add_argument("--max-rollbacks", type=int, default=3,
                    help="rollbacks before the run halts (exit code 86)")
    ap.add_argument("--lr-cut", type=float, default=0.5,
                    help="LR factor applied on the 2nd+ rollback")
    ap.add_argument("--chaos", default=None, metavar="PLAN.json",
                    help="deterministic fault-injection plan "
                         "(train/chaos.py; schema in docs/resilience.md)")
    return ap.parse_args(argv)


@dataclasses.dataclass
class TrainRun:
    """What :func:`train` did: the final state, one scalar-metrics dict per
    committed step, the jitted step and the batch function it ran on, and
    the concrete fused dispatch mode of the optimizer (None for presets
    without one)."""
    state: Any
    history: list[dict]
    step_fn: Callable
    batch_fn: Callable
    fused: str | None


def main(argv=None) -> int:
    args = build(argv)
    if args.supervise:
        from repro.train.supervisor import checkpoint_progress_fn, supervise
        child = [sys.executable, "-m", "repro.launch.train"] + [
            a for a in (argv or sys.argv[1:]) if a != "--supervise"]
        # progress-aware restarts: the budget resets while checkpoints
        # advance, and a crash loop (no progress) halts early
        progress_fn = (checkpoint_progress_fn(args.ckpt_dir)
                       if args.ckpt_dir else None)
        return supervise(child, progress_fn=progress_fn)

    from repro.launch.cache import enable_compile_cache
    from repro.train.resilience import HALT_EXIT_CODE, TrainingHalted

    enable_compile_cache()
    try:
        run = train(args)
    except TrainingHalted as e:
        # rung 4: deterministic divergence — the diagnostic dump is already
        # on disk; the exit code tells the supervisor not to restart
        print(f"[train] halted: {e}")
        return HALT_EXIT_CODE
    if run.history:
        print(f"[train] done at step {int(run.state.step)}: "
              f"loss {float(run.history[-1]['loss']):.4f}")
    return 0


def train(args: argparse.Namespace) -> TrainRun:
    """Run the training job ``args`` (from :func:`build`) in this process.
    Raises ``SystemExit`` on an invalid flag combination and
    ``TrainingHalted`` when the resilience ladder halts."""
    from repro.configs.registry import get_config
    from repro.core import fused_step
    from repro.parallel import sharding as sh
    from repro.data.synthetic import make_batch_fn
    from repro.optim.api import get_optimizer
    from repro.train.loop import Trainer
    from repro.train.schedule import cosine_warmup
    from repro.train.steps import init_state, make_train_step

    if args.tune_cache:
        # must happen before the first jit: block=None resolution runs at
        # trace time, and jit caches retraces only on shape/static changes
        from repro.tune import tuning_cache
        tuning_cache().load(args.tune_cache)
        print(f"[train] loaded tuning cache {args.tune_cache} "
              f"({len(tuning_cache())} entries)")

    cfg = get_config(args.arch, smoke=args.smoke)
    lr = cosine_warmup(args.lr, args.warmup, args.steps)
    chaos_plan = None
    if args.chaos is not None:
        from repro.train.chaos import ChaosPlan
        chaos_plan = ChaosPlan.load(args.chaos)
        print(f"[train] chaos plan armed: {len(chaos_plan.faults)} faults "
              f"from {args.chaos}")
    resilience = None
    if args.resilient:
        from repro.train.resilience import (ResilienceConfig,
                                            ResilienceManager)
        resilience = ResilienceManager(ResilienceConfig(
            max_skips=args.max_skips, max_rollbacks=args.max_rollbacks,
            lr_cut=args.lr_cut))
    opt_kw = {"weight_decay": args.weight_decay}
    if args.resilient:
        # the ladder's LR-cut rung needs the injected lr_scale leaf
        opt_kw["lr_scale"] = True
    if args.optimizer == "muon":
        # muon defaults to full-space Newton-Schulz; an explicit --rank
        # opts into subspace orthogonalization (DESIGN.md §14)
        if args.rank is not None:
            opt_kw["rank"] = args.rank
    elif args.optimizer != "adamw":
        opt_kw["rank"] = args.rank if args.rank is not None else 128
    if args.fused is not None:
        if args.optimizer not in FUSED_FAMILY:
            raise SystemExit(f"--fused applies to "
                             f"{'/'.join(FUSED_FAMILY)}, "
                             f"not {args.optimizer!r}")
        opt_kw["fused"] = args.fused
    if args.compute_dtype is not None:
        if args.optimizer != "dct_adamw":
            # only the dct_adamw preset exposes the rule's compute_dtype
            # field; the other family presets pin fp32
            raise SystemExit("--compute-dtype applies to dct_adamw, not "
                             f"{args.optimizer!r}")
        if args.compute_dtype != "fp32":
            # the lowp mirror only exists on the fused paths; fail at the
            # CLI instead of deep inside the first trace (fused="auto"
            # resolves to the reference path off-TPU)
            if fused_step.resolve(args.fused or "auto") == "off":
                raise SystemExit(
                    f"--compute-dtype {args.compute_dtype} requires a fused "
                    "dispatch mode; pass --fused on or --fused fft "
                    "(the default --fused auto resolves to the reference "
                    "path on this backend)")
        opt_kw["compute_dtype"] = args.compute_dtype
    if args.basis is not None:
        if args.optimizer == "dct_adamw":
            opt_kw["basis"] = args.basis
        elif args.optimizer in ("galore", "frugal", "fira"):
            opt_kw["projector"] = args.basis
        else:
            # ldadamw is defined by its power-iteration projector; the
            # non-family presets have no predefined-basis plug point
            raise SystemExit("--basis applies to dct_adamw/galore/frugal/"
                             f"fira, not {args.optimizer!r}")
    adaptive = args.adaptive_rank or args.adaptive_refresh
    zero_cfg = None
    mesh = None
    if args.zero != "off":
        zero_ok = (args.optimizer in ZERO_ALWAYS
                   or (args.optimizer in ("galore", "frugal")
                       and args.basis is not None))
        if not zero_ok:
            # every remaining combo keeps dense projector state
            # (power/svd) whose refresh is not row-decomposable, or (fira)
            # feeds psum'd norms into the update arithmetic — it would
            # silently keep every leaf replicated, so fail loudly instead
            raise SystemExit(
                "--zero needs a ZeRO-shardable optimizer: "
                f"{'/'.join(ZERO_ALWAYS)} (always), or galore/frugal with "
                "--basis <dct|dst|hadamard|randortho>; "
                f"{args.optimizer!r} would silently stay replicated")
        if adaptive:
            # a controller rebuild re-inits + migrates sharded state; that
            # composition is untested — fail loudly rather than subtly
            raise SystemExit("--zero cannot be combined with "
                             "--adaptive-rank/--adaptive-refresh yet")
        from repro.parallel.zero import ZeroConfig
        zero_cfg = ZeroConfig(mode=args.zero)
        opt_kw["zero"] = zero_cfg
        if jax.device_count() > 1:
            # data parallelism over every visible device (the pure_dp
            # layout): the batch splits over the "data" axis, parameters
            # replicate, the optimizer state partitions. Without --zero
            # the run keeps one device: outside ZeRO's shard_map the Pallas
            # kernels would sit in a program partitioned over the mesh,
            # which Mosaic refuses.
            from repro.launch.mesh import make_mesh
            if args.batch % jax.device_count():
                raise SystemExit(f"--batch {args.batch} does not split "
                                 f"over {jax.device_count()} devices")
            mesh = make_mesh((jax.device_count(),), ("data",))
        else:
            print("[train] --zero requested with a single visible device; "
                  "state stays replicated (on CPU, set XLA_FLAGS="
                  "--xla_force_host_platform_device_count=N to shard)")
    telemetry_on = args.telemetry != "off" or adaptive
    if adaptive and args.optimizer not in PROJECTED_ADAM_FAMILY:
        raise SystemExit("--adaptive-rank/--adaptive-refresh apply to the "
                         f"projected-Adam family only, not "
                         f"{args.optimizer!r}")
    if args.adaptive_refresh and args.optimizer != "dct_adamw":
        # drift is measured from index overlap, which only index-based
        # projectors emit (basis projectors report the -1 sentinel and the
        # scheduler would be silently inert) — the CLI presets for the
        # other family members use power/svd projectors
        raise SystemExit("--adaptive-refresh needs an index-based projector"
                         " (dct); use --optimizer dct_adamw")

    def make_optimizer(overrides=None):
        kw = dict(opt_kw)
        if overrides:
            kw["overrides"] = overrides
        return get_optimizer(args.optimizer, lr=lr, **kw)

    def make_step(opt):
        return jax.jit(make_train_step(cfg, opt, telemetry=telemetry_on,
                                       guard=args.resilient,
                                       chaos=chaos_plan),
                       donate_argnums=0)

    batch_fn = make_batch_fn(cfg, args.seq_len, args.batch, seed=args.seed)

    sink = None
    if args.telemetry != "off":
        from repro.telemetry.sink import TelemetrySink
        path = args.telemetry_path or (
            f"{args.ckpt_dir}/telemetry.{args.telemetry}" if args.ckpt_dir
            else f"telemetry.{args.telemetry}")
        # append exactly when this run will resume from a checkpoint: a
        # preemption restart must not truncate the pre-preemption
        # telemetry, while a fresh run must not inherit a stale file
        resuming = False
        if args.ckpt_dir:
            from repro.train.checkpoint import CheckpointManager
            resuming = CheckpointManager(
                args.ckpt_dir).latest_step() is not None
        sink = TelemetrySink(path, fmt=args.telemetry,
                             every=args.telemetry_every, append=resuming)

    obs_mod = None
    if args.obs_dir:
        from repro import obs as obs_mod
        obs_mod.enable()

    trainer_kw = dict(ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
                      log_every=args.log_every,
                      log_metrics=sink.log_metrics if sink else None,
                      resilience=resilience,
                      sync_sample_every=args.obs_sync_every)
    if chaos_plan is not None and args.ckpt_dir:
        trainer_kw["ckpt_fault_hook"] = chaos_plan.bind_checkpoint_dir(
            args.ckpt_dir)

    def trainer_batch_fn(s):
        return batch_fn(jnp.int32(s))
    if mesh is not None:
        # data parallelism: each device takes its rows of the batch; an
        # unplaced batch would sit wholly on device 0
        from jax.sharding import NamedSharding, PartitionSpec

        rows = NamedSharding(mesh, PartitionSpec("data"))
        unplaced_batch_fn = trainer_batch_fn

        def trainer_batch_fn(s):
            return jax.device_put(unplaced_batch_fn(s), rows)
    if chaos_plan is not None:
        trainer_batch_fn = chaos_plan.wrap_batch_fn(trainer_batch_fn)

    if adaptive:
        from repro.telemetry.adaptive import AdaptiveOptimizerManager
        from repro.telemetry.controllers import (
            RankAllocator, RankAllocatorConfig, RefreshScheduler,
            RefreshSchedulerConfig, leaf_inventory)
        from repro.models import transformer as T

        params_sds = jax.eval_shape(
            lambda: T.init_params(cfg, jax.random.PRNGKey(args.seed)))
        leaves = leaf_inventory(params_sds)
        allocator = scheduler = None
        if args.adaptive_rank:
            allocator = RankAllocator(
                RankAllocatorConfig(base_rank=args.rank,
                                    decide_every=args.control_every),
                leaves)
        if args.adaptive_refresh:
            # the ladder is seeded from the preset's refresh cadence (the
            # dct_adamw CLI preset runs T_u=1) so a stretch doubles the
            # configured interval rather than resetting it
            scheduler = RefreshScheduler(
                RefreshSchedulerConfig(base_interval=1,
                                       decide_every=args.control_every,
                                       cooldown=args.control_every),
                leaves)
        manager = AdaptiveOptimizerManager(
            make_optimizer=make_optimizer, make_step=make_step,
            make_train_state=lambda opt: init_state(
                cfg, opt, jax.random.PRNGKey(args.seed)),
            rank_allocator=allocator, refresh_scheduler=scheduler)
        trainer = Trainer(train_step=manager.step,
                          init_state_fn=manager.init_state,
                          batch_fn=trainer_batch_fn,
                          control_hook=manager.control_hook,
                          extra_state=manager, **trainer_kw)
    else:
        opt = make_optimizer()
        step_fn = make_step(opt)

        def init_fn():
            return init_state(cfg, opt, jax.random.PRNGKey(args.seed))

        if mesh is not None:
            # ZeRO-1: derive the partitioned placement (moments/EF split
            # over the data axis) and install it at init; the Trainer also
            # uses it to re-partition on checkpoint restore, so the DP
            # width may change across restarts (docs/distributed.md)
            from repro.train.steps import TrainState
            from jax.sharding import PartitionSpec as P

            state_sds = jax.eval_shape(init_fn)
            with sh.use_policy(layout="pure_dp"):
                p_specs = sh.params_specs(state_sds.params, mesh)
                o_specs = sh.opt_state_specs(state_sds.opt_state,
                                             state_sds.params, p_specs,
                                             zero=zero_cfg, mesh=mesh)
            shardings = sh.named_shardings(
                TrainState(P(), p_specs, o_specs), mesh)
            trainer_kw["state_shardings"] = shardings
            base_init = init_fn
            init_fn = lambda: jax.device_put(base_init(), shardings)  # noqa: E731

        trainer = Trainer(
            train_step=step_fn, init_state_fn=init_fn,
            batch_fn=trainer_batch_fn, **trainer_kw)

    try:
        if mesh is not None:
            with jax.set_mesh(mesh), sh.use_policy(layout="pure_dp"):
                state = trainer.run(total_steps=args.steps)
        else:
            state = trainer.run(total_steps=args.steps)
    finally:
        if sink is not None:
            sink.close()
        if obs_mod is not None:
            import os
            os.makedirs(args.obs_dir, exist_ok=True)
            prom = obs_mod.write_prometheus(
                os.path.join(args.obs_dir, "metrics.prom"))
            trace = obs_mod.write_chrome_trace(
                os.path.join(args.obs_dir, "trace.json"))
            print(f"[train] obs artifacts: {prom}, {trace}")
    if adaptive and args.adaptive_rank:
        print(f"[train] final rank allocation: {allocator.alloc}")
    fused = (fused_step.resolve(args.fused or "auto")
             if args.optimizer in FUSED_FAMILY else None)
    return TrainRun(state=state, history=trainer.metrics_history,
                    step_fn=trainer.train_step, batch_fn=trainer_batch_fn,
                    fused=fused)


if __name__ == "__main__":
    raise SystemExit(main())
