"""Training loop with checkpoint/restart, preemption handling, and elastic
restore — the single-process core that ``launch/train.py --supervise``
wraps with a restart supervisor for node-failure tolerance.

Observability and control plug in through three hooks (DESIGN.md §8):

``log_metrics(record)``
    Structured per-step metrics: ``record`` is ``{"step": int,
    "s_per_step": float, **metrics}`` with metric values still device-side
    (consumers decide when to sync). ``s_per_step`` is the wall time of
    the whole step body — data wait + dispatch + blocking on the loss —
    see the timing note inside :meth:`Trainer.run` for exactly what that
    does and does not measure. The trainer's own console line is built
    from the same records by an internal default formatter, so plain
    ``print`` and the telemetry sink are both just consumers of this hook.
``control_hook(step, state, metrics) -> state | None``
    Closed-loop controllers (adaptive rank/refresh): called every step;
    a non-None return replaces the train state (the hook owner also swaps
    its jitted step function — pass a delegating ``train_step``).
``extra_state``
    Object with ``state_dict() -> dict`` / ``load_state_dict(dict)``:
    JSON-serializable controller state checkpointed in the manifest and
    restored *before* ``init_state_fn`` runs, because restored controller
    state determines the optimizer-state shapes of the restore target.

Distributed state (DESIGN.md §9): checkpoints are saved mesh-agnostic
(gathered host arrays), so a ZeRO-partitioned run hands the Trainer its
``state_shardings`` (a TrainState-shaped tree of NamedShardings for the
*current* mesh) and restore re-partitions onto it — the DP width may
change between the save and the resume (elastic restart / resharding on
topology change).
"""
from __future__ import annotations

import contextlib
import os
import signal
import time
from typing import Any, Callable

import jax

from repro import obs
from repro.data.pipeline import DataPipeline

from .checkpoint import CheckpointManager
from .resilience import TrainingHalted
from .steps import TrainState


def _train_metrics():
    """Training-loop instruments on the process-wide registry (no-ops
    until ``obs.enable()``). Catalog: docs/observability.md."""
    r = obs.registry()
    return {
        "data_wait": r.histogram(
            "train_data_wait_seconds",
            "blocking on the data pipeline for the step's batch"),
        "dispatch": r.histogram(
            "train_dispatch_seconds",
            "train_step call: trace/dispatch only, returns before "
            "the device finishes"),
        "host_sync": r.histogram(
            "train_host_sync_seconds",
            "blocking on the loss scalar after dispatch"),
        "step_wall": r.histogram(
            "train_step_seconds",
            "full step body wall time (data wait + dispatch + loss sync)"),
        "full_sync": r.histogram(
            "train_full_sync_seconds",
            "sampled data-ready -> whole-TrainState-ready wall time "
            "(only when sync_sample_every > 0)"),
        "steps": r.counter("train_steps_total",
                           "step outcomes", labels=("outcome",)),
    }


class Trainer:
    def __init__(self, *, train_step, init_state_fn, batch_fn,
                 ckpt_dir: str | None = None, ckpt_every: int = 50,
                 keep: int = 3, log_every: int = 10,
                 log_fn: Callable[[str], None] = print,
                 log_metrics: Callable[[dict], None] | None = None,
                 control_hook=None, extra_state=None,
                 state_shardings=None, resilience=None,
                 ckpt_fault_hook=None, sync_sample_every: int = 0):
        self.train_step = train_step
        self.init_state_fn = init_state_fn
        self.batch_fn = batch_fn
        self.ckpt = (CheckpointManager(ckpt_dir, keep,
                                       fault_hook=ckpt_fault_hook,
                                       log=log_fn)
                     if ckpt_dir else None)
        self.ckpt_every = ckpt_every
        self.log_every = log_every
        self.log = log_fn
        self.log_metrics = log_metrics
        self.control_hook = control_hook
        self.extra_state = extra_state
        self.state_shardings = state_shardings
        self.resilience = resilience
        # 0 disables the sampled full-state sync; K > 0 blocks on the
        # whole TrainState every K steps to measure true per-step compute
        # (s_per_step alone can't — see the timing note in run())
        self.sync_sample_every = sync_sample_every
        self._m = _train_metrics()
        self._tracer = obs.tracer()
        self._preempted = False
        self._window: list[float] = []

    def _install_sigterm(self):
        def handler(signum, frame):
            # preemption notice: finish the current step, checkpoint, exit
            self._preempted = True
        try:
            signal.signal(signal.SIGTERM, handler)
        except ValueError:              # not on main thread (tests)
            pass

    def _step_annotation(self, step: int):
        """The profiler's step marker around one step body while obs is
        on: loop work outside the phase spans shows in a trace as the
        step's time less its children."""
        if not self._tracer.enabled:
            return contextlib.nullcontext()
        return jax.profiler.StepTraceAnnotation("train", step_num=step + 1)

    def _default_log_metrics(self, record: dict):
        """Console formatter over the structured records — same cadence and
        string as the historic pre-formatted logging."""
        self._window.append(record["s_per_step"])
        step = record["step"]
        if step % self.log_every == 0:
            dt = sum(self._window) / len(self._window)
            self._window = []
            self.log(f"[trainer] step {step} loss "
                     f"{float(record['loss']):.4f} "
                     f"({dt * 1e3:.0f} ms/step)")

    def _emit(self, step: int, metrics: dict, dt: float):
        record = {"step": step, "s_per_step": dt, **metrics}
        self._default_log_metrics(record)
        if self.log_metrics is not None:
            self.log_metrics(record)

    def _ckpt_extra(self) -> dict | None:
        extra = {}
        if self.extra_state is not None:
            extra["extra_state"] = self.extra_state.state_dict()
        if self.resilience is not None:
            extra["resilience"] = self.resilience.state_dict()
        return extra or None

    def _load_checkpoint(self, step: int, *,
                         load_resilience: bool) -> TrainState:
        """Restore ``step``: manifest-carried state first (controller state
        shapes the restore target; the ladder's counters only on a fresh
        resume — a mid-run rollback must *keep* its escalation state), then
        the arrays, then re-impose the cumulative LR cut (the checkpointed
        ``lr_scale`` leaf predates the cuts)."""
        manifest = self.ckpt.manifest(step)
        if self.extra_state is not None:
            extra = manifest.get("extra_state")
            if extra:
                self.extra_state.load_state_dict(extra)
        if load_resilience and self.resilience is not None:
            rs = manifest.get("resilience")
            if rs:
                self.resilience.load_state_dict(rs)
        state = self.ckpt.restore(step, self.init_state_fn(),
                                  shardings=self.state_shardings)
        if self.resilience is not None:
            state = state._replace(
                opt_state=self.resilience.apply_lr_scale(state.opt_state))
        return state

    def run(self, total_steps: int, resume: bool = True) -> TrainState:
        self._install_sigterm()
        res = self.resilience
        start = 0
        state = None
        if resume and self.ckpt is not None:
            # newest checkpoint that passes CRC verification — corrupt ones
            # are quarantined and the next-older candidate is tried
            resume_step = self.ckpt.latest_verified_step()
            if resume_step is not None:
                state = self._load_checkpoint(resume_step,
                                              load_resilience=True)
                start = resume_step
                self.log(f"[trainer] resumed from checkpoint step "
                         f"{resume_step}")
        if state is None:
            state = self.init_state_fn()

        offset = res.data_offset if res is not None else 0
        pipeline = DataPipeline(self.batch_fn, start_step=start + offset)
        losses = []
        step = start
        try:
            while step < total_steps:
                with self._step_annotation(step):
                    t0 = time.perf_counter()
                    data_step = step + (res.data_offset if res is not None
                                        else 0)
                    with self._tracer.span("train/data_wait", step=step + 1):
                        batch = pipeline.get(data_step)
                    t_data = time.perf_counter()
                    with self._tracer.span("train/dispatch", step=step + 1):
                        state, metrics = self.train_step(state, batch)
                    t_disp = time.perf_counter()
                    # Timing note: blocking on the loss scalar is the same
                    # sync point the historic float(loss) imposed, so
                    # s_per_step is comparable across versions — but it is
                    # NOT pure compute. It includes the data wait above and
                    # only proves the loss is ready; donated/async outputs of
                    # the step (params, opt state) may still be in flight.
                    # The honest full-state figure is the sampled sync below
                    # (sync_sample_every), exported as
                    # train_full_sync_seconds.
                    with self._tracer.span("train/host_sync", step=step + 1):
                        jax.block_until_ready(metrics["loss"])
                    dt = time.perf_counter() - t0
                    self._m["data_wait"].observe(t_data - t0)
                    self._m["dispatch"].observe(t_disp - t_data)
                    self._m["host_sync"].observe(max(dt - (t_disp - t0), 0.0))
                    self._m["step_wall"].observe(dt)
                    if self.sync_sample_every > 0 \
                            and (step + 1) % self.sync_sample_every == 0:
                        with self._tracer.span("train/full_sync",
                                               step=step + 1):
                            jax.block_until_ready(state)
                        # data-ready -> whole-state-ready: per-step compute
                        self._m["full_sync"].observe(
                            time.perf_counter() - t_data)
                    if "telemetry" in metrics and (
                            self.log_metrics is not None
                            or self.control_hook is not None):
                        # one bulk device->host transfer shared by the sink and
                        # the controllers (instead of per-field fetches twice)
                        metrics["telemetry"] = jax.device_get(
                            metrics["telemetry"])
                    committed = True
                    if res is not None:
                        action = res.observe(
                            step + 1, float(metrics["loss"]),
                            bool(metrics.get("all_finite", True)))
                        if action.reason:
                            self.log(f"[resilience] {action.kind}: "
                                     f"{action.reason}")
                        if action.kind == "skip":
                            # the guard already refused the update in-jit; the
                            # optimizer step stands still, the data step moves
                            # past the offending batch (offset+1 keeps the
                            # prefetch stream contiguous)
                            res.skipped()
                            committed = False
                            self._m["steps"].inc(1, ("skipped",))
                        elif action.kind == "rollback":
                            state, step, pipeline = self._rollback(step,
                                                                   pipeline)
                            committed = False
                            self._m["steps"].inc(1, ("rolled_back",))
                        elif action.kind == "halt":
                            if self.ckpt is not None:
                                res.dump(os.path.join(self.ckpt.dir,
                                                      "halt.json"),
                                         context={"trainer_step": step})
                            raise TrainingHalted(action.reason)
                    if committed:
                        self._m["steps"].inc(1, ("committed",))
                        # metrics_history keeps scalars only: retaining every
                        # step's per-leaf stats pytree would grow device memory
                        # unbounded, and the sink's ring/file persist them
                        losses.append({k: v for k, v in metrics.items()
                                       if k != "telemetry"})
                        self._emit(step + 1, metrics, dt)
                        if self.control_hook is not None:
                            new_state = self.control_hook(step + 1, state,
                                                          metrics)
                            if new_state is not None:
                                state = new_state
                        step += 1
                    if self.ckpt is not None and (
                            (committed and step % self.ckpt_every == 0)
                            or self._preempted):
                        self.ckpt.async_save(step, state,
                                             extra=self._ckpt_extra())
                    if self._preempted:
                        self.log("[trainer] SIGTERM -> checkpointed, exiting")
                        break
        finally:
            pipeline.close()
            if self.ckpt is not None:
                self.ckpt.wait()
        self.metrics_history = losses
        return state

    def _rollback(self, step: int, pipeline):
        """Ladder rung 2/3: restore the last verified checkpoint (or a
        fresh init when none survives verification), shift the data window
        past the offending batches, and rebuild the prefetch pipeline on
        the shifted stream."""
        if self.ckpt is not None:
            self.ckpt.wait()            # never read under a pending writer
            to_step = self.ckpt.latest_verified_step()
        else:
            to_step = None
        if to_step is not None:
            state = self._load_checkpoint(to_step, load_resilience=False)
        else:
            # nothing restorable — roll all the way back to initialization
            to_step = 0
            state = self.init_state_fn()
            state = state._replace(
                opt_state=self.resilience.apply_lr_scale(state.opt_state))
        self.log(f"[trainer] rollback: step {step} -> {to_step}")
        self.resilience.rolled_back(from_step=step, to_step=to_step)
        pipeline.close()
        pipeline = DataPipeline(
            self.batch_fn,
            start_step=to_step + self.resilience.data_offset)
        return state, to_step, pipeline
