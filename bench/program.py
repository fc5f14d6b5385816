"""The system under test, as the benchmark drives it.

This is the only module of the benchmark that imports the program
(``repro``). It composes the program's public pieces exactly as
``launch/train.py:train`` does, because ``train()`` has no seam between
building the job and running it:

- the model config from the program's registry, cut as the cell's
  configuration file says (``dataclasses.replace``);
- ``get_optimizer(<preset>, lr=cosine_warmup(...), **args)``;
- ``jax.jit(make_train_step(cfg, opt, grad_clip=...), donate_argnums=0)``;
- the ``train/loop.py`` ``Trainer`` over that step, fed through its own
  ``DataPipeline`` from a batch function of the benchmark's.

The weights come from the benchmark (``weights.py``), drawn on the device
from the seed; the program never sees the seed.
"""
from __future__ import annotations

import dataclasses
import math
import time

import jax
import jax.numpy as jnp

from . import synthetic, weights


def schedule(model: dict, registry) -> tuple:
    """The layer schedule a configuration file's ``model`` states: its
    ``schedule``, a list of ``[pattern, repeats]``, or ``n_layers`` alone,
    meaning ``[[["attn"], n_layers]]``. Its segments are the registry's, in
    the same order and with the same patterns, each repeated from once to
    as often as in the registry: whole periods, with a leading segment
    kept. A file cannot state a layout that the source does not have."""
    if "schedule" in model:
        stated = tuple((tuple(p), r) for p, r in model["schedule"])
    else:
        stated = ((("attn",), model["n_layers"]),)
    if [p for p, _ in stated] != [p for p, _ in registry]:
        raise ValueError(f"the schedule's patterns {[p for p, _ in stated]} "
                         f"are not the registry's {[p for p, _ in registry]}")
    for (pattern, r), (_, most) in zip(stated, registry):
        if r not in range(1, most + 1):
            raise ValueError(f"{pattern} repeats {r!r} times; the registry "
                             f"allows 1 to {most}")
    return stated


def model_config(spec: dict, job: dict | None = None):
    """The program's ``ModelConfig`` for a configuration file: the
    registry's published config with the file's ``model`` keys put in, its
    layers as :func:`schedule` reads them, and the job's rows per
    micro-batch (``train_microbatch``). Fails when the result disagrees
    with the file on any key."""
    from repro.configs.registry import get_config

    base = get_config(spec["registry"])
    model = dict(spec["model"])
    if job is not None:
        model["train_microbatch"] = job["rows_per_micro"]
    layers = schedule(model, base.schedule)
    model.pop("n_layers", None)
    model.pop("schedule", None)
    cfg = dataclasses.replace(base, schedule=layers, **model)
    for key, want in spec["model"].items():
        if key == "n_layers":
            got = cfg.n_layers
        elif key == "schedule":
            got = [[list(p), r] for p, r in cfg.schedule]
        else:
            got = getattr(cfg, key)
        if got != want:
            raise ValueError(f"{spec['name']}: {key} is {got!r}, the "
                             f"configuration file states {want!r}")
    return cfg


def param_specs(cfg) -> dict:
    """``{path: (shape, dtype)}`` of the program's parameter tree."""
    from repro.models import transformer as T

    tree = jax.eval_shape(lambda: T.init_params(cfg, jax.random.PRNGKey(0)))
    return {_path(kp): (tuple(x.shape), str(x.dtype))
            for kp, x in jax.tree_util.tree_leaves_with_path(tree)}


def _path(kp) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in kp)


def _state_leaves(opt_state) -> dict:
    """``{param path: per-leaf optimizer state}``: the projected-Adam
    leaves (``m``, ``v``, ``ef``) and the full-rank Adam leaves (``mom``)."""
    def is_leaf_state(x):
        fields = getattr(x, "_fields", ())
        return "mom" in fields or "ef" in fields

    out = {}
    for kp, x in jax.tree_util.tree_flatten_with_path(
            opt_state, is_leaf=is_leaf_state)[0]:
        if not is_leaf_state(x):
            continue
        keys = [str(getattr(k, "key", getattr(k, "idx", k))) for k in kp]
        for route in ("full", "lowrank"):
            if route in keys:
                out["/".join(keys[keys.index(route) + 1:])] = x
                break
    return out


def grad_norms_from_state(opt_state, b1: float) -> dict:
    """Per-leaf norm of the first gradient as the optimizer received it,
    worked out from its state after one step: full-rank Adam holds
    ``m = (1 - b1) g``; projected Adam holds ``m = (1 - b1) G Q_r`` and the
    int8 error feedback ``G - G Q_r Q_r^T``, orthogonal to it."""
    def one(x):
        if "mom" in x._fields:
            return jnp.linalg.norm(x.mom.m) / (1.0 - b1)
        low = jnp.sum(jnp.square(x.m)) / (1.0 - b1) ** 2
        ef = x.ef.q.astype(jnp.float32) * x.ef.scale
        return jnp.sqrt(low + jnp.sum(jnp.square(ef)))

    leaves = _state_leaves(opt_state)
    return jax.jit(lambda s: {p: one(x) for p, x in s.items()})(leaves)


def state_bytes(tree) -> int:
    """Bytes of ``tree`` on the device."""
    return sum(x.nbytes for x in jax.tree.leaves(tree))


@dataclasses.dataclass
class Run:
    """What set-up built and the first steps recorded."""
    trainer: object
    state: object
    batch_fn: object
    tokens_per_step: int
    losses: list
    step_seconds: list
    grad_norms: dict
    delta_norms: dict
    opt_state_bytes: int = 0


class Program:
    """Builds the cell's job from the program's pieces and drives it."""

    def __init__(self, spec: dict, job: dict, seed: int, *,
                 step_wrapper=None):
        from repro.optim.api import get_optimizer
        from repro.train.schedule import cosine_warmup
        from repro.train.steps import make_train_step

        self.spec, self.job, self.seed = spec, job, seed
        self.init = spec.get("init", {})
        self.cfg = model_config(spec, job)
        lr = cosine_warmup(job["lr"], job["warmup_steps"],
                           job["schedule_steps"])
        self.opt = get_optimizer(job["optimizer"], lr=lr,
                                 **job["optimizer_args"])
        step = make_train_step(self.cfg, self.opt,
                               grad_clip=job["grad_clip"])
        if step_wrapper is not None:
            step = step_wrapper(step)
        self.step_fn = jax.jit(step, donate_argnums=0)
        self.specs = param_specs(self.cfg)
        self.tokens_per_step = job["rows_per_step"] * job["seq_len"]
        self._batch = synthetic.make_batch_fn(
            self.cfg.vocab_size, job["seq_len"], job["rows_per_step"], seed,
            job.get("data", {}))

    def init_state(self):
        """Weights drawn on the device from the seed, in their stored
        dtype, and the optimizer's initial state: two jitted calls."""
        from repro.train.steps import TrainState

        flat = weights.draw_all(synthetic.seed_key(self.seed, 0), self.specs,
                                self.init)
        params = _unflatten_like(self.cfg, flat)
        opt_state = jax.jit(self.opt.init)(params)
        return TrainState(jnp.zeros((), jnp.int32), params, opt_state)

    def delta_norms(self, params) -> dict:
        """Per-leaf ``||theta - theta_0||``, drawing each initial leaf
        again alone so that no second copy of the weights is held."""
        key = synthetic.seed_key(self.seed, 0)
        return {_path(kp): _delta(x, weights.draw_one(
                    key, _path(kp), x.shape, x.dtype, self.init))
                for kp, x in jax.tree_util.tree_leaves_with_path(params)}

    def start(self) -> Run:
        """Set-up: build the state, then drive it through the job's first
        ``check_steps`` steps with the window's own ``Trainer``, call and
        feed. The first of them compiles the step."""
        from repro.train.loop import Trainer

        n_check = self.job["check_steps"]
        b1 = self.job["optimizer_args"].get("b1", 0.9)
        box = {"state": self.init_state(), "offset": 0}
        rec = {"losses": [], "dt": [], "grad": None, "delta": None}

        def batch_fn(s):
            return self._batch(jnp.int32(s + box["offset"]))

        def log_metrics(record):
            rec["dt"].append(record["s_per_step"])
            if record["step"] <= n_check and box["offset"] == 0:
                rec["losses"].append(record["loss"])

        def control_hook(step, state, metrics):
            if box["offset"]:
                return None
            if step == 1:
                rec["grad"] = grad_norms_from_state(state.opt_state, b1)
            if step == n_check:
                rec["delta"] = self.delta_norms(state.params)
            return None

        trainer = Trainer(train_step=self.step_fn,
                          init_state_fn=lambda: box["state"],
                          batch_fn=batch_fn, log_every=1 << 30,
                          log_fn=lambda s: None, log_metrics=log_metrics,
                          control_hook=control_hook)
        state = trainer.run(total_steps=n_check)
        jax.block_until_ready(state)
        box["state"] = state
        box["offset"] = n_check
        self._box = box
        losses = [float(x) for x in rec["losses"]]
        grads = ({p: float(v) for p, v in rec["grad"].items()}
                 if rec["grad"] is not None else {})
        deltas = ({p: float(v) for p, v in rec["delta"].items()}
                  if rec["delta"] is not None else {})
        return Run(trainer=trainer, state=state, batch_fn=batch_fn,
                   tokens_per_step=self.tokens_per_step, losses=losses,
                   step_seconds=list(rec["dt"]), grad_norms=grads,
                   delta_norms=deltas,
                   opt_state_bytes=state_bytes(state.opt_state))

    def window(self, run: Run, n_steps: int) -> tuple[float, object]:
        """Run ``n_steps`` more steps of the same trainer on the same
        state; returns the wall seconds up to the whole state being ready
        and the final state."""
        box = self._box
        box["state"] = run.state
        run.state = None
        t0 = time.perf_counter()
        state = run.trainer.run(total_steps=n_steps)
        jax.block_until_ready(state)
        seconds = time.perf_counter() - t0
        box["offset"] += n_steps
        box["state"] = None
        return seconds, state

    def release(self):
        """Drops the state. The trainer (which its SIGTERM handler keeps
        alive) reaches the state only through this box."""
        self._box["state"] = None


def _unflatten_like(cfg, flat: dict):
    from repro.models import transformer as T

    tree = jax.eval_shape(lambda: T.init_params(cfg, jax.random.PRNGKey(0)))
    paths, treedef = jax.tree_util.tree_flatten_with_path(tree)
    return jax.tree_util.tree_unflatten(
        treedef, [flat[_path(kp)] for kp, _ in paths])


@jax.jit
def _delta(x, x0):
    return jnp.linalg.norm(x.astype(jnp.float32) - x0.astype(jnp.float32))


def window_steps(step_seconds: list, seconds: float) -> int:
    """Steps that fill a window of ``seconds``, from the set-up steps'
    times (the first compiled, so it is left out)."""
    per = step_seconds[-1]
    return max(2, math.ceil(seconds / per))
