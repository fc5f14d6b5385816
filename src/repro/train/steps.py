"""Train/eval step factories — the functions the dry-run lowers.

``make_train_step`` returns a pure (state, batch) -> (state, metrics)
function containing the forward, backward, gradient-accumulation microbatch
loop, global-norm clipping, and the *optimizer update itself* — the paper's
contribution is optimizer-side, so the DCT projection, dynamic column
selection, Newton-Schulz and the low-rank collectives are all part of the
lowered HLO that the roofline analysis reads.

Gradient accumulation: ``cfg.train_microbatch`` rows per inner step via
`lax.scan`, fp32 accumulators. Cross-device gradient reduction is GSPMD's
(from the batch sharding); the §Perf log tracks what XLA does with the
per-microbatch all-reduces.
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.models import transformer as T
from repro.optim import apply_updates


class TrainState(NamedTuple):
    step: jax.Array
    params: Any
    opt_state: Any


def _cross_entropy(logits, targets):
    """Mean next-token NLL; fp32 log-softmax. targets: (B, S) int32."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return nll.mean()


def loss_fn(params, batch, cfg):
    inputs = {k: v for k, v in batch.items() if k != "targets"}
    logits, aux = T.forward(params, inputs, cfg)
    with jax.named_scope("model/head"):
        loss = _cross_entropy(logits, batch["targets"])
        metrics = {"ce": loss}
        loss = loss + aux["moe_aux"]
        if aux.get("mtp_logits") is not None:
            # MTP head predicts target_{t+1} from position t (DeepSeek-V3);
            # full-length logits, final position masked (rolled target)
            mtp_tgt = jnp.roll(batch["targets"], -1, axis=1)
            logp = jax.nn.log_softmax(
                aux["mtp_logits"].astype(jnp.float32), -1)
            nll = -jnp.take_along_axis(logp, mtp_tgt[..., None], -1)[..., 0]
            s = nll.shape[1]
            w = (jnp.arange(s) < s - 1).astype(jnp.float32)[None, :]
            mtp = (nll * w).sum() / w.sum() / nll.shape[0]
            loss = loss + 0.3 * mtp
            metrics["mtp_ce"] = mtp
    metrics["loss"] = loss
    return loss, metrics


def _global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                        for g in jax.tree.leaves(tree)))


def _clip_by_global_norm(tree, max_norm):
    norm = _global_norm(tree)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-9))
    return jax.tree.map(lambda g: g * scale, tree), norm


def _split_micro(batch, n_micro):
    """(B, ...) -> (n_micro, B/n_micro, ...) on every leaf."""
    return jax.tree.map(
        lambda x: x.reshape(n_micro, x.shape[0] // n_micro, *x.shape[1:]),
        batch)


def grad_fn(params, batch, cfg):
    (loss, metrics), grads = jax.value_and_grad(
        loss_fn, has_aux=True)(params, batch, cfg)
    return grads, metrics


def make_train_step(cfg, optimizer, *, grad_clip: float = 1.0,
                    accum_dtype: str = "float32", telemetry: bool = False,
                    guard: bool = False, chaos=None):
    """(TrainState, batch) -> (TrainState, metrics).

    ``accum_dtype``: microbatch gradient-accumulator dtype. fp32 default;
    bf16 halves the gradient HBM footprint for the >=90B archs (recorded as
    a precision trade in DESIGN.md §7).

    ``telemetry=True`` installs a stats collector around the (traced)
    optimizer update; the per-leaf :class:`SubspaceStats` the rules emit
    come back under ``metrics["telemetry"]`` (DESIGN.md §8). Off by
    default — the graph is then bit-identical to a telemetry-free build.

    ``guard=True`` arms the in-jit anomaly guard (DESIGN.md §11): one
    ``all_finite`` flag over loss / gradient norm / updates decides —
    *inside* the jitted step, donation-safe — whether the new state
    commits or the old one passes through unchanged
    (``resilience.select_tree``); the flag comes back under
    ``metrics["all_finite"]`` for the host-side escalation ladder. Off by
    default: the lowered HLO is then bit-identical to a guard-free build
    (``benchmarks/resilience_overhead.py`` gates the armed overhead).

    ``chaos``: a :class:`~repro.train.chaos.ChaosPlan` whose ``grads``
    faults are injected into the traced step, keyed on the data step the
    plan's batch wrapper stamps into each batch (tests/CI only).

    Every device op of the step runs under a ``<layer>/<part>`` name
    scope (``model/*`` in the model, ``step/*`` here, ``opt/*`` in the
    optimizer): compile-time metadata that names each op's layer in a
    profile (docs/observability.md, "Device scopes").
    """
    adt = jnp.dtype(accum_dtype)

    def train_step(state: TrainState, batch) -> tuple[TrainState, dict]:
        chaos_step = None
        if chaos is not None:
            from repro.train.chaos import strip_chaos_key

            batch, chaos_step = strip_chaos_key(batch)
        b = batch["tokens"].shape[0]
        mb = cfg.train_microbatch or b
        n_micro = max(1, b // mb)

        if n_micro == 1:
            grads, metrics = grad_fn(state.params, batch, cfg)
            with jax.named_scope("step/clip"):
                grads = jax.tree.map(lambda g: g.astype(adt), grads)
        else:
            micro = _split_micro(batch, n_micro)

            def acc_step(acc, mbatch):
                g, m = grad_fn(state.params, mbatch, cfg)
                acc = jax.tree.map(
                    lambda a, gi: a + (gi / n_micro).astype(adt), acc, g)
                return acc, m

            with jax.named_scope("step/accum"):
                zeros = jax.tree.map(
                    lambda p: jnp.zeros(p.shape, adt), state.params)
                grads, ms = jax.lax.scan(acc_step, zeros, micro)
                metrics = jax.tree.map(lambda x: x.mean(), ms)

        if chaos is not None and chaos_step is not None:
            grads = chaos.tamper_grads(chaos_step, grads)

        with jax.named_scope("step/clip"):
            if grad_clip:
                grads, gnorm = _clip_by_global_norm(grads, grad_clip)
            else:
                gnorm = _global_norm(grads)

        metrics = dict(metrics)
        with jax.named_scope("opt/update"):
            if telemetry:
                from repro.telemetry.stats import collect

                # the context manager lives entirely at trace time: the
                # rules record tracer-valued SubspaceStats into the
                # collector and the collected tree is returned as a
                # regular jit output
                with collect() as col:
                    updates, new_opt = optimizer.update(
                        grads, state.opt_state, state.params)
                tel = col.tree()
                if tel:
                    metrics["telemetry"] = tel
            else:
                updates, new_opt = optimizer.update(grads, state.opt_state,
                                                    state.params)
        with jax.named_scope("step/apply"):
            new_params = apply_updates(state.params, updates)
            new_state = TrainState(state.step + 1, new_params, new_opt)
        metrics["grad_norm"] = gnorm
        if guard:
            from repro.train.resilience import all_finite_tree, select_tree

            # one flag over loss / grad-norm / updates: gnorm is a sum of
            # squares over every gradient leaf, so any NaN/Inf anywhere in
            # the gradients poisons it for free; updates cover the
            # optimizer's own arithmetic. The commit point is a per-leaf
            # select between new and old state — donation-safe (the donated
            # old buffers feed the select, never aliased ambiguously), and
            # XLA folds select(p, x, x) for leaves the step didn't change.
            with jax.named_scope("step/guard"):
                flag = (jnp.isfinite(metrics["loss"])
                        & jnp.isfinite(gnorm)
                        & all_finite_tree(updates))
                new_state = select_tree(flag, new_state, state)
            metrics["all_finite"] = flag
        return new_state, metrics

    return train_step


def make_eval_step(cfg):
    def eval_step(params, batch):
        _, metrics = loss_fn(params, batch, cfg)
        return metrics
    return eval_step


def init_state(cfg, optimizer, key) -> TrainState:
    params = T.init_params(cfg, key)
    return TrainState(jnp.zeros((), jnp.int32), params,
                      optimizer.init(params))
