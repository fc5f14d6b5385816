"""Composable gradient-transform optimizer API (DESIGN.md §4).

The paper's thesis is that the projector is a *swappable component* inside
an otherwise identical low-rank Adam.  This module makes the whole
optimizer swappable, optax-style: a ``GradientTransform`` is an
``(init, update)`` pair with the stable signature

    init(params)                      -> state
    update(updates, state, params, ctx) -> (updates, state)

where ``ctx`` is the harness :class:`~repro.optim.common.Context` (global
step, shared DCT bases, PRNG key) threaded by the chain runtime — any
transform in the stack can request a basis via ``ctx.basis(n)``.

Combinators
-----------
- ``chain(*transforms)``          — sequential composition
- ``partition(by_label, label_fn)`` — route leaves to different transforms
  by an arbitrary label set (generalizes the old lowrank/full split: per
  group ranks, dct-adamw-on-attention + muon-on-mlp, …)
- ``inject_hyperparams(factory)`` — float hyperparameters (lr/wd/b1/b2/…)
  become state leaves updatable at runtime, no retrace

Primitives
----------
``clip_global_norm``, ``scale_by_schedule``, ``scale_by_learning_rate``,
``add_decayed_weights``, ``scale_by_adam`` (full-rank Adam direction) and
``lowrank_project(rule)`` which lifts any per-matrix-leaf
:class:`~repro.optim.common.MatrixRule` (``ProjectedAdamRule``, ``TrionRule``,
…, including the fused Pallas path) to a whole-tree transform.

``as_optimizer(transform)`` closes a transform into the legacy
``Optimizer(init, update)`` interface: it owns the step counter, the PRNG
key and the shared-basis store, and emits a :class:`ChainState` whose
field names (``step``/``key``/``bases``/``leaves``) match the old
``HarnessState`` so state-walking consumers keep working.

Per-leaf PRNG keys are derived from a *stable hash of the tree path*
(``fold_in(fold_in(key, step), crc32(path))``), not flat enumeration order
— adding or removing a parameter leaves every other leaf's randomness
unchanged.
"""
from __future__ import annotations

import dataclasses
import inspect
import zlib
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core.transforms import (
    basis_store_key,
    normalize_basis_request,
    shared_basis,
)

from .common import (
    AdamMoments,
    Context,
    FullAdamLeaf,
    MatrixRule,
    Optimizer,
    Schedule,
    adam_update,
    default_label_fn,
    labelled_tree,
    path_str,
    sched_value,
)


class GradientTransform(NamedTuple):
    """Composable optimizer building block.

    ``basis_sizes(params)`` declares which shared predefined bases the
    transform needs — ``(kind, n)`` pairs, or bare orders ``n`` (legacy
    spelling for the DCT basis); the chain runtime (``as_optimizer``)
    collects the union over the whole stack and stores one ``(n, n)``
    basis matrix per distinct request in the optimizer state
    (``basis_mode="stored"``), served from the process-wide
    :class:`~repro.core.transforms.BasisCache`.
    """

    init: Callable[[Any], Any]
    update: Callable[[Any, Any, Any, Context], tuple[Any, Any]]
    basis_sizes: Callable[[Any], set] = lambda params: set()


class EmptyState(NamedTuple):
    """State of a stateless transform (jit-stable placeholder)."""


class MaskedNode:
    """Placeholder for leaves hidden from a partitioned sub-transform.

    Registered as a pytree node with zero leaves, so ``jax.tree.map`` (and
    flatten/unflatten, checkpoint path-flattening, donation) simply skips
    the masked positions — sub-transforms need no masking awareness.
    """

    def __repr__(self):
        return "MaskedNode"

    def __eq__(self, other):
        return isinstance(other, MaskedNode)

    def __hash__(self):
        return hash(MaskedNode)


jax.tree_util.register_pytree_node(
    MaskedNode, lambda _: ((), None), lambda *_: MaskedNode()
)

MASKED = MaskedNode()

_is_str = lambda x: isinstance(x, str)  # noqa: E731


def path_hash(path: str) -> int:
    """Stable 31-bit hash of a tree path ('block/0/wq') — the per-leaf PRNG
    fold constant. crc32 is deterministic across processes and jax versions
    (unlike Python's salted ``hash``)."""
    return zlib.crc32(path.encode("utf-8")) & 0x7FFFFFFF


def leaf_key(key: jax.Array | None, path: str) -> jax.Array | None:
    """Per-leaf PRNG key: fold a stable path hash into the step key."""
    if key is None:
        return None
    return jax.random.fold_in(key, path_hash(path))


# ---------------------------------------------------------------------------
# chain
# ---------------------------------------------------------------------------
def chain(*transforms: GradientTransform) -> GradientTransform:
    """Apply ``transforms`` in sequence; state is the tuple of member states."""

    def init(params):
        return tuple(t.init(params) for t in transforms)

    def update(updates, state, params, ctx):
        new_state = []
        for t, s in zip(transforms, state):
            updates, s = t.update(updates, s, params, ctx)
            new_state.append(s)
        return updates, tuple(new_state)

    def basis_sizes(params):
        sizes = set()
        for t in transforms:
            sizes |= t.basis_sizes(params)
        return sizes

    return GradientTransform(init, update, basis_sizes)


# ---------------------------------------------------------------------------
# partition
# ---------------------------------------------------------------------------
def _mask(labels, tree, label):
    """Replace subtrees whose label != ``label`` with MASKED."""
    return jax.tree.map(lambda lbl, sub: sub if lbl == label else MASKED,
                        labels, tree, is_leaf=_is_str)


def merge_by_label(labels, by_label: dict):
    """Inverse of ``_mask``: combine per-label trees (with MASKED holes)
    into one tree, taking each leaf position from its own label's tree."""
    order = list(by_label)
    return jax.tree.map(
        lambda lbl, *subs: subs[order.index(lbl)],
        labels, *(by_label[k] for k in order), is_leaf=_is_str,
    )


def partition(
    transforms: dict[str, GradientTransform],
    label_fn=default_label_fn,
) -> GradientTransform:
    """Route each parameter leaf to the transform of its label.

    ``label_fn(path, leaf) -> str`` may return any label in ``transforms``
    — not just the classic ``lowrank``/``full`` pair: per-group ranks,
    per-module rules (dct-adamw on attention + muon on mlp), frozen
    groups, etc. An unknown label raises eagerly at ``init``.
    """

    def _labels(params):
        labels = labelled_tree(params, label_fn)
        seen = {l for l in jax.tree.leaves(labels, is_leaf=_is_str)}
        unknown = seen - set(transforms)
        if unknown:
            raise ValueError(
                f"label_fn produced labels {sorted(unknown)} with no "
                f"transform; have {sorted(transforms)}")
        return labels

    def init(params):
        labels = _labels(params)
        return {lbl: t.init(_mask(labels, params, lbl))
                for lbl, t in transforms.items()}

    def update(updates, state, params, ctx):
        labels = _labels(params)
        outs, new_state = {}, {}
        for lbl, t in transforms.items():
            u, s = t.update(_mask(labels, updates, lbl), state[lbl],
                            _mask(labels, params, lbl), ctx)
            outs[lbl] = u
            new_state[lbl] = s
        return merge_by_label(labels, outs), new_state

    def basis_sizes(params):
        labels = _labels(params)
        sizes = set()
        for lbl, t in transforms.items():
            sizes |= t.basis_sizes(_mask(labels, params, lbl))
        return sizes

    return GradientTransform(init, update, basis_sizes)


# ---------------------------------------------------------------------------
# inject_hyperparams
# ---------------------------------------------------------------------------
class InjectHyperparamsState(NamedTuple):
    hyperparams: dict[str, jax.Array]
    inner: Any


def inject_hyperparams(factory: Callable[..., GradientTransform],
                       *, static_args: tuple[str, ...] = ()):
    """Make a transform factory's float hyperparameters runtime-updatable.

    ``inject_hyperparams(adamw_transform)(lr=1e-3, weight_decay=0.1)``
    returns a transform whose state carries ``{"lr": …, "weight_decay": …}``
    as fp32 scalars; overwriting them between steps (LR surgery, schedule
    sweeps) changes the next update *without retracing* — the transform is
    rebuilt inside the traced update from the state leaves.

    Python floats are injected; ints, bools, strings, callables
    (schedules), rules and anything named in ``static_args`` stay static.
    """
    sig = inspect.signature(factory)

    def wrapped(*args, **kwargs) -> GradientTransform:
        bound = sig.bind(*args, **kwargs)
        bound.apply_defaults()
        hyper: dict[str, float] = {}
        static: dict[str, Any] = {}
        for name, val in bound.arguments.items():
            kind = sig.parameters[name].kind
            if kind == inspect.Parameter.VAR_KEYWORD:
                for k, v in val.items():
                    if k not in static_args and isinstance(v, float) \
                            and not isinstance(v, bool):
                        hyper[k] = v
                    else:
                        static[k] = v
            elif name not in static_args and isinstance(val, float) \
                    and not isinstance(val, bool):
                hyper[name] = val
            else:
                static[name] = val

        def make(hp):
            return factory(**static, **hp)

        def init(params):
            return InjectHyperparamsState(
                hyperparams={k: jnp.asarray(v, jnp.float32)
                             for k, v in hyper.items()},
                inner=make(hyper).init(params))

        def update(updates, state, params, ctx):
            t = make({k: state.hyperparams[k] for k in hyper})
            updates, inner = t.update(updates, state.inner, params, ctx)
            return updates, InjectHyperparamsState(dict(state.hyperparams),
                                                   inner)

        def basis_sizes(params):
            return make(hyper).basis_sizes(params)

        return GradientTransform(init, update, basis_sizes)

    return wrapped


# ---------------------------------------------------------------------------
# primitive transforms
# ---------------------------------------------------------------------------
def stateless(update_fn) -> GradientTransform:
    """Lift ``update_fn(updates, params, ctx) -> updates`` to a transform."""
    return GradientTransform(
        init=lambda params: EmptyState(),
        update=lambda u, s, p, ctx: (update_fn(u, p, ctx), s),
    )


def clip_global_norm(max_norm: float) -> GradientTransform:
    """Scale updates so their global l2 norm is at most ``max_norm``."""

    def upd(updates, params, ctx):
        norm = jnp.sqrt(sum(jnp.sum(jnp.square(u.astype(jnp.float32)))
                            for u in jax.tree.leaves(updates)))
        scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-9))
        return jax.tree.map(lambda u: u * scale, updates)

    return stateless(upd)


def scale_by_schedule(step_size_fn: Schedule) -> GradientTransform:
    """Multiply updates by ``step_size_fn(step)`` (or a constant)."""

    def upd(updates, params, ctx):
        s = sched_value(step_size_fn, ctx.step)
        return jax.tree.map(lambda u: s * u, updates)

    return stateless(upd)


def scale_by_learning_rate(lr: Schedule) -> GradientTransform:
    """Descent scaling ``u -> -lr_t * u`` (fp32), the harness convention."""

    def upd(updates, params, ctx):
        lr_t = sched_value(lr, ctx.step)
        return jax.tree.map(lambda u: -lr_t * u.astype(jnp.float32), updates)

    return stateless(upd)


def add_decayed_weights(weight_decay: float, *,
                        schedule: Schedule | None = None) -> GradientTransform:
    """Decoupled weight decay.

    Without ``schedule``: ``u + wd * p`` (optax convention — place *before*
    the lr scaling). With ``schedule``: ``u - lr_t * wd * p`` (place *after*
    ``scale_by_learning_rate``; bit-for-bit the matrix harness's decay).
    """

    def upd(updates, params, ctx):
        if schedule is None:
            return jax.tree.map(
                lambda u, p: u + weight_decay * p.astype(jnp.float32),
                updates, params)
        lr_t = sched_value(schedule, ctx.step)
        return jax.tree.map(
            lambda u, p: u - lr_t * weight_decay * p.astype(jnp.float32),
            updates, params)

    return stateless(upd)


def lr_scale_transform(initial: float = 1.0) -> GradientTransform:
    """A runtime LR multiplier as an injected hyperparameter.

    Appended at the end of a chain it scales the *final* update — exactly
    what scaling the learning rate would do (descent and tied weight decay
    alike). Its ``lr_scale`` state leaf is what the resilience ladder's
    LR-cut rung rewrites between steps
    (:func:`repro.train.resilience.scale_hyperparam` — pure state surgery,
    zero retrace). Enable via ``as_optimizer(..., lr_scale=True)``.
    """

    def factory(lr_scale: float = 1.0) -> GradientTransform:
        return stateless(
            lambda updates, params, ctx: jax.tree.map(
                lambda u: u * lr_scale, updates))

    return inject_hyperparams(factory)(lr_scale=float(initial))


def scale_by_adam(b1: float = 0.9, b2: float = 0.999,
                  eps: float = 1e-8) -> GradientTransform:
    """Full-rank Adam direction ``mhat / (sqrt(vhat) + eps)`` per leaf,
    bias-corrected by the global step (the harness's full-rank fallback)."""

    def init(params):
        return jax.tree.map(
            lambda p: FullAdamLeaf(AdamMoments(
                jnp.zeros(p.shape, jnp.float32),
                jnp.zeros(p.shape, jnp.float32))),
            params)

    def update(updates, state, params, ctx):
        with jax.named_scope("opt/full_adam"):
            pairs = jax.tree.map(
                lambda g, s: adam_update(g, s.mom, ctx.step, b1, b2, eps),
                updates, state,
                is_leaf=lambda x: isinstance(x, FullAdamLeaf))
        d = jax.tree.map(lambda g, pr: pr[0], updates, pairs)
        new_state = jax.tree.map(lambda g, pr: FullAdamLeaf(pr[1]),
                                 updates, pairs)
        return d, new_state

    return GradientTransform(init, update)


def lowrank_project(rule: MatrixRule, *,
                    overrides: dict[str, dict] | None = None
                    ) -> GradientTransform:
    """Lift a per-matrix-leaf :class:`MatrixRule` to a whole-tree transform.

    Each leaf gets a per-leaf :class:`Context` whose PRNG key folds in a
    stable hash of the leaf's tree path; the shared predefined bases (any
    registered backend kind the rule requests) arrive via the chain
    runtime; the telemetry collector (if one is installed) is narrowed to
    the leaf's path so the rule's :class:`SubspaceStats` land under a
    stable key. Emits the rule's raw descent direction ``D`` —
    compose with ``scale_by_learning_rate`` / ``add_decayed_weights``.

    ``overrides`` maps leaf tree paths (``path_str`` form, the same keys
    telemetry emits under) to per-leaf field replacements on ``rule`` —
    e.g. ``{"block/0/wq": {"rank": 192, "update_interval": 4}}``. This is
    the plug point the adaptive rank/refresh controllers drive
    (DESIGN.md §8): rank is a static shape parameter, so changed overrides
    mean a rebuilt optimizer + state migration, handled host-side by
    :mod:`repro.telemetry.adaptive`.
    """

    def rule_for(path: str) -> MatrixRule:
        if overrides and path in overrides:
            return dataclasses.replace(rule, **overrides[path])
        return rule

    def init(params):
        return jax.tree_util.tree_map_with_path(
            lambda kp, p: rule_for(path_str(kp)).init(p.shape, p.dtype),
            params)

    def update(updates, state, params, ctx):
        from repro.parallel import zero as zero_mod

        # ZeRO-1 (DESIGN.md §9): resolve the config once per update against
        # the active mesh; eligible leaves run their rule inside shard_map
        # on row blocks, the rest fall through to the replicated path.
        zctx = zero_mod.resolve(ctx.zero)

        def leaf(kp, g, s, p):
            path = path_str(kp)
            r = rule_for(path)
            leaf_ctx = dataclasses.replace(
                ctx, key=leaf_key(ctx.key, path),
                stats=ctx.stats.scope(path) if ctx.stats is not None
                else None)
            if (zctx is not None and r.zero_shardable
                    and zero_mod.eligible(p.shape, zctx.n_shards)):
                return zero_mod.sharded_leaf_update(r, g, s, p, leaf_ctx,
                                                    zctx)
            return r.update(g, s, p, leaf_ctx)

        pairs = jax.tree_util.tree_map_with_path(leaf, updates, state, params)
        d = jax.tree.map(lambda g, pr: pr[0], updates, pairs)
        new_state = jax.tree.map(lambda g, pr: pr[1], updates, pairs)
        return d, new_state

    def basis_sizes(params):
        sizes = set()
        if rule.needs_shared_basis:
            for p in jax.tree.leaves(params):
                sizes.update(rule.basis_sizes(p.shape))
        return sizes

    return GradientTransform(init, update, basis_sizes)


# ---------------------------------------------------------------------------
# the chain runtime: GradientTransform -> Optimizer
# ---------------------------------------------------------------------------
class ChainState(NamedTuple):
    """Top-level optimizer state emitted by ``as_optimizer``.

    Field names match the legacy ``HarnessState`` (``step``/``key``/
    ``bases``/``leaves``) so structure-agnostic consumers (checkpointing,
    sharding-spec derivation, state-bytes accounting) work unchanged;
    ``leaves`` holds the wrapped transform's state.
    """

    step: jax.Array
    key: jax.Array
    bases: dict
    leaves: Any


def as_optimizer(transform: GradientTransform, *, seed: int = 0,
                 basis_mode: str = "stored", zero=None,
                 lr_scale: bool = False) -> Optimizer:
    """Close a transform into the ``Optimizer(init, update)`` interface.

    The runtime owns the global step, the PRNG key (per-step fold) and the
    shared-basis store: ``basis_mode="stored"`` materializes one ``(n, n)``
    basis matrix per distinct ``(kind, n)`` requested by the stack (the
    paper's whole-model shared basis, via the process-wide
    :class:`~repro.core.transforms.BasisCache` so adaptive-controller
    rebuilds re-use it); ``"onthefly"`` stores nothing and lets
    ``Context.basis`` recompute inside the step.

    ``zero``: a :class:`repro.parallel.zero.ZeroConfig` enabling ZeRO-1
    partitioning of eligible low-rank leaf state across the data axes
    (DESIGN.md §9). It rides the :class:`Context` into every transform;
    ``lowrank_project`` resolves it against the mesh active at trace time,
    so one optimizer object works on any topology (including none).

    ``lr_scale=True`` appends :func:`lr_scale_transform` — the resilience
    ladder's retrace-free LR-cut seam (off by default: the chain and its
    state are then bit-identical to builds that predate the knob).
    """
    if basis_mode not in ("stored", "onthefly"):
        raise ValueError(f"unknown basis_mode {basis_mode!r}; expected "
                         f"'stored' or 'onthefly'")
    if lr_scale:
        transform = chain(transform, lr_scale_transform())

    def init(params):
        sizes = transform.basis_sizes(params) if basis_mode == "stored" else ()
        reqs = sorted({normalize_basis_request(s) for s in sizes})
        bases = {basis_store_key(k, n): shared_basis(k, n, jnp.float32)
                 for k, n in reqs}
        return ChainState(
            step=jnp.zeros((), jnp.int32),
            key=jax.random.PRNGKey(seed),
            bases=bases,
            leaves=transform.init(params),
        )

    def update(grads, state: ChainState, params):
        from repro.telemetry.stats import active_collector

        step = state.step + 1
        # the collector (if installed via telemetry.stats.collect around
        # this — traced — call) rides the ctx; rules record SubspaceStats
        # into it and the caller returns collector.tree() as a jit output
        ctx = Context(step=step, bases=state.bases,
                      key=jax.random.fold_in(state.key, step),
                      stats=active_collector(), zero=zero)
        updates, leaves = transform.update(grads, state.leaves, params, ctx)
        return updates, ChainState(step=step, key=state.key,
                                   bases=state.bases, leaves=leaves)

    return Optimizer(init=init, update=update)


def matrix_optimizer(
    rule: MatrixRule,
    lr: Schedule,
    *,
    weight_decay: float = 0.0,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
    label_fn=default_label_fn,
    basis_mode: str = "stored",
    seed: int = 0,
    fullrank_weight_decay: bool = True,
    overrides: dict[str, dict] | None = None,
    zero=None,
    lr_scale: bool = False,
) -> Optimizer:
    """The classic matrix-optimizer preset, rebuilt as a chain: route
    matrix leaves to ``rule`` and everything else to full-rank Adam, then
    apply lr scaling and decoupled weight decay. Drop-in replacement for
    the legacy ``make_matrix_optimizer`` (bit-for-bit, see
    tests/test_transform_api.py). ``overrides`` is the per-leaf-path rule
    field override map forwarded to :func:`lowrank_project` (the adaptive
    rank/refresh controllers' plug point); ``zero`` and ``lr_scale``
    (the resilience ladder's LR-cut seam) are forwarded to
    :func:`as_optimizer`."""
    routes = {"lowrank": lowrank_project(rule, overrides=overrides),
              "full": scale_by_adam(b1, b2, eps)}
    if fullrank_weight_decay:
        t = chain(partition(routes, label_fn),
                  scale_by_learning_rate(lr),
                  add_decayed_weights(weight_decay, schedule=lr))
    else:
        t = partition({
            "lowrank": chain(routes["lowrank"], scale_by_learning_rate(lr),
                             add_decayed_weights(weight_decay, schedule=lr)),
            "full": chain(routes["full"], scale_by_learning_rate(lr)),
        }, label_fn)
    return as_optimizer(t, seed=seed, basis_mode=basis_mode, zero=zero,
                        lr_scale=lr_scale)
