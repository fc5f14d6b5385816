"""ZeRO-1-style partitioning of low-rank optimizer state (DESIGN.md §9).

The paper's memory claim — rank-independent runtime with up to 25% lower
optimizer memory — compounds with data parallelism: the projected-Adam
state (Adam moments in R^{rows x r}, the int8/fp32 error-feedback buffer in
R^{rows x cols}, per-row EF scales) is *row-parallel*, so it can be
partitioned across the ``('pod', 'data')`` axes and each device can run the
fused select+project+update step on its own row block. Per-device
optimizer-state bytes drop by the DP world size on top of the paper's
low-rank reduction.

Why the row-block decomposition is exact (not an approximation):

* ``S = G @ Q`` is row-parallel — every row of ``S`` is an independent
  contraction of the matching row of ``G`` with the shared basis ``Q``.
* Dynamic column selection needs the *global* column energies
  ``||S[:, j]||^2`` — the only cross-shard quantity in the whole step. Each
  shard reduces its row block and one ``(n,)``-sized ``psum`` over the DP
  axes makes the statistic (and therefore the selected indices, the
  rotation, and the telemetry aggregates) identical on every shard.
* The Adam moment update, bias correction, back-projection
  ``u @ Q_r^T`` and the per-row q8 EF quantization are all elementwise or
  row-parallel, so they run shard-local with zero communication.

The update direction leaves the ``shard_map`` still row-sharded
(``out_specs`` keeps the DP axes on the row dim); the all-gather back to
the parameter's sharding happens lazily where ``apply_updates`` consumes
it, which lets XLA overlap each leaf's gather with the next leaf's
shard-local compute instead of serializing a collective per leaf.

Scope (``MatrixRule.zero_shardable``): rules whose projector state is an
*index set into the shared basis* — any registered basis backend with a
row-decomposable energy statistic (``BasisBackend.zero_shardable``:
dct / dst / hadamard / randortho), plus the identity-basis ``randperm`` —
and, since DESIGN.md §14, the momentum-orthogonalization families
muon / trion / dion. Muon/trion add exactly one new cross-shard term
beyond the psum'd column statistic: the Newton-Schulz all-gather of the
*rank-sized* low-rank factor (NS mixes rows through its Gram matrix, so
it is recomputed identically per shard from the gathered factor and each
shard keeps its own output rows — see ``fused_step.fused_newton_schulz``).
Dion all-gathers the full momentum sum (its ``B^T P`` contraction spans
all rows) and re-slices; its per-layer ``q`` basis comes out replicated
and is placed replicated (``state_specs``). Dense-basis projected-Adam
projectors (svd / power / random) keep a per-matrix ``(n, r)`` basis whose
refresh is not row-decomposable; those leaves — and any leaf whose
oriented row count does not divide the shard count — fall back to the
replicated update path unchanged.
"""
from __future__ import annotations

import dataclasses

import jax
from jax.sharding import PartitionSpec as P

from repro.optim.common import deorient, orient_right
from repro.parallel.sharding import active_mesh

ZERO_MODES = ("off", "1")


@dataclasses.dataclass(frozen=True)
class ZeroConfig:
    """Optimizer-state partitioning config.

    ``mode``: "off" (replicated state, the historical behaviour) or "1"
    (ZeRO-1: state + update step partitioned, updates all-gathered).
    ``axes``: mesh axes to partition over; the present subset of the
    active mesh is used (same convention as ``sharding.DP_AXES``).
    """

    mode: str = "off"
    axes: tuple[str, ...] = ("pod", "data")

    def __post_init__(self):
        if self.mode not in ZERO_MODES:
            raise ValueError(f"unknown zero mode {self.mode!r}; "
                             f"allowed: {ZERO_MODES}")
        if isinstance(self.axes, list):
            object.__setattr__(self, "axes", tuple(self.axes))

    @property
    def active(self) -> bool:
        return self.mode != "off"


ZERO_OFF = ZeroConfig()


def parse_zero(flag: str) -> ZeroConfig:
    """CLI helper: ``--zero {off,1}`` -> :class:`ZeroConfig`."""
    return ZeroConfig(mode=flag)


@dataclasses.dataclass(frozen=True)
class ZeroContext:
    """Resolved partitioning info for the active mesh (trace-time)."""

    mesh: object
    axes: tuple[str, ...]
    n_shards: int


def present_axes(mesh, cfg: ZeroConfig) -> tuple[str, ...]:
    if mesh is None:
        return ()
    return tuple(a for a in cfg.axes if a in mesh.axis_names)


def resolve(cfg: ZeroConfig | None) -> ZeroContext | None:
    """Resolve a config against the active mesh; None when inactive
    (mode off, no mesh, configured axes absent, or a 1-wide shard set)."""
    if cfg is None or not cfg.active:
        return None
    mesh = active_mesh()
    axes = present_axes(mesh, cfg)
    if not axes:
        return None
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    if n <= 1:
        return None
    return ZeroContext(mesh=mesh, axes=axes, n_shards=n)


# ---------------------------------------------------------------------------
# shard placement policy
# ---------------------------------------------------------------------------
def _oriented_rows(param_shape) -> int:
    """The oriented row count: rules orient matrices so the *projected*
    dimension is last and rows = max of the trailing two dims."""
    return max(param_shape[-2], param_shape[-1])


def eligible(param_shape, n_shards: int) -> bool:
    """A leaf's state partitions iff its oriented row dim splits evenly."""
    if len(param_shape) < 2 or n_shards <= 1:
        return False
    return _oriented_rows(param_shape) % n_shards == 0


def grad_spec(param_shape, axes: tuple[str, ...]) -> P:
    """Spec splitting an *oriented* (rows-at-dim-(-2)) array's row dim.

    Gradients are right-oriented before entering the shard_map (and
    updates deoriented after it) so the split dim is always -2 — deciding
    orientation on a local row block would be wrong, since a block's
    aspect ratio can differ from the global leaf's.
    """
    lead = (None,) * (len(param_shape) - 2)
    return P(*lead, axes, None)


def state_array_spec(param_shape, state_shape, axes: tuple[str, ...]) -> P:
    """Spec for one optimizer-state array of an eligible leaf.

    State arrays are stored *oriented* (rows first of the trailing two
    dims): moments ``(..., rows, r)``, EF payload ``(..., rows, cols)``,
    per-row EF scales ``(..., rows, 1)`` all shard the row dim; index
    sets ``(..., r)``, scalars and anything else replicate.
    """
    rows = _oriented_rows(param_shape)
    if (len(state_shape) == len(param_shape)
            and len(state_shape) >= 2 and state_shape[-2] == rows):
        return P(*([None] * (len(state_shape) - 2)), axes, None)
    return P()


def state_specs(param_shape, state_tree, axes: tuple[str, ...]):
    """Per-array specs for a whole per-leaf state subtree (ProjAdamLeaf,
    including a nested q8 ``QuantizedBuffer``; MuonLeaf/TrionLeaf/DionLeaf).

    Dion's per-layer basis ``q (..., cols, r)`` is special-cased to
    replicate: it is computed from the all-gathered momentum sum (identical
    on every shard), and on *square* leaves its ``cols`` dim would
    otherwise be indistinguishable from a row dim and wrongly sharded.
    """
    from repro.optim.dion import DionLeaf  # lazy: avoids transform cycle

    if isinstance(state_tree, DionLeaf):
        return DionLeaf(
            m=state_array_spec(param_shape, state_tree.m.shape, axes),
            q=P())
    return jax.tree.map(
        lambda s: state_array_spec(param_shape, s.shape, axes), state_tree)


# ---------------------------------------------------------------------------
# the sharded leaf update
# ---------------------------------------------------------------------------
class _CaptureScope:
    """Single-leaf stats buffer used *inside* the shard_map body.

    The real collector lives outside the shard_map trace; recording outer
    tracers from inside would leak. The rule records into this local
    buffer, the stats ride out as a (replicated — every term is psum'd or
    index-derived) shard_map output, and the caller re-records them into
    the outer scope.
    """

    def __init__(self):
        self.stats = None

    def record(self, stats) -> None:
        self.stats = stats


def sharded_leaf_update(rule, g, state, param, ctx, zctx: ZeroContext):
    """Run ``rule.update`` with rows partitioned over ``zctx.axes``.

    Splits the gradient and the row-parallel state arrays across the DP
    shards, runs the (fused or reference) step shard-locally with
    ``ctx.axis`` set so row reductions psum, and returns the update
    direction still row-sharded plus the new (sharded) state. Leaf
    telemetry is computed in-shard from psum'd aggregates and re-recorded
    into the outer collector.
    """
    axes = zctx.axes
    gspec = grad_spec(param.shape, axes)
    sspecs = state_specs(param.shape, state, axes)
    capture = ctx.stats is not None
    # orientation is a *global* property: decide it on the full leaf and
    # hand the shard_map a pre-oriented gradient (ctx.oriented tells the
    # rule not to re-decide on its — possibly differently-shaped — block)
    gf, transposed = orient_right(g)

    def local(g_blk, s_blk, p_blk, step, key, bases):
        cap = _CaptureScope() if capture else None
        inner = dataclasses.replace(ctx, step=step, key=key, bases=bases,
                                    axis=axes, stats=cap, oriented=True)
        d, new_s = rule.update(g_blk, s_blk, p_blk, inner)
        return d, new_s, (cap.stats if capture else None)

    fn = jax.shard_map(
        local, mesh=zctx.mesh,
        in_specs=(gspec, sspecs, P(), P(), P(), P()),
        out_specs=(gspec, sspecs, P()),
        check_vma=False)
    d, new_state, stats = fn(gf, state, param, ctx.step, ctx.key, ctx.bases)
    if capture and stats is not None:
        ctx.record_stats(stats)
    return deorient(d, transposed), new_state

