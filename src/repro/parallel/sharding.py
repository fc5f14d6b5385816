"""Logical sharding rules for the (pod, data, model) production mesh.

Model code annotates activations with *logical* axis names; the mapping to
physical mesh axes adapts to whichever mesh is active (single-pod
``(data, model)`` or multi-pod ``(pod, data, model)``), and degrades to
no-ops when no mesh is active (CPU unit tests).

Parameter sharding follows the MaxText FSDP x TP recipe:
  * 2D weights  (d_in, d_out)      -> P(fsdp, tp)   (fsdp = ('pod','data'))
  * stacked     (L, ..., d_in, d_out) -> P(None, ..., fsdp, tp)
  * embeddings  (vocab, d_model)   -> P(tp, fsdp)   (vocab-sharded logits)
  * expert weights (L, E, d, f)    -> P(None, tp, fsdp, None)  (EP on tp axis)
  * 1D params                      -> replicated

The layout policy is a :class:`ShardingPolicy` carried in a
``contextvars.ContextVar`` — scope one with ``use_policy(layout=...)``.
Context variables are per-thread (and per-asyncio-task), so concurrent
dry-runs deriving specs under different layouts cannot race the way the
old module-global ``_LAYOUT`` setter could.
"""
from __future__ import annotations

import contextlib
import contextvars
import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


DP_AXES = ("pod", "data")   # batch/FSDP axes (present subset is used)
TP_AXIS = "model"

LAYOUTS = ("fsdp_tp", "pure_dp", "decode_tp")


@dataclasses.dataclass(frozen=True)
class ShardingPolicy:
    """Explicit layout policy object (replaces the old mutable globals).

    ``layout`` (§Perf iter): "fsdp_tp" (default) shards params FSDP x TP;
    "pure_dp" replicates params and data-parallelizes the batch over EVERY
    mesh axis — the right layout for small archs (whisper/rwkv) where
    256-way model sharding makes shards tiny and collectives dominant;
    "decode_tp" is the decode-time Megatron layout (§Perf iter-6).

    ``seq_parallel`` (§Perf iter-2): shard the residual stream's sequence
    dim over the `model` axis (Megatron-SP style) — activations between
    blocks stay sequence-sharded, so GSPMD stops re-gathering them around
    attention.
    """

    layout: str = "fsdp_tp"
    seq_parallel: bool = False

    def __post_init__(self):
        if self.layout not in LAYOUTS:
            raise ValueError(f"unknown layout {self.layout!r}; "
                             f"allowed: {LAYOUTS}")


_POLICY: contextvars.ContextVar[ShardingPolicy] = contextvars.ContextVar(
    "repro_sharding_policy", default=ShardingPolicy())


def current_policy() -> ShardingPolicy:
    return _POLICY.get()


@contextlib.contextmanager
def use_policy(policy: ShardingPolicy | None = None, **replacements):
    """Scope a layout policy: ``with use_policy(layout="pure_dp"): ...``.

    Either pass a full :class:`ShardingPolicy` or field replacements over
    the current one. Restores the previous policy on exit; per-thread, so
    concurrent derivations under different layouts don't interfere.
    """
    if policy is None:
        policy = dataclasses.replace(current_policy(), **replacements)
    elif replacements:
        raise TypeError("pass either a policy object or field replacements,"
                        " not both")
    token = _POLICY.set(policy)
    try:
        yield policy
    finally:
        _POLICY.reset(token)


def layout_policy() -> str:
    """Current layout name (read-only view of :func:`current_policy`)."""
    return current_policy().layout


def seq_parallel() -> bool:
    """Current sequence-parallel flag (read-only view)."""
    return current_policy().seq_parallel


def active_mesh():
    """The mesh installed by ``jax.set_mesh``, or None."""
    m = jax.sharding.get_abstract_mesh()
    return m if m.axis_names else None


def dp_axes(mesh=None) -> tuple[str, ...]:
    mesh = mesh or active_mesh()
    if mesh is None:
        return ()
    return tuple(a for a in DP_AXES if a in mesh.axis_names)


def tp_axis(mesh=None):
    mesh = mesh or active_mesh()
    if mesh is None or TP_AXIS not in mesh.axis_names:
        return None
    return TP_AXIS


def logical_to_spec(axes: tuple, mesh=None) -> P:
    """Map logical names to a PartitionSpec for the active mesh.

    Logical names: 'batch' (DP axes), 'tp' (model axis), 'seq' (sharded over
    DP axes — used for long-context KV), None (replicated). Under the
    'pure_dp' layout, 'batch' spans every mesh axis and 'tp' replicates.
    """
    mesh = mesh or active_mesh()
    policy = current_policy()
    dp = dp_axes(mesh)
    tp = tp_axis(mesh)
    if policy.layout == "pure_dp":
        batch_axes = tuple(a for a in (*dp, tp) if a) or None
        tp = None
    else:
        batch_axes = dp if dp else None
    out = []
    for a in axes:
        if a == "batch" or a == "seq":
            out.append(batch_axes)
        elif a == "tp":
            out.append(tp)
        elif a == "sp":
            out.append(tp if policy.seq_parallel else None)
        elif a is None:
            out.append(None)
        else:
            raise ValueError(f"unknown logical axis {a!r}")
    return P(*out)


def shard(x: jax.Array, *axes) -> jax.Array:
    """with_sharding_constraint by logical axes; no-op without a mesh."""
    mesh = active_mesh()
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(x, logical_to_spec(axes, mesh))


# ---------------------------------------------------------------------------
# Parameter partition specs (by path pattern + shape)
# ---------------------------------------------------------------------------
_REPLICATED_HINTS = ("norm", "scale", "bias", "gate", "mu_", "decay",
                     "bonus", "a_log", "d_skip", "conv", "ln_")


def _fit_spec(axes: tuple, shape: tuple[int, ...], mesh) -> P:
    """Drop mesh axes that do not evenly divide their dim (e.g. whisper's
    prime-ish vocab 51866 can't shard 16 ways -> that dim replicates)."""
    out = []
    for a, dim in zip(axes, shape):
        if a is None:
            out.append(None)
        elif dim % _axis_size(mesh, a) == 0:
            out.append(a)
        else:
            out.append(None)
    return P(*out)


def param_spec(path: str, shape: tuple[int, ...], mesh=None,
               policy: ShardingPolicy | None = None) -> P:
    mesh = mesh or active_mesh()
    policy = policy or current_policy()
    if policy.layout == "pure_dp":
        return P()              # params replicated; batch over all axes
    dp = dp_axes(mesh)
    dp = dp if dp else None
    tp = tp_axis(mesh)
    nd = len(shape)
    lpath = path.lower()
    if nd == 0 or nd == 1:
        return P()
    if any(h in lpath for h in _REPLICATED_HINTS):
        # stacked small params (norm scales, biases, ssm constants): the
        # leading dim is layers, the rest are tiny -> replicate
        return P()
    is_row = any(seg in ("wd", "wo", "out_proj")
                 for seg in lpath.split("/"))
    if policy.layout == "decode_tp":
        # §Perf iter-6: decode-time Megatron layout over the COMBINED
        # (dp x tp) axes — every matrix column-parallel (d_out over all
        # chips), down/out projections row-parallel. A decode step then
        # runs shard-local matmuls with one tiny activation psum per
        # block instead of re-gathering weight shards per token.
        allax = tuple(a for a in (*(dp or ()), tp) if a) or None
        lead = (None,) * (nd - 2)
        if "embed" in lpath or "unembed" in lpath or "lm_head" in lpath:
            return _fit_spec((*lead, allax, None), shape, mesh)
        if "expert" in lpath and nd >= 3:
            # experts on tp; expert hidden column/row-parallel on dp
            lead3 = (None,) * (nd - 3)
            if is_row:   # (L, E, f, d)
                return _fit_spec((*lead3, tp, dp, None), shape, mesh)
            return _fit_spec((*lead3, tp, None, dp), shape, mesh)
        if is_row:
            return _fit_spec((*lead, allax, None), shape, mesh)
        return _fit_spec((*lead, None, allax), shape, mesh)
    if "embed" in lpath or "unembed" in lpath or "lm_head" in lpath:
        # (vocab, d) or (L?, vocab, d): vocab on tp, d on fsdp
        lead = (None,) * (nd - 2)
        return _fit_spec((*lead, tp, dp), shape, mesh)
    if "expert" in lpath and nd >= 3:
        # (L, E, d_in, d_out): experts on tp (EP), d_in on fsdp
        lead = (None,) * (nd - 3)
        return _fit_spec((*lead, tp, dp, None), shape, mesh)
    lead = (None,) * (nd - 2)
    if is_row:
        # §Perf iter-3: down/out projections row-parallel (contraction dim
        # on `model`) so the Megatron column->row pair needs one output
        # psum instead of re-gathering the full hidden activation
        return _fit_spec((*lead, tp, dp), shape, mesh)
    if nd >= 2:
        # (L?, d_in, d_out): fsdp x tp
        return _fit_spec((*lead, dp, tp), shape, mesh)
    return P()


def params_specs(params: Any, mesh=None,
                 policy: ShardingPolicy | None = None) -> Any:
    from repro.optim.common import path_str

    policy = policy or current_policy()
    return jax.tree_util.tree_map_with_path(
        lambda kp, p: param_spec(path_str(kp), p.shape, mesh, policy), params
    )


def named_shardings(tree_of_specs: Any, mesh: Mesh) -> Any:
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree_of_specs,
                        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# Optimizer-state partition specs — derived from the param specs by shape
# matching (DESIGN.md §5): full-size state follows the param; low-rank (…, r)
# keeps the row specs and replicates the rank dim; indices/scalars replicate.
# ---------------------------------------------------------------------------
def _match_state_spec(p_shape, p_spec: P, s_shape) -> P:
    if tuple(s_shape) == tuple(p_shape):
        return p_spec
    # transpose-oriented full-size state (EF buffers are stored oriented)
    if (len(s_shape) == len(p_shape)
            and tuple(s_shape[:-2]) == tuple(p_shape[:-2])
            and (s_shape[-2], s_shape[-1]) == (p_shape[-1], p_shape[-2])):
        sp = list(p_spec) + [None] * (len(p_shape) - len(p_spec))
        sp[-2], sp[-1] = sp[-1], sp[-2]
        return P(*sp)
    # low-rank (..., rows, r): keep leading/row specs, replicate rank dim
    if len(s_shape) == len(p_shape):
        sp = list(p_spec) + [None] * (len(p_shape) - len(p_spec))
        out = []
        for i, (ss, ps) in enumerate(zip(s_shape, p_shape)):
            out.append(sp[i] if ss == ps else None)
        return P(*out)
    if len(s_shape) == len(p_shape) + 1 and tuple(s_shape[:-1]) == tuple(p_shape):
        sp = list(p_spec) + [None] * (len(p_shape) - len(p_spec))
        return P(*sp, None)
    # anything else (indices, scales, scalars): replicate
    return P()


def _axis_size(mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def batch_specs_tree(batch, mesh,
                     policy: ShardingPolicy | None = None) -> Any:
    """Input batch: leading batch dim over the DP axes (if divisible);
    under 'pure_dp' over every mesh axis, falling back to dp-only when the
    batch doesn't divide the full device count (prefill/decode shapes)."""
    policy = policy or current_policy()
    dp_only = dp_axes(mesh) or None
    if policy.layout == "pure_dp":
        all_axes = tuple(a for a in (*dp_axes(mesh), tp_axis(mesh)) if a) \
            or None
        candidates = (all_axes, dp_only)
    else:
        candidates = (dp_only,)

    def spec(x):
        for axes in candidates:
            if axes and x.shape[0] % _axis_size(mesh, axes) == 0:
                return P(axes, *([None] * (len(x.shape) - 1)))
        return P(*([None] * len(x.shape)))

    return jax.tree.map(spec, batch)


def cache_specs_tree(cache, mesh) -> Any:
    """Decode-cache sharding. Leaves are (repeats, B, ...) stacked.

    Rules (DESIGN.md §5): shard batch over DP when divisible; otherwise
    (long-context B=1) shard the *sequence* axis of attention caches over
    DP. KV heads / channel dims go on `model` when divisible; everything
    else replicates.
    """
    dp = dp_axes(mesh) or None
    tp = tp_axis(mesh)
    dp_n = _axis_size(mesh, dp)
    tp_n = _axis_size(mesh, tp) if tp else 1

    def leaf_spec(kp, x):
        name = str(getattr(kp[-1], "key", kp[-1])) if kp else ""
        shp = x.shape
        out = [None] * len(shp)
        b_ok = len(shp) >= 2 and shp[1] % dp_n == 0 and dp is not None
        if b_ok:
            out[1] = dp
        if name in ("k", "v", "xk", "xv"):            # (R,B,S,H,hd)
            if not b_ok and dp is not None and shp[2] % dp_n == 0:
                out[2] = dp                           # sequence-sharded KV
            if tp and shp[3] % tp_n == 0:
                out[3] = tp
        elif name in ("ckv", "krope"):                # (R,B,S,dim) MLA latent
            if not b_ok and dp is not None and shp[2] % dp_n == 0:
                out[2] = dp
        elif name == "conv":                          # (R,B,K,din)
            if tp and shp[3] % tp_n == 0:
                out[3] = tp
        elif name == "ssm":                           # (R,B,din,st)
            if tp and shp[2] % tp_n == 0:
                out[2] = tp
        elif name == "wkv":                           # (R,B,H,K,V)
            if tp and shp[2] % tp_n == 0:
                out[2] = tp
        return P(*out)

    return jax.tree_util.tree_map_with_path(leaf_spec, cache)


def telemetry_specs(tree: Any) -> Any:
    """PartitionSpecs for telemetry pytrees (per-leaf SubspaceStats under
    ``metrics["telemetry"]``, controller state, sink records).

    Stats are per-leaf scalars or (layers,)-vectors produced by full
    reductions over sharded operands — GSPMD already all-reduces them, so
    every leaf replicates; controller state is host-side JSON mirrored to
    tiny arrays at most. One rule, applied uniformly: replicate.
    """
    return jax.tree.map(lambda _: P(), tree)


def opt_state_specs(opt_state, params, p_specs, *, zero=None, mesh=None):
    """PartitionSpecs for an optimizer state given param specs.

    ``params`` drives the association; each per-param state subtree
    (TrionLeaf / ProjAdamLeaf / FullAdamLeaf / ...) is walked and every array
    gets a spec by shape-matching against its parameter.

    Handles both the legacy ``HarnessState`` (``leaves`` is a params-shaped
    tree of per-leaf states) and the transform-chain ``ChainState``
    (``leaves`` nests combinator state: chain tuples, partition dicts whose
    per-label trees are params-shaped with MaskedNode holes,
    inject-hyperparams records). The walk descends combinator containers
    until a params-shaped subtree matches; anything unmatched (hyperparam
    scalars, empty states) replicates.

    ``zero`` (a :class:`repro.parallel.zero.ZeroConfig`) switches eligible
    projected-Adam leaves to the ZeRO-1 placement (DESIGN.md §9): moments,
    EF payloads and per-row EF scales partition their oriented row dim
    over the config's data axes — matching the shard_map layout the
    distributed step runs with — while index sets and scalars replicate.
    Eligibility is basis-agnostic: any leaf whose projector state is an
    index set into a shared basis (every registered
    :class:`~repro.core.transforms.BasisBackend` kind, plus randperm)
    qualifies; ineligible leaves (dense-basis projector state, rows not
    divisible by the shard count) keep the shape-matched placement.
    """
    zinfo = None
    if zero is not None and zero.active:
        from repro.parallel import zero as zero_mod

        mesh = mesh or active_mesh()
        axes = zero_mod.present_axes(mesh, zero)
        n_shards = _axis_size(mesh, axes) if axes else 1
        if n_shards > 1:
            zinfo = (zero_mod, axes, n_shards)

    def _zero_partitioned(p, leaf_state):
        """Leaves the sharded update path claims (DESIGN.md §9/§14):
        ProjAdamLeaf with index-typed projector state, plus the
        momentum-orthogonalization families (muon/trion/dion — always
        shardable by gather-compute-slice), whose rows split evenly."""
        if zinfo is None:
            return False
        from repro.optim.dion import DionLeaf
        from repro.optim.muon import MuonLeaf
        from repro.optim.projected_adam import ProjAdamLeaf
        from repro.optim.trion import TrionLeaf

        zero_mod, axes, n_shards = zinfo
        if not zero_mod.eligible(p.shape, n_shards):
            return False
        if isinstance(leaf_state, (MuonLeaf, TrionLeaf, DionLeaf)):
            return True
        return (isinstance(leaf_state, ProjAdamLeaf)
                and jnp.issubdtype(leaf_state.proj.dtype, jnp.integer))

    def leaf_specs(p, p_spec, leaf_state):
        if _zero_partitioned(p, leaf_state):
            zero_mod, axes, _ = zinfo
            return zero_mod.state_specs(p.shape, leaf_state, axes)
        return jax.tree.map(
            lambda s: _match_state_spec(p.shape, p_spec, s.shape), leaf_state
        )

    def try_params_shaped(node):
        # structural probe only: does `node` flatten up to the params tree?
        try:
            jax.tree_util.tree_structure(params).flatten_up_to(node)
        except (ValueError, TypeError, KeyError):
            return None
        # it does — a failure deriving specs past this point is a real bug
        # and must raise, not silently degrade to replication
        return jax.tree.map(leaf_specs, params, p_specs, node)

    def walk(node):
        mapped = try_params_shaped(node)
        if mapped is not None:
            return mapped
        if isinstance(node, tuple) and hasattr(node, "_fields"):
            return type(node)(*[walk(c) for c in node])
        if isinstance(node, (tuple, list)):
            return type(node)(walk(c) for c in node)
        if isinstance(node, dict):
            return {k: walk(v) for k, v in node.items()}
        return P()

    return type(opt_state)(
        step=P(),
        key=P(),
        bases=jax.tree.map(lambda _: P(), opt_state.bases),
        leaves=walk(opt_state.leaves),
    )
