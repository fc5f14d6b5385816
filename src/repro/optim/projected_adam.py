"""Generic low-rank projected AdamW — one rule, five optimizers.

This is the plug point the paper argues for: the projector (DCT dynamic
column selection vs SVD vs block power iteration vs random/randperm) is a
swappable component inside an otherwise identical low-rank Adam(W):

  optimizer   projector   T_u    rotate   residual handling
  ---------   ---------   ----   ------   -----------------
  DCT-AdamW   dct         any    yes      error feedback (fp32 or int8)
  LDAdamW     power       1      yes      error feedback (optional)
  GaLore      svd         200    no       discarded
  FRUGAL      svd/dct/..  200    no       SignSGD on the state-free part
  FIRA        svd/dct     200    no       norm-scaled pass-through

Per 2D leaf (oriented so the projected dim is last, size n <= m):
    G_t  = grad (+ EF buffer)
    refresh (every T_u steps): new indices/basis from G_t; rotation
        R = Q_prev^T Q_crt applied to m, v (|.| on v) — for index-based
        projectors R is a 0/1 partial permutation (DESIGN.md §1)
    g_t  = G_t @ Q_crt                      (m x r)
    Xi   = G_t - g_t Q_crt^T                (residual; see table)
    m, v = Adam moments on g_t; u = mhat / (sqrt(vhat) + eps)
    D    = u @ Q_crt^T (+ residual term)

Execution dispatch (``fused`` field, DESIGN.md §3): for every
predefined-basis projector (a registered
:class:`~repro.core.transforms.BasisBackend`: dct/dst/hadamard/randortho)
the hot path runs through core/fused_step.py — one fused select+project
pass over G (g_t extracted from S, no second matmul), one shared Q_r^T
gather for both back-projections, and int8 EF consumed/produced by fused
quantize kernels. "off" is the bit-identical seed reference path.
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.core import fused_step
from repro.core.error_feedback import QuantizedBuffer, zeros_q8
from repro.core.projectors import (
    Projector,
    projector_kinds,
    rotation_matrix,
)
from repro.core.selection import index_overlap, topr_margin
from repro.core.transforms import get_backend, is_backend
from repro.telemetry import stats as tstats

from .common import (
    MatrixRule,
    Optimizer,
    Schedule,
    deorient,
    orient_right,
    oriented_dims,
)
from .transform import (
    GradientTransform,
    add_decayed_weights,
    chain,
    lowrank_project,
    matrix_optimizer,
    scale_by_learning_rate,
)

RESIDUAL_MODES = ("ef", "discard", "sign", "fira")
EF_DTYPES = ("q8", "fp32")
RANKING_NORMS = ("l1", "l2")


class ProjAdamLeaf(NamedTuple):
    m: jax.Array            # (..., rows, r) first moment, low-rank
    v: jax.Array            # (..., rows, r) second moment, low-rank
    proj: Any               # projector state (indices or basis)
    ef: Any                 # None | fp32 array | QuantizedBuffer
    inner_step: jax.Array   # steps since last subspace refresh (bias corr.)


@dataclasses.dataclass(frozen=True)
class ProjectedAdamRule(MatrixRule):
    rank: int = 128
    projector: str = "dct"
    update_interval: int = 1          # T_u
    rotate: bool = True
    residual: str = "ef"              # "ef" | "discard" | "sign" | "fira"
    ef_dtype: str = "q8"              # "fp32" | "q8" (when residual == "ef")
    b1: float = 0.9
    b2: float = 0.999
    eps: float = 1e-8
    ranking_norm: str = "l2"
    exact_rotation_matmul: bool = False   # paper-literal R via matmul
    needs_shared_basis: bool = True       # harness stores DCT bases if needed
    fused: str = "auto"                   # fused-step dispatch (DESIGN.md §3):
    #   "auto" (kernels on TPU, reference elsewhere) | "on" (Pallas kernels,
    #   interpret off-TPU) | "fft" (Makhoul host fast path) | "off" (seed jnp)
    emit_stats: bool = True               # emit SubspaceStats when a
    #   telemetry collector is installed (DESIGN.md §8). With no collector
    #   the traced graph is identical either way; False opts this rule out
    #   even under an active collector.
    compute_dtype: str = "fp32"           # projection-matmul precision
    #   (DESIGN.md §15): "fp32" (bit-identical default) | "bf16" | "int8"
    #   (per-row/column scales folded into the epilogue). Applies to the
    #   select+project pass and both back-projections on the fused modes
    #   only — the reference path has no lowp mirror, so a non-fp32 dtype
    #   with fused="off" (eager), or resolving to the reference path at
    #   trace time (fused="auto" off-TPU, dense-basis projectors), raises
    #   instead of silently running fp32. Error vs fp32 bounded by
    #   fused_step.LOWP_ERROR_BOUNDS (gated in
    #   benchmarks/projection_errors.py).

    def __post_init__(self):
        """Eager config validation: fail at construction with the allowed
        values, not deep inside the jit trace. Only static (string/int)
        fields are checked so floats may be tracers (inject_hyperparams)."""
        def check(name, value, allowed):
            if value not in allowed:
                raise ValueError(f"{type(self).__name__}: unknown {name} "
                                 f"{value!r}; allowed: {allowed}")

        check("projector", self.projector, projector_kinds())
        check("residual", self.residual, RESIDUAL_MODES)
        check("ef_dtype", self.ef_dtype, EF_DTYPES)
        check("ranking_norm", self.ranking_norm, RANKING_NORMS)
        check("fused", self.fused, fused_step.FUSED_MODES)
        check("compute_dtype", self.compute_dtype, fused_step.COMPUTE_DTYPES)
        if self.compute_dtype != "fp32" and self.fused == "off":
            raise ValueError(
                f"{type(self).__name__}: compute_dtype={self.compute_dtype!r} "
                "requires the fused dataflow (fused='on'/'fft'); the fused"
                "='off' reference path has no low-precision mirror and would "
                "silently run fp32")
        if isinstance(self.rank, int) and self.rank < 1:
            raise ValueError(f"rank must be >= 1, got {self.rank}")
        if isinstance(self.update_interval, int) and self.update_interval < 1:
            raise ValueError(
                f"update_interval must be >= 1, got {self.update_interval}")

    @property
    def zero_shardable(self) -> bool:
        """Index-into-shared-basis projectors keep only ``r`` integers of
        projector state and their whole step is row-parallel given one
        psum'd column statistic — the ZeRO-1 precondition (DESIGN.md §9).
        Any registered basis backend with a row-decomposable energy
        statistic (``backend.zero_shardable``) qualifies, as does the
        identity-basis randperm; dense-basis refreshes (svd) need all
        rows and stay replicated.

        The FIRA residual is also excluded: its ``phi`` scaling feeds
        psum'd norms into the *update arithmetic* (not just ranking), and
        a psum of per-shard partial sums rounds differently than the
        replicated single-pass reduction — it would break the bit-exact
        sharded/replicated contract the parity suite pins."""
        if self.residual == "fira":
            return False
        if is_backend(self.projector):
            return get_backend(self.projector).zero_shardable
        return self.projector == "randperm"

    def _proj(self):
        return Projector(kind=self.projector, r=self.rank, norm=self.ranking_norm)

    def basis_sizes(self, shape) -> tuple:
        """The shared basis this leaf needs: ``(kind, n)`` at the min
        oriented dim (bare ``n`` for dct — the legacy store key). Dense
        projector kinds (svd/power/random/randperm) request nothing, even
        when ``needs_shared_basis`` was left True on the rule."""
        if not is_backend(self.projector):
            return ()
        n = oriented_dims(shape)[1]
        return ((self.projector, n),) if self.projector != "dct" else (n,)

    def init(self, shape, dtype):
        *batch, _, _ = shape
        rows, cols = oriented_dims(shape)
        r = min(self.rank, cols)
        p = self._proj()
        # m and v must be distinct buffers (donation aliases leaves 1:1)
        mz = jnp.zeros((*batch, rows, r), jnp.float32)
        vz = jnp.zeros((*batch, rows, r), jnp.float32)
        if self.residual == "ef":
            orient_shape = (*batch, rows, cols)
            ef = (zeros_q8(orient_shape) if self.ef_dtype == "q8"
                  else jnp.zeros(orient_shape, jnp.float32))
        else:
            ef = None
        return ProjAdamLeaf(m=mz, v=vz, proj=p.init((*batch, rows, cols)),
                            ef=ef, inner_step=jnp.zeros((), jnp.int32))

    def update(self, g, state, param, ctx):
        p = self._proj()
        if ctx.oriented:        # ZeRO row block: already right-oriented
            gf, transposed = g.astype(jnp.float32), False
        else:
            gf, transposed = orient_right(g.astype(jnp.float32))
        rows, cols = gf.shape[-2], gf.shape[-1]
        r = min(self.rank, cols)
        backend = get_backend(self.projector) if is_backend(self.projector) \
            else None
        q = (ctx.basis(cols, jnp.float32, kind=self.projector)
             if p.needs_shared_basis else None)
        mode = fused_step.resolve(self.fused)
        # the fused dataflow exists for the index-into-shared-basis
        # projectors (any registered basis backend); dense-basis kinds keep
        # the reference math (EF still goes fused)
        fused = mode != "off" and backend is not None
        if self.compute_dtype != "fp32" and not fused:
            # only the fused dataflow has the lowp mirror; refuse loudly
            # instead of silently running fp32 (reachable past __post_init__
            # via fused="auto" resolving to "off", or a dense-basis
            # projector)
            raise ValueError(
                f"compute_dtype={self.compute_dtype!r} needs the fused "
                f"dataflow, but this update resolved to the reference path "
                f"(fused={self.fused!r} -> mode={mode!r}, "
                f"projector={self.projector!r}); pass fused='on'/'fft' with "
                "a registered basis backend")

        # name scopes (docs/observability.md, "Device scopes"): the
        # caller's opt/update holds the orientation and casts; each phase
        # below names its own device ops
        if state.ef is not None:
            with jax.named_scope("opt/ef"):
                gf = fused_step.ef_add(gf, state.ef, mode=mode)

        def eye_rot():
            eye = jnp.eye(r, dtype=jnp.float32)
            return jnp.broadcast_to(eye, (*gf.shape[:-2], r, r))

        # telemetry (DESIGN.md §8): both cond branches append a small aux
        # tuple (margin, overlap, total energy) so per-step stats ride the
        # existing control flow. With no collector installed (want_stats
        # False) nothing is appended and the graph is unchanged. On the
        # fused refresh path the total comes from the already-reduced
        # column norms (||S||_F^2 == ||G||_F^2, Q orthogonal) — zero extra
        # G-sized work; elsewhere it is one reduction fused into reads of
        # gf the step performs anyway.
        want_stats = ctx.wants_stats and self.emit_stats
        need_resid = self.residual != "discard"
        idx_based = p.index_based
        batch = gf.shape[:-2]

        def keep_aux(g_low):
            # keep step: no selection happened, so neither margin nor
            # overlap is a measurement — both report the -1 sentinel
            # (consumers gate on >= 0). Col energies from the skinny g_low
            # (an (m, r) reduction). Row reductions psum across ZeRO
            # shards (ctx.axis; identity when replicated).
            return (-jnp.ones(batch, jnp.float32),
                    -jnp.ones(batch, jnp.float32),
                    ctx.psum(jnp.sum(gf * gf, axis=(-2, -1))),
                    None if g_low is None
                    else ctx.psum(jnp.sum(g_low * g_low, axis=-2)))

        def refresh_aux(new_proj, norms_sq):
            margin = (topr_margin(norms_sq, r) if norms_sq is not None
                      else -jnp.ones(batch, jnp.float32))
            overlap = (index_overlap(state.proj, new_proj) if idx_based
                       else -jnp.ones(batch, jnp.float32))
            # the barrier pins this tiny (n,) -> () reduction to the
            # already-materialized norms: without it XLA re-derives the sum
            # from the G-sized squared-S, an extra full read of S that the
            # ≤3% overhead gate (telemetry_overhead bench) catches
            total = (jnp.sum(jax.lax.optimization_barrier(norms_sq),
                             axis=-1) if norms_sq is not None
                     else ctx.psum(jnp.sum(gf * gf, axis=(-2, -1))))
            # selected column energies ||G q_i||^2 == norms_sq[idx]: a free
            # (n,) -> (r,) gather of the already-reduced ranking statistic,
            # NOT a fresh reduction over S/g_low (that extra S-sized read
            # is exactly what the ≤3% overhead gate caught)
            col_e = (None if norms_sq is None else
                     jnp.take_along_axis(norms_sq, new_proj, axis=-1))
            return (margin, overlap, total, col_e)

        if fused:
            # refresh folds selection AND projection into one pass over G:
            # g_low falls out of S (Alg. 1 line 8), so both branches return it
            def refresh(_):
                with jax.named_scope("opt/select_project"):
                    sp = fused_step.select_and_project(
                        gf, q, r, norm=self.ranking_norm, mode=mode,
                        return_norms=want_stats, psum_axes=ctx.axis,
                        backend=backend, compute_dtype=self.compute_dtype)
                new_proj, g_low = sp[0], sp[1]
                out = (new_proj, g_low)
                if self.rotate:
                    with jax.named_scope("opt/adam"):
                        rot = rotation_matrix(
                            state.proj, new_proj, p, cols, shared_q=q,
                            exact_matmul=self.exact_rotation_matmul)
                    out = (new_proj, rot, g_low)
                return out + ((refresh_aux(new_proj, sp[2]),) if want_stats
                              else ())

            def keep(_):
                with jax.named_scope("opt/select_project"):
                    g_low = fused_step.project_with_indices(
                        gf, q, state.proj, compute_dtype=self.compute_dtype)
                out = ((state.proj, g_low) if not self.rotate
                       else (state.proj, eye_rot(), g_low))
                return out + ((keep_aux(g_low),) if want_stats else ())
        else:
            def refresh(_):
                with jax.named_scope("opt/select_project"):
                    new_proj = p.update(gf, state.proj, shared_q=q,
                                        key=ctx.key, psum_axes=ctx.axis)
                out = (new_proj,)
                if self.rotate:
                    with jax.named_scope("opt/adam"):
                        rot = rotation_matrix(
                            state.proj, new_proj, p, cols, shared_q=q,
                            exact_matmul=self.exact_rotation_matmul)
                    out = (new_proj, rot)
                return out + ((refresh_aux(new_proj, None),) if want_stats
                              else ())

            def keep(_):
                out = ((state.proj,) if not self.rotate
                       else (state.proj, eye_rot()))
                return out + ((keep_aux(None),) if want_stats else ())

        if self.update_interval == 1:
            out = refresh(None)
        else:
            do_refresh = (ctx.step % self.update_interval == 1) | (ctx.step == 1)
            out = jax.lax.cond(do_refresh, refresh, keep, None)
        proj_state = out[0]
        stats_aux = out[-1] if want_stats else None

        if fused:
            g_low = out[2 if self.rotate else 1]                # (..., rows, r)
        else:
            with jax.named_scope("opt/select_project"):
                g_low = p.project(gf, proj_state, shared_q=q)   # (.., rows, r)

        with jax.named_scope("opt/adam"):
            if self.rotate:
                rot = out[1]
                m_prev = jnp.einsum("...mr,...rs->...ms", state.m, rot)
                v_prev = jnp.abs(jnp.einsum("...mr,...rs->...ms", state.v,
                                            rot))
            else:
                m_prev, v_prev = state.m, state.v
            inner = state.inner_step + 1

            m = self.b1 * m_prev + (1.0 - self.b1) * g_low
            v = self.b2 * v_prev + (1.0 - self.b2) * g_low * g_low
            t = inner.astype(jnp.float32)
            mhat = m / (1.0 - self.b1**t)
            vhat = v / (1.0 - self.b2**t)
            u_low = mhat / (jnp.sqrt(vhat) + self.eps)

        with jax.named_scope("opt/backproject"):
            if fused:
                if need_resid:
                    d, recon = fused_step.fused_dual_backproject(
                        u_low, g_low, q, proj_state, mode=mode,
                        compute_dtype=self.compute_dtype)
                    resid = gf - recon
                else:
                    d = fused_step.fused_backproject(
                        u_low, q, proj_state, mode=mode,
                        compute_dtype=self.compute_dtype)
            else:
                d = p.backproject(u_low, proj_state, shared_q=q, n=cols)
                if need_resid:
                    resid = gf - p.backproject(g_low, proj_state, shared_q=q,
                                               n=cols)

        new_ef = state.ef
        if need_resid:
            if self.residual == "ef":
                with jax.named_scope("opt/ef"):
                    new_ef = fused_step.ef_store(resid, self.ef_dtype,
                                                 mode=mode)
            elif self.residual == "sign":
                d = d + jnp.sign(resid)                         # FRUGAL state-free
            elif self.residual == "fira":
                # sqrt-of-psum'd-square-sums == jnp.linalg.norm when
                # unsharded; under ZeRO the norms span all row shards
                u_n = jnp.sqrt(ctx.psum(
                    jnp.sum(u_low * u_low, axis=(-2, -1), keepdims=True)))
                g_n = jnp.sqrt(ctx.psum(
                    jnp.sum(g_low * g_low, axis=(-2, -1), keepdims=True)))
                d = d + (u_n / (g_n + self.eps)) * resid        # FIRA scaling

        if want_stats:
            # every term is resident already: selected column energies and
            # total energy from the branch aux, and the residual mass from
            # the exact orthogonal split ||Xi||^2 = ||G||^2 - ||g_low||^2 —
            # never a reduction over the materialized residual
            col_e = stats_aux[3]                                # (..., r)
            if col_e is None:      # reference path: reduce the skinny g_low
                col_e = ctx.psum(jnp.sum(g_low * g_low, axis=-2))
            sel_sq = jnp.sum(col_e, axis=-1)
            total_sq = stats_aux[2]
            if self.residual == "ef":
                ef_norm = jnp.sqrt(jnp.maximum(total_sq - sel_sq, 0.0))
            else:
                ef_norm = jnp.zeros(batch, jnp.float32)
            ctx.record_stats(tstats.SubspaceStats(
                captured_energy=tstats.captured_energy(sel_sq, total_sq),
                topr_margin=stats_aux[0],
                index_overlap=stats_aux[1],
                ef_norm=ef_norm,
                rank_utilization=tstats.rank_utilization(col_e)))

        d = deorient(d, transposed)
        return d, ProjAdamLeaf(m=m, v=v, proj=proj_state, ef=new_ef,
                               inner_step=inner)


def _rule(rule_kw) -> ProjectedAdamRule:
    rule_kw.setdefault("needs_shared_basis",
                       is_backend(rule_kw.get("projector")))
    return ProjectedAdamRule(**rule_kw)


def _build(lr, rule_kw, harness_kw) -> Optimizer:
    rule = _rule(rule_kw)
    return matrix_optimizer(rule, lr, b1=rule.b1, b2=rule.b2, eps=rule.eps,
                            **harness_kw)


def projected_adam_transform(rule: ProjectedAdamRule, lr: Schedule, *,
                             weight_decay: float = 0.0,
                             overrides: dict[str, dict] | None = None
                             ) -> GradientTransform:
    """Matrix-leaf projected-Adam pipeline (rule -> -lr -> decay) for use
    inside ``partition`` (e.g. dct-adamw-on-attention + muon-on-mlp)."""
    return chain(lowrank_project(rule, overrides=overrides),
                 scale_by_learning_rate(lr),
                 add_decayed_weights(weight_decay, schedule=lr))


def dct_adamw_transform(lr: Schedule, *, rank: int = 128,
                        update_interval: int = 1, weight_decay: float = 0.01,
                        error_feedback: bool = True, ef_dtype: str = "q8",
                        b1: float = 0.9, b2: float = 0.999, eps: float = 1e-8,
                        fused: str = "auto", basis: str = "dct",
                        compute_dtype: str = "fp32",
                        overrides: dict | None = None) -> GradientTransform:
    """Matrix-leaf DCT-AdamW pipeline for ``partition``/``inject_hyperparams``.
    ``basis`` swaps the predefined orthogonal basis (any registered
    backend: dct/dst/hadamard/randortho — docs/transforms.md)."""
    rule = _rule(dict(rank=rank, projector=basis,
                      update_interval=update_interval, rotate=True,
                      residual="ef" if error_feedback else "discard",
                      ef_dtype=ef_dtype, b1=b1, b2=b2, eps=eps, fused=fused,
                      compute_dtype=compute_dtype))
    return projected_adam_transform(rule, lr, weight_decay=weight_decay,
                                    overrides=overrides)


def dct_adamw(lr: Schedule, *, rank: int = 128, update_interval: int = 1,
              weight_decay: float = 0.01, error_feedback: bool = True,
              ef_dtype: str = "q8", b1: float = 0.9, b2: float = 0.999,
              eps: float = 1e-8, exact_rotation_matmul: bool = False,
              fused: str = "auto", basis: str = "dct",
              compute_dtype: str = "fp32", basis_mode: str = "stored",
              label_fn=None, overrides: dict | None = None,
              zero=None, lr_scale: bool = False) -> Optimizer:
    """The paper's DCT-AdamW (Algorithm 2). ``fused`` selects the execution
    layer: "auto" | "on" (Pallas kernels) | "fft" (the backend's fast
    transform: Makhoul FFT for dct, FHT for hadamard) | "off" (jnp
    reference) — see core/fused_step.py / DESIGN.md §3.
    ``basis``: the predefined orthogonal basis — any registered
    :class:`~repro.core.transforms.BasisBackend` kind
    (dct/dst/hadamard/randortho); the whole fused/ZeRO/telemetry stack is
    basis-agnostic (DESIGN.md §10).
    ``overrides``: per-leaf-path rule field overrides (e.g. per-layer ranks
    from the adaptive rank allocator, DESIGN.md §8)."""
    if not is_backend(basis):
        from repro.core.transforms import backend_kinds
        raise ValueError(f"unknown basis {basis!r}; registered backends: "
                         f"{backend_kinds()}")
    hk = dict(weight_decay=weight_decay, basis_mode=basis_mode,
              overrides=overrides, zero=zero, lr_scale=lr_scale)
    if label_fn is not None:
        hk["label_fn"] = label_fn
    return _build(lr, dict(rank=rank, projector=basis,
                           update_interval=update_interval, rotate=True,
                           residual="ef" if error_feedback else "discard",
                           ef_dtype=ef_dtype, b1=b1, b2=b2, eps=eps,
                           exact_rotation_matmul=exact_rotation_matmul,
                           fused=fused, compute_dtype=compute_dtype), hk)


def ldadamw(lr: Schedule, *, rank: int = 128, weight_decay: float = 0.01,
            error_feedback: bool = True, b1: float = 0.9, b2: float = 0.999,
            eps: float = 1e-8, fused: str = "auto", label_fn=None,
            overrides: dict | None = None, zero=None,
            lr_scale: bool = False) -> Optimizer:
    """LDAdamW baseline: block power iteration, per-step subspace, rotation
    via real r x r matmul of two stored projection matrices. ``fused``
    covers the EF quantize/dequant kernels (the power projector itself
    keeps the reference math)."""
    hk = dict(weight_decay=weight_decay, overrides=overrides, zero=zero,
              lr_scale=lr_scale)
    if label_fn is not None:
        hk["label_fn"] = label_fn
    return _build(lr, dict(rank=rank, projector="power", update_interval=1,
                           rotate=True,
                           residual="ef" if error_feedback else "discard",
                           ef_dtype="fp32", b1=b1, b2=b2, eps=eps,
                           fused=fused), hk)


def galore(lr: Schedule, *, rank: int = 128, update_interval: int = 200,
           weight_decay: float = 0.01, projector: str = "svd",
           b1: float = 0.9, b2: float = 0.999, eps: float = 1e-8,
           fused: str = "auto", label_fn=None,
           overrides: dict | None = None, zero=None,
           lr_scale: bool = False) -> Optimizer:
    """GaLore baseline: SVD every T_u steps, residual discarded, no rotation."""
    hk = dict(weight_decay=weight_decay, overrides=overrides, zero=zero,
              lr_scale=lr_scale)
    if label_fn is not None:
        hk["label_fn"] = label_fn
    return _build(lr, dict(rank=rank, projector=projector,
                           update_interval=update_interval, rotate=False,
                           residual="discard", b1=b1, b2=b2, eps=eps,
                           fused=fused), hk)


def frugal(lr: Schedule, *, rank: int = 128, update_interval: int = 200,
           weight_decay: float = 0.01, projector: str = "svd",
           b1: float = 0.9, b2: float = 0.999, eps: float = 1e-8,
           fused: str = "auto", label_fn=None,
           overrides: dict | None = None, zero=None,
           lr_scale: bool = False) -> Optimizer:
    """FRUGAL baseline: state-full low-rank AdamW + state-free SignSGD on the
    residual. ``projector`` in {svd, random, randperm} or any registered
    basis-backend kind (dct/dst/hadamard/randortho — paper Table 6)."""
    hk = dict(weight_decay=weight_decay, overrides=overrides, zero=zero,
              lr_scale=lr_scale)
    if label_fn is not None:
        hk["label_fn"] = label_fn
    return _build(lr, dict(rank=rank, projector=projector,
                           update_interval=update_interval, rotate=False,
                           residual="sign", b1=b1, b2=b2, eps=eps,
                           fused=fused), hk)


def fira(lr: Schedule, *, rank: int = 128, update_interval: int = 200,
         weight_decay: float = 0.01, projector: str = "svd",
         b1: float = 0.9, b2: float = 0.999, eps: float = 1e-8,
         fused: str = "auto", label_fn=None,
         overrides: dict | None = None, zero=None,
         lr_scale: bool = False) -> Optimizer:
    """FIRA baseline: low-rank AdamW + norm-scaled full-rank residual."""
    hk = dict(weight_decay=weight_decay, overrides=overrides, zero=zero,
              lr_scale=lr_scale)
    if label_fn is not None:
        hk["label_fn"] = label_fn
    return _build(lr, dict(rank=rank, projector=projector,
                           update_interval=update_interval, rotate=False,
                           residual="fira", b1=b1, b2=b2, eps=eps,
                           fused=fused), hk)
