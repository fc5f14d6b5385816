"""Flash attention Pallas kernels (TPU target): GQA, causal, windowed,
with a fused backward, so attention trains without any score tile in HBM.

Layout. The wrapper moves q to ``(B*Hq, S, hd)`` and k, v to
``(B*Hkv, S, hd)``; q head ``h`` of a batch row reads kv head
``h // G`` (``G = Hq // Hkv``) through the index maps, so K/V are never
repeated in HBM. ``hd`` is the array's whole last dim (96 and 128 here).

Forward (``flash_attention_fwd``). Grid ``(B*Hq, nq, nk)``, kv innermost.
The online-softmax state (fp32 accumulator, running max ``m`` and sum
``l``) stays in VMEM across the kv sweep; the last kv step writes
``O = acc / l`` and the per-row logsumexp ``lse = m + log(l)`` (fp32,
``(B*Hq, 1, S)``: a lane-dense row per q block).

Backward (FlashAttention-2). ``D = rowsum(dO * O)`` is one jnp reduction.
Both kernels recompute ``P = exp(Q K^T * scale - lse)`` block by block
from the residuals ``q, k, v, o, lse``; nothing of size S^2 is stored.

- ``flash_attention_bwd_dkv``: grid ``(B*Hkv, nk, G*nq)``, the q axis
  innermost, walking the G query heads of the kv head's group in turn.
  dK and dV accumulate in VMEM over all of them and are written once.
  It works on the transposed tile ``K Q^T`` (kv rows, q lanes), so
  ``lse`` and ``D`` broadcast as rows and no tile is transposed:
  ``dV += P^T dO``, ``dK += (P^T * (V dO^T - D)) Q``.
- ``flash_attention_bwd_dq``: grid ``(B*Hq, nq, nk)``, kv innermost,
  ``dQ += (P * (dO V^T - D)) K``.

Precision: every dot takes the operands' dtype (bf16 in training) with
fp32 accumulation; P and dS are cast to that dtype before their dots,
as in ``models/layers.py:blockwise_attention``. Softmax statistics,
accumulators and ``lse`` are fp32.

Blocks wholly above the causal diagonal or outside the window are
skipped with ``pl.when``, and their index maps repeat the nearest live
block so that no DMA is issued for them. Only blocks that straddle a
mask edge build the mask.

Validated against ``ref.flash_attention_ref`` and ``blockwise_attention``
in interpret mode, values and gradients (tests/test_kernels.py); compiled
for a described v5e in tests/test_tpu_compile.py.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# Fastest of 256..2048 on one v5e for fwd + bwd at S=4096, hd 96 and 128
# (PERF.md, section 6)
BLOCK_Q = 1024
BLOCK_K = 1024
_LANES = 128
_NT = (((1,), (1,)), ((), ()))         # a @ b.T
_NN = (((1,), (0,)), ((), ()))         # a @ b


class _Cfg(NamedTuple):
    causal: bool
    window: int | None
    bq: int
    bk: int
    interpret: bool


def supports(seq_len: int, block_q: int = BLOCK_Q,
             block_k: int = BLOCK_K) -> bool:
    """Whether the kernels tile a sequence of ``seq_len``: whole lane
    tiles, and whole blocks of both sizes."""
    bq, bk = min(block_q, seq_len), min(block_k, seq_len)
    return seq_len % _LANES == 0 and seq_len % bq == 0 and seq_len % bk == 0


# ---------------------------------------------------------------------------
# block predicates and clamped index maps
# ---------------------------------------------------------------------------
def _live(q0, k0, c: _Cfg):
    """Some (q, k) pair of the block pair is unmasked."""
    live = jnp.bool_(True)
    if c.causal:
        live = jnp.logical_and(live, k0 <= q0 + c.bq - 1)
    if c.window is not None:
        live = jnp.logical_and(live, q0 - (k0 + c.bk - 1) < c.window)
    return live


def _edge(q0, k0, c: _Cfg):
    """Some (q, k) pair of the block pair is masked."""
    edge = jnp.bool_(False)
    if c.causal:
        edge = jnp.logical_or(edge, k0 + c.bk - 1 > q0)
    if c.window is not None:
        edge = jnp.logical_or(edge, q0 + c.bq - 1 - k0 >= c.window)
    return edge


def _kv_block(i, kb, nk: int, c: _Cfg):
    """kv block ``kb`` for q block ``i``, clamped into the live range."""
    if c.window is not None:
        kb = jnp.maximum(kb, jnp.maximum(i * c.bq - c.window + 1, 0) // c.bk)
    if c.causal:
        kb = jnp.minimum(kb, ((i + 1) * c.bq - 1) // c.bk)
    return jnp.minimum(kb, nk - 1)


def _q_block(kb, i, nq: int, c: _Cfg):
    """q block ``i`` for kv block ``kb``, clamped into the live range."""
    if c.causal:
        i = jnp.maximum(i, (kb * c.bk) // c.bq)
    if c.window is not None:
        i = jnp.minimum(i, (kb * c.bk + c.bk - 1 + c.window - 1) // c.bq)
    return jnp.minimum(i, nq - 1)


def _mask(s, q0, k0, c: _Cfg, *, transposed: bool = False):
    """NEG_INF where masked; ``s`` is (bq, bk), or (bk, bq) transposed."""
    rows, cols = s.shape
    r = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 0)
    cc = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 1)
    q_pos, k_pos = (q0 + cc, k0 + r) if transposed else (q0 + r, k0 + cc)
    keep = jnp.ones((rows, cols), jnp.bool_)
    if c.causal:
        keep = jnp.logical_and(keep, q_pos >= k_pos)
    if c.window is not None:
        keep = jnp.logical_and(keep, q_pos - k_pos < c.window)
    return jnp.where(keep, s, NEG_INF)


def _when_live(q0, k0, c: _Cfg, body):
    """Runs ``body(masked)`` on a live block pair: masked only on an edge."""
    if not (c.causal or c.window is not None):
        body(False)
        return
    live, edge = _live(q0, k0, c), _edge(q0, k0, c)
    pl.when(jnp.logical_and(live, jnp.logical_not(edge)))(
        lambda: body(False))
    pl.when(jnp.logical_and(live, edge))(lambda: body(True))


def _col_to_row(x):
    """(n, 1) -> (1, n) through a lane-wide transpose."""
    return jnp.broadcast_to(x, (x.shape[0], _LANES)).T[:1]


def _row_to_col(x):
    """(1, n) -> (n, 1) through a lane-wide transpose."""
    return jnp.broadcast_to(x, (_LANES, x.shape[1])).T[:, :1]


def _params(interpret: bool):
    if interpret:
        return None
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                *, nk: int, scale: float, c: _Cfg):
    i, kb = pl.program_id(1), pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q0, k0 = i * c.bq, kb * c.bk

    def body(masked):
        v = v_ref[0]
        s = jax.lax.dot_general(q_ref[0], k_ref[0], _NT,
                                preferred_element_type=jnp.float32) * scale
        if masked:
            s = _mask(s, q0, k0, c)
        m_prev = m_ref[...]                                   # (bq, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, _NN, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    _when_live(q0, k0, c, body)

    @pl.when(kb == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)
        lse_ref[...] = _col_to_row(m_ref[...] + jnp.log(l))


@functools.partial(jax.jit, static_argnames=("group", "c"))
def flash_attention_fwd(qf, kf, vf, *, group: int, c: _Cfg):
    """qf: (B*Hq, S, hd); kf, vf: (B*Hkv, S, hd). Returns ``o`` like qf
    and ``lse`` (B*Hq, 1, S) fp32."""
    bh, s, hd = qf.shape
    nq, nk = s // c.bq, s // c.bk

    def kv_index(h, i, kb):
        return (h // group, _kv_block(i, kb, nk, c), 0)

    return pl.pallas_call(
        functools.partial(_fwd_kernel, nk=nk, scale=1.0 / math.sqrt(hd),
                          c=c),
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, c.bq, hd), lambda h, i, kb: (h, i, 0)),
            pl.BlockSpec((1, c.bk, hd), kv_index),
            pl.BlockSpec((1, c.bk, hd), kv_index),
        ],
        out_specs=[
            pl.BlockSpec((1, c.bq, hd), lambda h, i, kb: (h, i, 0)),
            pl.BlockSpec((None, 1, c.bq), lambda h, i, kb: (h, 0, i)),
        ],
        out_shape=[jax.ShapeDtypeStruct(qf.shape, qf.dtype),
                   jax.ShapeDtypeStruct((bh, 1, s), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((c.bq, hd), jnp.float32),
                        pltpu.VMEM((c.bq, 1), jnp.float32),
                        pltpu.VMEM((c.bq, 1), jnp.float32)],
        compiler_params=_params(c.interpret),
        interpret=c.interpret,
    )(qf, kf, vf)


# ---------------------------------------------------------------------------
# backward: dK, dV
# ---------------------------------------------------------------------------
def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, d_ref, dk_ref, dv_ref,
                dk_acc, dv_acc, *, nq: int, scale: float, c: _Cfg):
    kb, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q0, k0 = (j % nq) * c.bq, kb * c.bk

    def body(masked):
        q, do = q_ref[0], do_ref[0]
        st = jax.lax.dot_general(k_ref[0], q, _NT,
                                 preferred_element_type=jnp.float32) * scale
        if masked:
            st = _mask(st, q0, k0, c, transposed=True)
        pt = jnp.exp(st - lse_ref[...])                       # (bk, bq)
        dv_acc[...] += jax.lax.dot_general(
            pt.astype(do.dtype), do, _NN, preferred_element_type=jnp.float32)
        dpt = jax.lax.dot_general(v_ref[0], do, _NT,
                                  preferred_element_type=jnp.float32)
        dst = pt * (dpt - d_ref[...])
        dk_acc[...] += jax.lax.dot_general(
            dst.astype(q.dtype), q, _NN, preferred_element_type=jnp.float32)

    _when_live(q0, k0, c, body)

    @pl.when(j == pl.num_programs(2) - 1)
    def _finalize():
        dk_ref[0] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnames=("group", "c"))
def flash_attention_bwd_dkv(qf, kf, vf, do, lse, d, *, group: int, c: _Cfg):
    """dK, dV (like kf, vf), each summed over its group's query heads."""
    bh, s, hd = kf.shape
    nq, nk = s // c.bq, s // c.bk

    def q_index(h, kb, j):
        return (h * group + j // nq, _q_block(kb, j % nq, nq, c), 0)

    def row_index(h, kb, j):
        return (h * group + j // nq, 0, _q_block(kb, j % nq, nq, c))

    q_spec = pl.BlockSpec((1, c.bq, hd), q_index)
    kv_spec = pl.BlockSpec((1, c.bk, hd), lambda h, kb, j: (h, kb, 0))
    row_spec = pl.BlockSpec((None, 1, c.bq), row_index)
    return pl.pallas_call(
        functools.partial(_dkv_kernel, nq=nq, scale=1.0 / math.sqrt(hd),
                          c=c),
        grid=(bh, nk, group * nq),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=[kv_spec, kv_spec],
        out_shape=[jax.ShapeDtypeStruct(kf.shape, kf.dtype),
                   jax.ShapeDtypeStruct(vf.shape, vf.dtype)],
        scratch_shapes=[pltpu.VMEM((c.bk, hd), jnp.float32),
                        pltpu.VMEM((c.bk, hd), jnp.float32)],
        compiler_params=_params(c.interpret),
        interpret=c.interpret,
    )(qf, kf, vf, do, lse, d)


# ---------------------------------------------------------------------------
# backward: dQ
# ---------------------------------------------------------------------------
def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, d_ref, dq_ref,
               dq_acc, lse_col, d_col, *, nk: int, scale: float, c: _Cfg):
    i, kb = pl.program_id(1), pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)
        lse_col[...] = _row_to_col(lse_ref[...])
        d_col[...] = _row_to_col(d_ref[...])

    q0, k0 = i * c.bq, kb * c.bk

    def body(masked):
        k = k_ref[0]
        s = jax.lax.dot_general(q_ref[0], k, _NT,
                                preferred_element_type=jnp.float32) * scale
        if masked:
            s = _mask(s, q0, k0, c)
        p = jnp.exp(s - lse_col[...])                         # (bq, bk)
        dp = jax.lax.dot_general(do_ref[0], v_ref[0], _NT,
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - d_col[...])
        dq_acc[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, _NN, preferred_element_type=jnp.float32)

    _when_live(q0, k0, c, body)

    @pl.when(kb == nk - 1)
    def _finalize():
        dq_ref[0] = (dq_acc[...] * scale).astype(dq_ref.dtype)


@functools.partial(jax.jit, static_argnames=("group", "c"))
def flash_attention_bwd_dq(qf, kf, vf, do, lse, d, *, group: int, c: _Cfg):
    """dQ, like qf."""
    bh, s, hd = qf.shape
    nq, nk = s // c.bq, s // c.bk

    def kv_index(h, i, kb):
        return (h // group, _kv_block(i, kb, nk, c), 0)

    q_spec = pl.BlockSpec((1, c.bq, hd), lambda h, i, kb: (h, i, 0))
    kv_spec = pl.BlockSpec((1, c.bk, hd), kv_index)
    row_spec = pl.BlockSpec((None, 1, c.bq), lambda h, i, kb: (h, 0, i))
    return pl.pallas_call(
        functools.partial(_dq_kernel, nk=nk, scale=1.0 / math.sqrt(hd), c=c),
        grid=(bh, nq, nk),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(qf.shape, qf.dtype),
        scratch_shapes=[pltpu.VMEM((c.bq, hd), jnp.float32),
                        pltpu.VMEM((c.bq, 1), jnp.float32),
                        pltpu.VMEM((c.bq, 1), jnp.float32)],
        compiler_params=_params(c.interpret),
        interpret=c.interpret,
    )(qf, kf, vf, do, lse, d)


# ---------------------------------------------------------------------------
# the differentiable op
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash(qf, kf, vf, group: int, c: _Cfg):
    return flash_attention_fwd(qf, kf, vf, group=group, c=c)[0]


def _flash_vjp_fwd(qf, kf, vf, group, c):
    o, lse = flash_attention_fwd(qf, kf, vf, group=group, c=c)
    return o, (qf, kf, vf, o, lse)


def _flash_vjp_bwd(group, c, res, do):
    qf, kf, vf, o, lse = res
    d = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)
    d = d[:, None, :]                                         # (B*Hq, 1, S)
    dk, dv = flash_attention_bwd_dkv(qf, kf, vf, do, lse, d, group=group,
                                     c=c)
    dq = flash_attention_bwd_dq(qf, kf, vf, do, lse, d, group=group, c=c)
    return dq, dk, dv


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def _heads_major(x):
    b, s, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "block_q", "block_k", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int | None = None,
                    block_q: int = BLOCK_Q, block_k: int = BLOCK_K,
                    interpret: bool = False) -> jax.Array:
    """q: (B, S, Hq, hd); k, v: (B, S, Hkv, hd) of q's dtype, with
    Hq % Hkv == 0. Returns (B, S, Hq, hd) in q.dtype; differentiable in
    q, k and v. S must satisfy :func:`supports` for the blocks."""
    b, s, hq, hd = q.shape
    hkv = k.shape[2]
    assert hq % hkv == 0 and k.shape == v.shape == (b, s, hkv, hd)
    bq, bk = min(block_q, s), min(block_k, s)
    assert s % bq == 0 and s % bk == 0, (s, bq, bk)
    c = _Cfg(causal, window, bq, bk, interpret)
    out = _flash(_heads_major(q), _heads_major(k), _heads_major(v),
                 hq // hkv, c)
    return out.reshape(b, hq, s, hd).transpose(0, 2, 1, 3)
