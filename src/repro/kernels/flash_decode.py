"""Paged flash-decode Pallas kernel (TPU target).

Single-query attention for continuous-batching decode: each sequence's
K/V live scattered across fixed-size blocks of a global pool
(serve/kv_cache.py), addressed by a per-slot block table. The kernel
gathers K/V *through the table* via the BlockSpec index maps — the
scalar-prefetched ``block_table`` is available before the body runs, so
each grid step DMAs exactly one pool block into VMEM; the paged cache
is never densified in HBM.

Structure (mirrors ``flash_attention.py``):

  * GQA head-grouping — q is laid out ``(B, Hkv, group, hd)``. Every
    grid row is one slot: it DMAs a whole ``(bs, Hkv, hd)`` pool block
    once and, in a static loop over the kv heads, attends that head's
    ``group`` query heads against it (the same ``q_head // group``
    folding as the prefill kernel). A block of the whole head axis is
    what the TPU lowering accepts: its last two dims equal the pool's.
  * Split-KV parallelism — the block-table walk is split into
    ``num_splits`` *parallel* grid rows, each producing an unnormalized
    partial ``(acc, m, l)`` online-softmax state over its share of the
    cache blocks; a tiny jnp epilogue merges the splits with the
    standard max-shift algebra. Within a split the walk is the
    innermost (sequential) grid dimension with the accumulator resident
    in VMEM, exactly like the prefill kernel's KV sweep.
  * Blocks entirely past a slot's ``length`` (or entirely outside its
    sliding window) are skipped with ``pl.when`` — no DMA'd garbage is
    ever computed on, which is also what makes a slot's output
    bit-independent of whatever other sequences occupy the pool.

``lengths[b] == 0`` (an inactive scheduler slot) produces a zero output
row rather than NaN: the merge guards the empty-softmax case.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(table_ref, lengths_ref, q_ref, k_ref, v_ref,
            o_ref, m_ref, l_ref, acc_ref, ms_ref, ls_ref, *,
            hkv: int, bps: int, bs: int, group: int,
            window: int | None, scale: float):
    b = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        ms_ref[...] = jnp.full_like(ms_ref, NEG_INF)
        ls_ref[...] = jnp.zeros_like(ls_ref)

    blk = pl.program_id(0) * bps + j        # global block-table column
    start = blk * bs
    length = lengths_ref[b]
    run = start < length
    if window is not None:
        run = jnp.logical_and(run, start + bs - 1 >= length - window)

    @pl.when(run)
    def _block():
        k_pos = start + jax.lax.broadcasted_iota(jnp.int32, (group, bs), 1)
        mask = k_pos < length
        if window is not None:
            mask = jnp.logical_and(mask, k_pos >= length - window)
        for h in range(hkv):                             # static head loop
            q = q_ref[0, h].astype(jnp.float32)          # (group, hd)
            k = k_ref[0, :, h, :].astype(jnp.float32)    # (bs, hd)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = jnp.where(mask, s * scale, NEG_INF)

            m_prev = ms_ref[h]                           # (group, 1)
            m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            ls_ref[h] = ls_ref[h] * corr + p.sum(axis=1, keepdims=True)
            v = v_ref[0, :, h, :].astype(jnp.float32)    # (bs, hd)
            acc_ref[h] = acc_ref[h] * corr + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            ms_ref[h] = m_new

    @pl.when(j == bps - 1)
    def _finalize():
        o_ref[0, 0] = acc_ref[...]
        m_ref[0, 0] = ms_ref[...]
        l_ref[0, 0] = ls_ref[...]


@functools.partial(jax.jit, static_argnames=(
    "window", "num_splits", "interpret"))
def flash_decode(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                 block_table: jax.Array, lengths: jax.Array, *,
                 window: int | None = None, num_splits: int = 1,
                 interpret: bool = False) -> jax.Array:
    """Paged single-query attention.

    q: (B, Hq, hd); k_pool/v_pool: (NB, bs, Hkv, hd); block_table:
    (B, MAXB) int32 pool-block ids (unused entries must be in-range,
    conventionally 0); lengths: (B,) int32 valid tokens per slot
    (0 = inactive slot -> zero output). ``Hq % Hkv == 0``. Splits the
    MAXB-entry table walk into ``num_splits`` parallel partials (MAXB
    is right-padded to a multiple). Returns (B, Hq, hd) in q.dtype.
    """
    b, hq, hd = q.shape
    nb, bs, hkv, hd_k = k_pool.shape
    assert hd_k == hd and v_pool.shape == k_pool.shape, (q.shape, k_pool.shape)
    assert hq % hkv == 0, (hq, hkv)
    assert block_table.shape[0] == b and lengths.shape == (b,)
    group = hq // hkv
    maxb = block_table.shape[1]
    num_splits = max(1, min(num_splits, maxb))
    bps = -(-maxb // num_splits)             # table columns per split
    pad = num_splits * bps - maxb
    table = block_table.astype(jnp.int32)
    if pad:
        table = jnp.pad(table, ((0, 0), (0, pad)))
    lengths = lengths.astype(jnp.int32)
    scale = 1.0 / math.sqrt(hd)

    qf = q.reshape(b, hkv, group, hd)

    def kv_index(s, bi, j, table_ref, lengths_ref):
        return (table_ref[bi, s * bps + j], 0, 0, 0)

    def row_index(s, bi, j, table_ref, lengths_ref):
        return (s, bi, 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(num_splits, b, bps),
        in_specs=[
            pl.BlockSpec((1, hkv, group, hd),
                         lambda s, bi, j, t, ln: (bi, 0, 0, 0)),
            pl.BlockSpec((1, bs, hkv, hd), kv_index),
            pl.BlockSpec((1, bs, hkv, hd), kv_index),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, hkv, group, hd), row_index),
            pl.BlockSpec((1, 1, hkv, group, 1), row_index),
            pl.BlockSpec((1, 1, hkv, group, 1), row_index),
        ],
        scratch_shapes=[
            pltpu.VMEM((hkv, group, hd), jnp.float32),
            pltpu.VMEM((hkv, group, 1), jnp.float32),
            pltpu.VMEM((hkv, group, 1), jnp.float32),
        ],
    )
    part = (num_splits, b, hkv, group)
    o_part, m_part, l_part = pl.pallas_call(
        functools.partial(_kernel, hkv=hkv, bps=bps, bs=bs, group=group,
                          window=window, scale=scale),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((*part, hd), jnp.float32),
            jax.ShapeDtypeStruct((*part, 1), jnp.float32),
            jax.ShapeDtypeStruct((*part, 1), jnp.float32),
        ],
        interpret=interpret,
    )(table, lengths, qf, k_pool, v_pool)

    # online-softmax merge across splits (all-empty slots stay zero)
    m_star = jnp.max(m_part, axis=0, keepdims=True)      # (1, B, Hkv, g, 1)
    alpha = jnp.exp(m_part - jnp.maximum(m_star, NEG_INF / 2))
    l_tot = jnp.sum(alpha * l_part, axis=0)              # (B, Hkv, g, 1)
    acc = jnp.sum(alpha * o_part, axis=0)                # (B, Hkv, g, hd)
    out = acc / jnp.maximum(l_tot, 1e-30)
    return out.reshape(b, hq, hd).astype(q.dtype)
