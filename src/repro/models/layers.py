"""Model primitives: norms, RoPE, blockwise (flash-style) attention, MLPs.

Pure-functional: params are nested dicts of arrays; every apply function is
shape-polymorphic over leading batch dims where possible. Activations are
annotated with *logical* sharding (repro.parallel.sharding.shard) so the same
code runs on CPU tests and the 512-chip mesh.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.parallel.sharding import shard


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------
def dense_init(key, d_in: int, d_out: int, dtype, scale: float | None = None):
    scale = scale if scale is not None else 1.0 / math.sqrt(d_in)
    return (jax.random.normal(key, (d_in, d_out)) * scale).astype(dtype)


def embed_init(key, vocab: int, d: int, dtype):
    return (jax.random.normal(key, (vocab, d)) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------
def rms_norm(x, scale, eps=1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


def layer_norm(x, scale, bias, eps=1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (out * scale + bias).astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------
def rope_table(seq_len: int, head_dim: int, theta: float = 1e4,
               offset: int = 0, dtype=jnp.float32):
    half = head_dim // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    pos = jnp.arange(offset, offset + seq_len, dtype=jnp.float32)
    ang = pos[:, None] * freqs[None, :]
    return jnp.cos(ang).astype(dtype), jnp.sin(ang).astype(dtype)


def apply_rope(x, cos, sin):
    """x: (..., S, H, hd); tables (S, hd/2)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c = cos[..., :, None, :]
    s = sin[..., :, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1
                           ).astype(x.dtype)


def rope_at(pos, head_dim: int, theta: float = 1e4):
    """Per-position rope tables for decode. pos: (B,) int32 -> (B, 1, half)."""
    half = head_dim // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * freqs[None, :]
    return jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]


def rope_tables_at(positions, head_dim: int, theta: float = 1e4,
                   dtype=jnp.float32):
    """``rope_table`` for a *traced* position vector (chunked prefill:
    the chunk's absolute start is a runtime scalar, so the static
    ``offset`` of ``rope_table`` can't express it). positions: (S,)
    int32 -> ((S, half), (S, half)) for ``apply_rope``."""
    half = head_dim // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * freqs[None, :]
    return jnp.cos(ang).astype(dtype), jnp.sin(ang).astype(dtype)


# ---------------------------------------------------------------------------
# Blockwise (flash-style) causal attention — pure JAX online softmax.
# Memory: O(S * chunk) instead of O(S^2); the fully-masked block pairs are
# still *computed* (mask applied) — removing them is a §Perf iteration.
# ---------------------------------------------------------------------------
NEG_INF = -1e30


def _attn_scores(qg, k, mask, hd):
    """qg: (B,Hkv,G,qc,hd); k: (B,Hkv,kc,hd) -> scores (B,Hkv,G,qc,kc).
    bf16 inputs, fp32 accumulation — no fp32 copies of K blocks."""
    s = jnp.einsum("bhgqd,bhkd->bhgqk", qg.astype(k.dtype), k,
                   preferred_element_type=jnp.float32) / math.sqrt(hd)
    return jnp.where(mask, s, NEG_INF)


def blockwise_attention(q, k, v, *, causal: bool, window: int | None = None,
                        q_chunk: int = 512, kv_chunk: int = 512,
                        q_offset: int = 0):
    """Online-softmax attention.

    q: (B, Sq, Hq, hd); k, v: (B, Skv, Hkv, hd). ``q_offset`` is the absolute
    position of q[0] (prefill continuation). Returns (B, Sq, Hq, hd).
    """
    b, sq, hq, hd = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    vd = v.shape[-1]          # value dim may differ from qk dim (MLA)
    q_chunk = min(q_chunk, sq)
    kv_chunk = min(kv_chunk, skv)
    if sq % q_chunk:
        q_chunk = sq       # odd lengths (tests): one chunk
    if skv % kv_chunk:
        kv_chunk = skv
    nq, nk = sq // q_chunk, skv // kv_chunk
    group = hq // hkv

    qt = q.transpose(0, 2, 1, 3).reshape(b, hq, nq, q_chunk, hd)
    kt = k.transpose(0, 2, 1, 3).reshape(b, hkv, nk, kv_chunk, hd)
    vt = v.transpose(0, 2, 1, 3).reshape(b, hkv, nk, kv_chunk, vd)

    q_pos = (q_offset + jnp.arange(sq)).reshape(nq, q_chunk)
    k_pos = jnp.arange(skv).reshape(nk, kv_chunk)

    def q_step(qi):
        qb = qt[:, :, qi].reshape(b, hkv, group, q_chunk, hd)
        qp = q_pos[qi]                                    # (qc,)

        def kv_step(carry, ki):
            acc, m, denom = carry
            kb, vb = kt[:, :, ki], vt[:, :, ki]
            kp = k_pos[ki]
            mask = jnp.ones((q_chunk, kv_chunk), bool)
            if causal:
                mask &= qp[:, None] >= kp[None, :]
            if window is not None:
                mask &= qp[:, None] - kp[None, :] < window
            s = _attn_scores(qb, kb, mask, hd)            # (B,Hkv,G,qc,kc)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            denom = denom * corr + p.sum(axis=-1)
            pv = jnp.einsum("bhgqk,bhkd->bhgqd", p.astype(vb.dtype), vb,
                            preferred_element_type=jnp.float32)
            acc = acc * corr[..., None] + pv
            return (acc, m_new, denom), None

        acc0 = jnp.zeros((b, hkv, group, q_chunk, vd), jnp.float32)
        m0 = jnp.full((b, hkv, group, q_chunk), NEG_INF, jnp.float32)
        d0 = jnp.zeros((b, hkv, group, q_chunk), jnp.float32)
        (acc, m, denom), _ = jax.lax.scan(kv_step, (acc0, m0, d0),
                                          jnp.arange(nk))
        out = acc / jnp.maximum(denom[..., None], 1e-30)
        return out.reshape(b, hq, q_chunk, vd)

    outs = jax.lax.map(q_step, jnp.arange(nq))            # (nq,B,Hq,qc,hd)
    out = outs.transpose(1, 2, 0, 3, 4).reshape(b, hq, sq, vd)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def sp_blockwise_attention(q, k, v, *, causal: bool, window=None,
                           q_chunk: int = 512, kv_chunk: int = 512):
    """Sequence-parallel attention (§Perf iter-1, beyond-paper).

    Shards the *query sequence* over the `model` axis inside a shard_map:
    each chip runs blockwise attention for its S/tp query slice against
    the full K/V (gathered ONCE per layer at the shard_map boundary).
    Without this, GSPMD re-gathers operands inside every (q-chunk,
    kv-chunk) loop iteration — the dominant collective in the train
    baseline. Head counts never need to divide tp (qwen's 40/8 heads).
    Falls back to the plain path when no mesh / not divisible.
    """
    from jax.sharding import PartitionSpec as P
    from repro.parallel.sharding import active_mesh, dp_axes, tp_axis

    mesh = active_mesh()
    tp = tp_axis(mesh)
    b, s, hq, hd = q.shape
    if mesh is None or tp is None:
        return blockwise_attention(q, k, v, causal=causal, window=window,
                                   q_chunk=q_chunk, kv_chunk=kv_chunk)
    tp_n = mesh.shape[tp]
    dp = dp_axes(mesh)
    dp_n = 1
    for a in dp:
        dp_n *= mesh.shape[a]
    if s % tp_n or (s // tp_n) < 64 or (dp and b % dp_n):
        return blockwise_attention(q, k, v, causal=causal, window=window,
                                   q_chunk=q_chunk, kv_chunk=kv_chunk)
    s_loc = s // tp_n
    dps = dp if dp else None

    def local(qs, ks, vs):
        off = jax.lax.axis_index(tp) * s_loc
        return blockwise_attention(qs, ks, vs, causal=causal, window=window,
                                   q_chunk=min(q_chunk, s_loc),
                                   kv_chunk=kv_chunk, q_offset=off)

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(dps, tp, None, None), P(dps, None, None, None),
                  P(dps, None, None, None)),
        out_specs=P(dps, tp, None, None),
        check_vma=False,   # scan carries start unvarying (zeros init)
    )(q, k, v)


def decode_attention(q, k_cache, v_cache, *, length=None, window=None,
                     mask=None, scale=None):
    """Single-token attention against a (B, S, Hkv, hd) cache.

    q: (B, Hq, hd). ``length``: (B,) valid cache length (entries >= length
    masked). ``mask``: explicit (B, S) bool validity (ring buffers) —
    overrides length/window. Returns (B, Hq, vd)."""
    b, hq, hd = q.shape
    s, hkv = k_cache.shape[1], k_cache.shape[2]
    group = hq // hkv
    # bf16 x bf16 -> fp32-accumulated dots (MXU path); never materialize an
    # fp32 copy of the cache (perf iter-0, EXPERIMENTS.md §Perf)
    qg = q.reshape(b, hkv, group, hd).astype(k_cache.dtype)
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    scores = jnp.einsum("bhgd,bshd->bhgs", qg, k_cache,
                        preferred_element_type=jnp.float32) * scale
    if mask is None:
        pos = jnp.arange(s)[None, :]
        mask = jnp.ones((b, s), bool)
        if length is not None:
            mask &= pos < length[:, None]
        if window is not None and length is not None:
            mask &= pos >= (length[:, None] - window)
    scores = jnp.where(mask[:, None, None, :], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1).astype(v_cache.dtype)
    out = jnp.einsum("bhgs,bshd->bhgd", p, v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, hq, -1).astype(q.dtype)


def chunk_attention(q, k_cache, v_cache, mask, *, scale=None):
    """Multi-token attention against a cache (chunked paged prefill).

    q: (B, C, Hq, hd) — the prompt chunk's queries; k/v_cache:
    (B, S, Hkv, hd) — the prefill scratch holding every position written
    so far (including this chunk's); mask: (C, S) or (B, C, S) bool
    validity (causal-with-offset, sliding window). Returns (B, C, Hq,
    hd) in q.dtype. Same bf16-dot/fp32-accumulate discipline as
    ``decode_attention``."""
    b, c, hq, hd = q.shape
    s, hkv = k_cache.shape[1], k_cache.shape[2]
    group = hq // hkv
    qg = q.reshape(b, c, hkv, group, hd).astype(k_cache.dtype)
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    scores = jnp.einsum("bckgd,bskd->bkcgs", qg, k_cache,
                        preferred_element_type=jnp.float32) * scale
    if mask.ndim == 2:
        mask = mask[None]
    scores = jnp.where(mask[:, None, :, None, :], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1).astype(v_cache.dtype)
    out = jnp.einsum("bkcgs,bskd->bckgd", p, v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, c, hq, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------
def _shard_hidden(h):
    """Constrain (B, ..., f) activations: batch x tp normally; under
    sequence parallelism (§Perf iter-2) batch x seq@tp x replicated —
    keeping the hidden dim whole avoids resharding between the
    sequence-sharded residual stream and each MLP."""
    from repro.parallel.sharding import seq_parallel
    if seq_parallel() and h.ndim >= 3:
        axes = ("batch", "sp") + (None,) * (h.ndim - 2)
    else:
        axes = ("batch",) + (None,) * (h.ndim - 2) + ("tp",)
    return shard(h, *axes)


def swiglu(x, wg, wu, wd):
    h = jax.nn.silu(x @ wg) * (x @ wu)
    h = _shard_hidden(h)
    return h @ wd


def gelu_mlp(x, wi, bi, wo, bo):
    h = jax.nn.gelu(x @ wi + bi, approximate=True)
    h = _shard_hidden(h)
    return h @ wo + bo
