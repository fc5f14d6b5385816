"""Mixture-of-Experts FFN with expert parallelism over the `model` mesh axis.

Design (DESIGN.md §5): tokens enter the block replicated across the `model`
axis (the same layout dense TP uses between blocks). Each device routes all
its tokens, keeps only those destined for its local expert shard
(E_loc = E / tp), runs the expert FFNs on a capacity-bounded (E_loc, C, d)
buffer, scatters results back token-space, and the cross-device combine is a
single psum over `model` — the identical communication pattern as a dense TP
MLP's output all-reduce, so EP costs no extra collective class.

Without an active mesh (CPU unit tests) the same code runs with tp=1.
"""
from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.parallel.sharding import active_mesh, dp_axes, tp_axis

from .layers import dense_init


class MoEParams(NamedTuple):
    router: jax.Array        # (d, E)
    wg: jax.Array            # (E, d, f) gate   ("experts" in path -> EP spec)
    wu: jax.Array            # (E, d, f) up
    wd: jax.Array            # (E, f, d) down


def init_moe(key, cfg) -> dict:
    e, d, f = cfg.n_experts, cfg.d_model, cfg.moe_d_ff
    ks = jax.random.split(key, 4)
    dt = jnp.dtype(cfg.param_dtype)
    scale_d = 1.0 / math.sqrt(d)
    scale_f = 1.0 / math.sqrt(f)
    p = {
        "router": {"kernel": dense_init(ks[0], d, e, jnp.float32)},
        "experts": {
            "wg": (jax.random.normal(ks[1], (e, d, f)) * scale_d).astype(dt),
            "wu": (jax.random.normal(ks[2], (e, d, f)) * scale_d).astype(dt),
            "wd": (jax.random.normal(ks[3], (e, f, d)) * scale_f).astype(dt),
        },
    }
    if cfg.n_shared_experts:
        kk = jax.random.split(ks[0], 3)
        fs = cfg.shared_d_ff or cfg.moe_d_ff * cfg.n_shared_experts
        p["shared"] = {
            "wg": dense_init(kk[0], d, fs, dt),
            "wu": dense_init(kk[1], d, fs, dt),
            "wd": dense_init(kk[2], fs, d, dt),
        }
    return p


def _local_moe(x, router_w, wg, wu, wd, *, cfg, tp_index, tp_size):
    """Per-device MoE body. x: (B_loc, S, d) (replicated over tp); expert
    weights are the local shard (E_loc, ...). Returns partial output that
    must be psum'd over tp."""
    b, s, d = x.shape
    e_loc = wg.shape[0]
    e = e_loc * tp_size
    k = cfg.moe_top_k
    t = b * s

    xf = x.reshape(t, d)
    logits = (xf.astype(jnp.float32) @ router_w.astype(jnp.float32))  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_w, gate_e = jax.lax.top_k(probs, k)                          # (T, k)
    gate_w = gate_w / jnp.clip(gate_w.sum(-1, keepdims=True), 1e-9)

    # ---- aux load-balance loss (computed on full router; identical on all
    # tp shards so the psum-combine divides it back out) -------------------
    me = probs.mean(axis=0)
    ce = jnp.zeros((e,), jnp.float32).at[gate_e.reshape(-1)].add(
        jnp.ones((t * k,), jnp.float32)) / (t * k)
    aux = e * jnp.sum(me * ce)

    # ---- capacity-bounded dispatch to local experts ----------------------
    cap = int(math.ceil(t * k / e * cfg.capacity_factor))
    cap = max(8, -(-cap // 8) * 8)
    flat_e = gate_e.reshape(-1)                                       # (T*k,)
    flat_w = gate_w.reshape(-1)
    flat_tok = jnp.repeat(jnp.arange(t), k)

    first = tp_index * e_loc
    local = (flat_e >= first) & (flat_e < first + e_loc)
    leid = jnp.where(local, flat_e - first, e_loc)                    # e_loc = drop
    # position of each (token, expert) pair within its expert's capacity
    onehot = jax.nn.one_hot(leid, e_loc, dtype=jnp.int32)             # (T*k, E_loc)
    pos = jnp.cumsum(onehot, axis=0) - 1
    pos = jnp.sum(pos * onehot, axis=1)                               # (T*k,)
    keep = local & (pos < cap)
    slot = jnp.where(keep, leid * cap + pos, e_loc * cap)             # overflow slot

    buf = jnp.zeros((e_loc * cap + 1, d), x.dtype)
    buf = buf.at[slot].set(xf[flat_tok])
    buf = buf[:-1].reshape(e_loc, cap, d)

    h = jnp.einsum("ecd,edf->ecf", buf, wg)
    u = jnp.einsum("ecd,edf->ecf", buf, wu)
    h = jax.nn.silu(h) * u
    out_buf = jnp.einsum("ecf,efd->ecd", h, wd).reshape(e_loc * cap, d)
    out_buf = jnp.concatenate([out_buf, jnp.zeros((1, d), out_buf.dtype)], 0)

    contrib = out_buf[slot] * flat_w[:, None].astype(out_buf.dtype)
    out = jnp.zeros((t, d), x.dtype).at[flat_tok].add(
        jnp.where(keep[:, None], contrib, 0))
    return out.reshape(b, s, d), aux


def moe_ffn(params: dict, x: jax.Array, cfg):
    """(B, S, d) -> (B, S, d), aux-loss scalar. Runs expert-parallel over the
    `model` axis when a mesh is active."""
    mesh = active_mesh()
    tp = tp_axis(mesh)
    router_w = params["router"]["kernel"]
    ex = params["experts"]

    if tp is None:
        out, aux = _local_moe(x, router_w, ex["wg"], ex["wu"], ex["wd"],
                              cfg=cfg, tp_index=0, tp_size=1)
    else:
        dp = dp_axes(mesh)
        tp_size = mesh.shape[tp]
        dp_size = 1
        for a in dp:
            dp_size *= mesh.shape[a]
        # decode / tiny batches can't shard over dp -> replicate tokens,
        # keep experts sharded over tp (each chip runs all tokens against
        # its local expert shard; psum combines)
        batch_sharded = dp and x.shape[0] % max(dp_size, 1) == 0
        from repro.parallel.sharding import layout_policy
        decode_tp = layout_policy() == "decode_tp"
        if decode_tp:
            batch_sharded = False       # tokens replicated; weights f-sharded
        x_spec = P(dp, None, None) if batch_sharded else P(None, None, None)
        # decode_tp (§Perf iter-6): expert hidden column/row-parallel over
        # dp — wg/wu f-sliced, wd f-sliced on its contraction dim; the
        # down-projection partials psum over dp (tiny: one (T, d) vector)
        up_spec = P(tp, None, dp) if decode_tp else P(tp, None, None)
        dn_spec = P(tp, dp, None) if decode_tp else P(tp, None, None)

        def body(xl, rw, wg, wu, wd):
            idx = jax.lax.axis_index(tp)
            out, aux = _local_moe(xl, rw, wg, wu, wd, cfg=cfg,
                                  tp_index=idx, tp_size=tp_size)
            aux = jax.lax.psum(aux, tp) / jnp.float32(tp_size)
            if batch_sharded:
                aux = jax.lax.pmean(aux, dp)   # global load-balance loss
            out = jax.lax.psum(out, tp)
            if decode_tp and dp:
                out = jax.lax.psum(out, dp)    # combine f-partials
            return out, aux

        out, aux = jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(x_spec, P(None, None), up_spec, up_spec, dn_spec),
            out_specs=(x_spec, P()),
            check_vma=False,
        )(x, router_w, ex["wg"], ex["wu"], ex["wd"])

    if "shared" in params:
        sh = params["shared"]
        from .layers import swiglu

        out = out + swiglu(x, sh["wg"], sh["wu"], sh["wd"])
    return out, aux * cfg.router_aux_weight
