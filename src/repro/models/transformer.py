"""Unified schedule-driven model builder for every assigned architecture.

One code path builds dense GQA transformers (qwen/phi3/command-r/llama),
sliding-window interleaves (gemma3), MoE (deepseek-moe), MLA+MoE
(deepseek-v3 incl. MTP head), hybrid Mamba+attention+MoE (jamba), RWKV-6,
encoder-decoder audio (whisper — conv frontend stubbed to precomputed frame
embeddings), and cross-attention VLM (llama-3.2-vision — vision tower
stubbed to precomputed patch embeddings).

The layer layout comes from ``cfg.schedule``: segments of repeating
super-block patterns, each `lax.scan`ned over its repeats with stacked
params — HLO stays O(pattern), not O(layers). The same structure is reused
for the decode cache, so decode scans too.

Three entry points:
  forward(params, batch, cfg)               -> (logits, aux)     train/eval
  prefill(params, batch, cfg)               -> (logits, cache)   inference
  decode_step(params, cache, token, pos, cfg)-> (logits, cache)  1 new token
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from repro.parallel.sharding import shard

from .layers import (
    apply_rope,
    attention,
    chunk_attention,
    decode_attention,
    dense_init,
    embed_init,
    gelu_mlp,
    layer_norm,
    rms_norm,
    rope_at,
    rope_table,
    rope_tables_at,
    sp_blockwise_attention,
    swiglu,
)
from .mamba import init_mamba, init_mamba_cache, mamba_mix, mamba_step
from .moe import init_moe, moe_ffn
from .rwkv import (
    channel_mix,
    channel_mix_step,
    init_rwkv,
    time_mix,
    time_mix_step,
)

ATTN_KINDS = ("attn", "local", "attn_moe", "enc", "dec", "cross")
MLA_KINDS = ("mla_dense", "mla_moe")
MOE_KINDS = ("attn_moe", "mla_moe", "mamba_moe")


# ===========================================================================
# Parameter init
# ===========================================================================
def _init_gqa(key, cfg, *, bidirectional=False, bias=False):
    d, hq, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    ks = jax.random.split(key, 4)
    dt = jnp.dtype(cfg.param_dtype)
    p = {
        "wq": {"kernel": dense_init(ks[0], d, hq * hd, dt)},
        "wk": {"kernel": dense_init(ks[1], d, hkv * hd, dt)},
        "wv": {"kernel": dense_init(ks[2], d, hkv * hd, dt)},
        "wo": {"kernel": dense_init(ks[3], hq * hd, d, dt)},
    }
    if bias or cfg.qkv_bias:
        p["wq"]["bias"] = jnp.zeros((hq * hd,), dt)
        p["wk"]["bias"] = jnp.zeros((hkv * hd,), dt)
        p["wv"]["bias"] = jnp.zeros((hkv * hd,), dt)
        if bias:
            p["wo"]["bias"] = jnp.zeros((d,), dt)
    if cfg.use_qk_norm:
        p["q_norm_scale"] = jnp.zeros((hd,), jnp.float32)
        p["k_norm_scale"] = jnp.zeros((hd,), jnp.float32)
    return p


def _init_mla(key, cfg):
    d = cfg.d_model
    h = cfg.n_heads
    qk = cfg.qk_nope_dim + cfg.qk_rope_dim
    ks = jax.random.split(key, 5)
    dt = jnp.dtype(cfg.param_dtype)
    return {
        "wq_a": {"kernel": dense_init(ks[0], d, cfg.q_lora_rank, dt)},
        "q_norm_scale": jnp.zeros((cfg.q_lora_rank,), jnp.float32),
        "wq_b": {"kernel": dense_init(ks[1], cfg.q_lora_rank, h * qk, dt)},
        "wkv_a": {"kernel": dense_init(
            ks[2], d, cfg.kv_lora_rank + cfg.qk_rope_dim, dt)},
        "kv_norm_scale": jnp.zeros((cfg.kv_lora_rank,), jnp.float32),
        "wkv_b": {"kernel": dense_init(
            ks[3], cfg.kv_lora_rank,
            h * (cfg.qk_nope_dim + cfg.v_head_dim), dt)},
        "wo": {"kernel": dense_init(ks[4], h * cfg.v_head_dim, d, dt)},
    }


def _init_swiglu(key, cfg, d_ff=None):
    d, f = cfg.d_model, d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    dt = jnp.dtype(cfg.param_dtype)
    return {
        "wg": {"kernel": dense_init(ks[0], d, f, dt)},
        "wu": {"kernel": dense_init(ks[1], d, f, dt)},
        "wd": {"kernel": dense_init(ks[2], f, d, dt)},
    }


def _init_gelu_mlp(key, cfg):
    d, f = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 2)
    dt = jnp.dtype(cfg.param_dtype)
    return {
        "wi": {"kernel": dense_init(ks[0], d, f, dt),
               "bias": jnp.zeros((f,), dt)},
        "wo": {"kernel": dense_init(ks[1], f, d, dt),
               "bias": jnp.zeros((d,), dt)},
    }


def _ln(cfg, with_bias=False):
    d = cfg.d_model
    if with_bias:
        return {"scale": jnp.ones((d,), jnp.float32),
                "bias": jnp.zeros((d,), jnp.float32)}
    return {"scale": jnp.zeros((d,), jnp.float32)}  # rms (1 + scale)


def init_block(key, kind: str, cfg) -> dict:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    if kind in ("attn", "local"):
        return {"ln1": _ln(cfg), "attn": _init_gqa(k1, cfg),
                "ln2": _ln(cfg), "mlp": _init_swiglu(k2, cfg)}
    if kind == "attn_moe":
        return {"ln1": _ln(cfg), "attn": _init_gqa(k1, cfg),
                "ln2": _ln(cfg), "moe": init_moe(k2, cfg)}
    if kind == "mla_dense":
        return {"ln1": _ln(cfg), "attn": _init_mla(k1, cfg),
                "ln2": _ln(cfg), "mlp": _init_swiglu(k2, cfg)}
    if kind == "mla_moe":
        return {"ln1": _ln(cfg), "attn": _init_mla(k1, cfg),
                "ln2": _ln(cfg), "moe": init_moe(k2, cfg)}
    if kind == "mamba_dense":
        return {"ln1": _ln(cfg), "mamba": init_mamba(k1, cfg),
                "ln2": _ln(cfg), "mlp": _init_swiglu(k2, cfg)}
    if kind == "mamba_moe":
        return {"ln1": _ln(cfg), "mamba": init_mamba(k1, cfg),
                "ln2": _ln(cfg), "moe": init_moe(k2, cfg)}
    if kind == "rwkv":
        p = init_rwkv(k1, cfg)
        p["ln1"] = _ln(cfg, with_bias=True)
        p["ln2"] = _ln(cfg, with_bias=True)
        return p
    if kind == "cross":
        # llama-3.2-vision style gated cross-attention block
        return {"ln1": _ln(cfg), "xattn": _init_gqa(k1, cfg),
                "gate_attn": jnp.zeros((), jnp.float32),
                "ln2": _ln(cfg), "mlp": _init_swiglu(k2, cfg),
                "gate_mlp": jnp.zeros((), jnp.float32)}
    if kind == "enc":
        return {"ln1": _ln(cfg, True),
                "attn": _init_gqa(k1, cfg, bidirectional=True, bias=True),
                "ln2": _ln(cfg, True), "mlp": _init_gelu_mlp(k2, cfg)}
    if kind == "dec":
        return {"ln1": _ln(cfg, True), "attn": _init_gqa(k1, cfg, bias=True),
                "ln2": _ln(cfg, True), "xattn": _init_gqa(k3, cfg, bias=True),
                "ln3": _ln(cfg, True), "mlp": _init_gelu_mlp(k4, cfg)}
    raise ValueError(f"unknown block kind {kind!r}")


def init_params(cfg, key) -> dict:
    """Full parameter tree. Segment i, pattern position j lives at
    params['segments'][i][f'p{j}'] with leading stacked axis = repeats."""
    keys = jax.random.split(key, 8)
    dt = jnp.dtype(cfg.param_dtype)
    params: dict[str, Any] = {
        "embed": {"kernel": embed_init(keys[0], cfg.vocab_size, cfg.d_model, dt)},
        "final_norm": _ln(cfg, with_bias=(cfg.family == "encdec")),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = {"kernel": embed_init(
            keys[1], cfg.vocab_size, cfg.d_model, dt)}

    segs = []
    seg_keys = jax.random.split(keys[2], len(cfg.schedule))
    for (pattern, repeats), sk in zip(cfg.schedule, seg_keys):
        pos_keys = jax.random.split(sk, len(pattern))
        seg = {}
        for j, (kind, pk) in enumerate(zip(pattern, pos_keys)):
            layer_keys = jax.random.split(pk, repeats)
            seg[f"p{j}"] = jax.vmap(lambda k: init_block(k, kind, cfg))(
                layer_keys)
        segs.append(seg)
    params["segments"] = segs

    if cfg.encoder_layers:
        enc_keys = jax.random.split(keys[3], cfg.encoder_layers)
        params["encoder"] = {
            "blocks": jax.vmap(lambda k: init_block(k, "enc", cfg))(enc_keys),
            "ln_post": _ln(cfg, True),
        }
    if cfg.mtp:
        params["mtp"] = {
            "norm": _ln(cfg),
            "proj": {"kernel": dense_init(keys[4], 2 * cfg.d_model,
                                          cfg.d_model, dt)},
        }
    return params


def param_count(params) -> int:
    return sum(int(p.size) for p in jax.tree.leaves(params))


_PRECISION_CRITICAL = ("norm", "ln", "scale", "bias", "a_log", "d_skip",
                       "decay", "bonus", "gate", "mu_")


def cast_params(params, cfg):
    """Mixed precision: weights cast to compute dtype at use (bf16 MXU
    path); small precision-critical leaves (norms, ssm decay constants,
    gates) stay in their stored dtype."""
    cdt = jnp.dtype(cfg.compute_dtype)

    def cast(kp, p):
        path = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                        for k in kp).lower()
        if any(h in path for h in _PRECISION_CRITICAL):
            return p
        if jnp.issubdtype(p.dtype, jnp.floating):
            return p.astype(cdt)
        return p

    return jax.tree_util.tree_map_with_path(cast, params)


# ===========================================================================
# Attention application (train / prefill path)
# ===========================================================================
def _qk_norm(x, scale):
    return rms_norm(x, scale)


def _gqa_apply(p, x, cfg, *, causal, window=None, kv_src=None, rope=True,
               q_offset=0, return_kv=False):
    """x: (B,S,d); kv_src (B,Skv,d) for cross-attention (no rope on kv)."""
    b, s, d = x.shape
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    src = kv_src if kv_src is not None else x

    def proj(w, t, h):
        y = t @ w["kernel"]
        if "bias" in w:
            y = y + w["bias"]
        return y.reshape(*t.shape[:-1], h, hd)

    q = proj(p["wq"], x, hq)
    k = proj(p["wk"], src, hkv)
    v = proj(p["wv"], src, hkv)
    if cfg.use_qk_norm:
        q = _qk_norm(q, p["q_norm_scale"])
        k = _qk_norm(k, p["k_norm_scale"])
    if rope and kv_src is None:
        cos, sin = rope_table(s, hd, cfg.rope_theta, offset=q_offset)
        q = apply_rope(q, cos, sin)
        cos_k, sin_k = rope_table(src.shape[1], hd, cfg.rope_theta)
        k = apply_rope(k, cos_k, sin_k)
    if cfg.attn_sp:
        q = shard(q, "batch", "sp", None, None)
        k = shard(k, "batch", None, None, None)
        v = shard(v, "batch", None, None, None)
        out = sp_blockwise_attention(q, k, v, causal=causal, window=window,
                                     q_chunk=cfg.q_chunk,
                                     kv_chunk=cfg.kv_chunk)
        out = shard(out, "batch", "sp", None, None)
    else:
        q = shard(q, "batch", None, "tp", None)
        out = attention(q, k, v, causal=causal, window=window,
                        q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk,
                        q_offset=q_offset)
    out = out.reshape(b, s, hq * hd)
    y = out @ p["wo"]["kernel"]
    if "bias" in p["wo"]:
        y = y + p["wo"]["bias"]
    if return_kv:
        return y, (k, v)
    return y, None


def _mla_apply(p, x, cfg, *, return_kv=False):
    """DeepSeek MLA, non-absorbed (train/prefill) form."""
    b, s, d = x.shape
    h = cfg.n_heads
    nope, rope_d, vd = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim

    cq = rms_norm(x @ p["wq_a"]["kernel"], p["q_norm_scale"])
    q = (cq @ p["wq_b"]["kernel"]).reshape(b, s, h, nope + rope_d)
    q_nope, q_rope = q[..., :nope], q[..., nope:]

    ckv = x @ p["wkv_a"]["kernel"]
    c_kv, k_rope = ckv[..., :cfg.kv_lora_rank], ckv[..., cfg.kv_lora_rank:]
    c_kv = rms_norm(c_kv, p["kv_norm_scale"])
    kv = (c_kv @ p["wkv_b"]["kernel"]).reshape(b, s, h, nope + vd)
    k_nope, v = kv[..., :nope], kv[..., nope:]

    cos, sin = rope_table(s, rope_d, cfg.rope_theta)
    q_rope = apply_rope(q_rope, cos, sin)
    k_rope = apply_rope(k_rope[:, :, None, :], cos, sin)     # (B,S,1,rope)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, (b, s, h, rope_d))], axis=-1)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)

    if cfg.attn_sp:
        out = sp_blockwise_attention(q, k, v, causal=True,
                                     q_chunk=cfg.q_chunk,
                                     kv_chunk=cfg.kv_chunk)
    else:
        out = attention(q, k, v, causal=True, q_chunk=cfg.q_chunk,
                        kv_chunk=cfg.kv_chunk)
    y = out.reshape(b, s, h * vd) @ p["wo"]["kernel"]
    if return_kv:
        # decode cache stores the *latent* (c_kv) + roped shared k_rope
        return y, (c_kv, k_rope[:, :, 0, :])
    return y, None


# ===========================================================================
# Block application (train / prefill)
# ===========================================================================
def block_apply(kind: str, p, x, cfg, ctx, *, return_kv=False):
    """Returns (x_out, aux_scalar, cache_entry_or_None).

    Each half of the block (pre-norm, its mixer, residual add) runs under
    a ``model/<part>`` name scope, so every device op of the train step
    names the layer it belongs to (docs/observability.md, "Device
    scopes")."""
    aux = jnp.zeros((), jnp.float32)
    kv = None
    if kind in ("attn", "local", "attn_moe"):
        with jax.named_scope("model/attn"):
            h = rms_norm(x, p["ln1"]["scale"], cfg.norm_eps)
            window = cfg.sliding_window if kind == "local" else None
            a, kv = _gqa_apply(p["attn"], h, cfg, causal=True,
                               window=window, return_kv=return_kv)
            x = x + a
        with jax.named_scope("model/mlp"):
            h = rms_norm(x, p["ln2"]["scale"], cfg.norm_eps)
            if kind == "attn_moe":
                m, aux = moe_ffn(p["moe"], h, cfg)
            else:
                m = swiglu(h, p["mlp"]["wg"]["kernel"],
                           p["mlp"]["wu"]["kernel"], p["mlp"]["wd"]["kernel"])
            x = x + m
    elif kind in MLA_KINDS:
        with jax.named_scope("model/attn"):
            h = rms_norm(x, p["ln1"]["scale"], cfg.norm_eps)
            a, kv = _mla_apply(p["attn"], h, cfg, return_kv=return_kv)
            x = x + a
        with jax.named_scope("model/mlp"):
            h = rms_norm(x, p["ln2"]["scale"], cfg.norm_eps)
            if kind == "mla_moe":
                m, aux = moe_ffn(p["moe"], h, cfg)
            else:
                m = swiglu(h, p["mlp"]["wg"]["kernel"],
                           p["mlp"]["wu"]["kernel"], p["mlp"]["wd"]["kernel"])
            x = x + m
    elif kind in ("mamba_dense", "mamba_moe"):
        with jax.named_scope("model/ssm"):
            h = rms_norm(x, p["ln1"]["scale"], cfg.norm_eps)
            if return_kv:
                mx, kv = mamba_mix(p["mamba"], h, cfg, return_state=True)
            else:
                mx = mamba_mix(p["mamba"], h, cfg)
            x = x + mx
        with jax.named_scope("model/mlp"):
            h = rms_norm(x, p["ln2"]["scale"], cfg.norm_eps)
            if kind == "mamba_moe":
                m, aux = moe_ffn(p["moe"], h, cfg)
            else:
                m = swiglu(h, p["mlp"]["wg"]["kernel"],
                           p["mlp"]["wu"]["kernel"], p["mlp"]["wd"]["kernel"])
            x = x + m
    elif kind == "rwkv":
        b, s, d = x.shape
        hh, hs = cfg.rwkv_n_heads, cfg.rwkv_head_size
        with jax.named_scope("model/rwkv"):
            h = layer_norm(x, p["ln1"]["scale"], p["ln1"]["bias"],
                           cfg.norm_eps)
            x_prev0 = jnp.zeros((b, d), h.dtype)
            st0 = jnp.zeros((b, hh, hs, hs), jnp.float32)
            tm_out, last_x, st = time_mix(p["tm"], h, x_prev0, st0, cfg)
            x = x + tm_out
        with jax.named_scope("model/mlp"):
            h2 = layer_norm(x, p["ln2"]["scale"], p["ln2"]["bias"],
                            cfg.norm_eps)
            cm_out, last_cm = channel_mix(p["cm"], h2,
                                          jnp.zeros((b, d), h2.dtype))
            x = x + cm_out
        if return_kv:
            kv = {"x_prev_tm": last_x, "x_prev_cm": last_cm, "wkv": st}
    elif kind == "cross":
        with jax.named_scope("model/xattn"):
            h = rms_norm(x, p["ln1"]["scale"], cfg.norm_eps)
            a, kv = _gqa_apply(p["xattn"], h, cfg, causal=False,
                               kv_src=ctx["image_embeds"], rope=False,
                               return_kv=return_kv)
            x = x + jnp.tanh(p["gate_attn"]) * a
        with jax.named_scope("model/mlp"):
            h = rms_norm(x, p["ln2"]["scale"], cfg.norm_eps)
            m = swiglu(h, p["mlp"]["wg"]["kernel"], p["mlp"]["wu"]["kernel"],
                       p["mlp"]["wd"]["kernel"])
            x = x + jnp.tanh(p["gate_mlp"]) * m
    elif kind == "enc":
        with jax.named_scope("model/attn"):
            h = layer_norm(x, p["ln1"]["scale"], p["ln1"]["bias"],
                           cfg.norm_eps)
            a, _ = _gqa_apply(p["attn"], h, cfg, causal=False, rope=False)
            x = x + a
        with jax.named_scope("model/mlp"):
            h = layer_norm(x, p["ln2"]["scale"], p["ln2"]["bias"],
                           cfg.norm_eps)
            x = x + gelu_mlp(h, p["mlp"]["wi"]["kernel"],
                             p["mlp"]["wi"]["bias"], p["mlp"]["wo"]["kernel"],
                             p["mlp"]["wo"]["bias"])
    elif kind == "dec":
        with jax.named_scope("model/attn"):
            h = layer_norm(x, p["ln1"]["scale"], p["ln1"]["bias"],
                           cfg.norm_eps)
            a, kv_self = _gqa_apply(p["attn"], h, cfg, causal=True,
                                    return_kv=return_kv)
            x = x + a
        with jax.named_scope("model/xattn"):
            h = layer_norm(x, p["ln2"]["scale"], p["ln2"]["bias"],
                           cfg.norm_eps)
            a, kv_cross = _gqa_apply(p["xattn"], h, cfg, causal=False,
                                     kv_src=ctx["enc_out"], rope=False,
                                     return_kv=return_kv)
            x = x + a
        with jax.named_scope("model/mlp"):
            h = layer_norm(x, p["ln3"]["scale"], p["ln3"]["bias"],
                           cfg.norm_eps)
            x = x + gelu_mlp(h, p["mlp"]["wi"]["kernel"],
                             p["mlp"]["wi"]["bias"], p["mlp"]["wo"]["kernel"],
                             p["mlp"]["wo"]["bias"])
        kv = (kv_self, kv_cross) if return_kv else None
    else:
        raise ValueError(kind)
    return x, aux, kv


# ===========================================================================
# Encoder (whisper) — stub frontend: input is (B, enc_seq, d) frame embeds
# ===========================================================================
def _sinusoid(seq, d, dtype):
    half = d // 2
    freqs = jnp.exp(-math.log(10000.0) * jnp.arange(half) / (half - 1))
    ang = jnp.arange(seq)[:, None] * freqs[None, :]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], -1).astype(dtype)


def encode(params, frames, cfg):
    """frames: (B, enc_seq, d_model) precomputed (conv frontend stub)."""
    cdt = jnp.dtype(cfg.compute_dtype)
    x = frames.astype(cdt)
    x = x + _sinusoid(x.shape[1], cfg.d_model, x.dtype)[None]

    def body(x, p):
        x, _, _ = block_apply("enc", p, x, cfg, {})
        return x.astype(cdt), None     # pin carry dtype

    if cfg.remat:
        body = jax.checkpoint(body)
    with jax.named_scope("model/blocks"):
        x, _ = jax.lax.scan(body, x, params["encoder"]["blocks"])
    ln = params["encoder"]["ln_post"]
    return layer_norm(x, ln["scale"], ln["bias"], cfg.norm_eps)


# ===========================================================================
# Forward (train / eval / prefill)
# ===========================================================================
def forward(params, batch, cfg, *, return_cache: bool = False):
    """batch: {'tokens': (B,S) int32, 'frames': (B,enc_seq,d)?,
    'image_embeds': (B,n_img,d)?}. Returns (logits, aux) or, with
    return_cache, (logits, aux, cache)."""
    tokens = batch["tokens"]
    cdt = jnp.dtype(cfg.compute_dtype)
    with jax.named_scope("model/embed"):
        params = cast_params(params, cfg)
        x = params["embed"]["kernel"][tokens]
        x = shard(x, "batch", "sp", None)

    ctx = {}
    if cfg.encoder_layers:
        with jax.named_scope("model/encoder"):
            ctx["enc_out"] = encode(params, batch["frames"], cfg)
    if cfg.n_image_tokens:
        with jax.named_scope("model/embed"):
            ctx["image_embeds"] = batch["image_embeds"].astype(cdt)

    aux_total = jnp.zeros((), jnp.float32)
    caches = []
    for (pattern, repeats), seg in zip(cfg.schedule, params["segments"]):

        def body(carry, layer_p):
            x, aux = carry
            entries = {}
            for j, kind in enumerate(pattern):
                x, a, kv = block_apply(kind, layer_p[f"p{j}"], x, cfg, ctx,
                                       return_kv=return_cache)
                x = x.astype(cdt)      # pin residual-stream dtype (carry)
                aux = aux + a
                if return_cache:
                    entries[f"p{j}"] = kv
            return (x, aux), (entries if return_cache else None)

        if cfg.remat:
            body = jax.checkpoint(body)
        # the scan's own work (slicing each layer's weights out of the
        # stack, stacking residuals and gradients) lands in model/blocks
        with jax.named_scope("model/blocks"):
            (x, aux_total), seg_cache = jax.lax.scan(body, (x, aux_total),
                                                     seg)
        caches.append(seg_cache)

    with jax.named_scope("model/head"):
        if cfg.family == "encdec":
            fn = params["final_norm"]
            x = layer_norm(x, fn["scale"], fn["bias"], cfg.norm_eps)
        else:
            x = rms_norm(x, params["final_norm"]["scale"], cfg.norm_eps)

        unemb = (params["embed"] if cfg.tie_embeddings else params["unembed"])
        logits = x @ unemb["kernel"].astype(cdt).T
        from repro.parallel.sharding import seq_parallel as _seq_par
        if _seq_par():
            logits = shard(logits, "batch", "sp", None)
        else:
            logits = shard(logits, "batch", None, "tp")

        aux = {"moe_aux": aux_total, "mtp_logits": None}
        if cfg.mtp and "mtp" in params:
            # DeepSeek-style multi-token prediction: predict t+2 from
            # [h_t ; embed(token_{t+1})]. Full-length with a roll (position
            # S-1 is masked in the loss) so the gather keeps the (B, S)
            # sharding — a [:, 1:] slice makes S odd and forces SPMD to
            # replicate the embedding table (XLA "involuntary full
            # rematerialization").
            emb_next = params["embed"]["kernel"][jnp.roll(tokens, -1, axis=1)]
            h_mtp = jnp.concatenate([x, emb_next], axis=-1)
            h_mtp = h_mtp @ params["mtp"]["proj"]["kernel"].astype(cdt)
            h_mtp = rms_norm(h_mtp, params["mtp"]["norm"]["scale"],
                             cfg.norm_eps)
            aux["mtp_logits"] = h_mtp @ unemb["kernel"].astype(cdt).T

    if return_cache:
        return logits, aux, caches, ctx
    return logits, aux


# ===========================================================================
# Decode cache
# ===========================================================================
def _cache_layout(kind: str, cfg, batch: int, max_len: int, cdt):
    """Zeros cache entry for one layer of ``kind`` (unstacked)."""
    hkv, hd = cfg.n_kv_heads, cfg.hd
    if kind in ("attn", "attn_moe", "dec"):
        kv = {"k": jnp.zeros((batch, max_len, hkv, hd), cdt),
              "v": jnp.zeros((batch, max_len, hkv, hd), cdt)}
        if kind == "dec":
            es = cfg.encoder_seq
            kv["xk"] = jnp.zeros((batch, es, hkv, hd), cdt)
            kv["xv"] = jnp.zeros((batch, es, hkv, hd), cdt)
        return kv
    if kind == "local":
        w = min(cfg.sliding_window, max_len)
        return {"k": jnp.zeros((batch, w, hkv, hd), cdt),
                "v": jnp.zeros((batch, w, hkv, hd), cdt)}
    if kind in MLA_KINDS:
        return {"ckv": jnp.zeros((batch, max_len, cfg.kv_lora_rank), cdt),
                "krope": jnp.zeros((batch, max_len, cfg.qk_rope_dim), cdt)}
    if kind in ("mamba_dense", "mamba_moe"):
        return init_mamba_cache(cfg, batch, cdt)
    if kind == "rwkv":
        d = cfg.d_model
        hh, hs = cfg.rwkv_n_heads, cfg.rwkv_head_size
        return {"x_prev_tm": jnp.zeros((batch, d), cdt),
                "x_prev_cm": jnp.zeros((batch, d), cdt),
                "wkv": jnp.zeros((batch, hh, hs, hs), jnp.float32)}
    if kind == "cross":
        n = cfg.n_image_tokens
        return {"xk": jnp.zeros((batch, n, hkv, hd), cdt),
                "xv": jnp.zeros((batch, n, hkv, hd), cdt)}
    raise ValueError(kind)


def init_cache(cfg, batch: int, max_len: int) -> list:
    """Zeroed decode cache matching the segment/scan structure."""
    cdt = jnp.dtype(cfg.compute_dtype)
    caches = []
    for pattern, repeats in cfg.schedule:
        seg = {}
        for j, kind in enumerate(pattern):
            one = _cache_layout(kind, cfg, batch, max_len, cdt)
            seg[f"p{j}"] = jax.tree.map(
                lambda t: jnp.zeros((repeats, *t.shape), t.dtype), one)
        caches.append(seg)
    return caches


# ===========================================================================
# Decode step (single new token against the cache)
# ===========================================================================
def _rope_decode(x, cos, sin):
    """x: (B, H, hd); tables (B, 1, half) — broadcast over the head axis."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def _gqa_decode(p, x_t, cache, pos, cfg, *, window=None):
    """x_t: (B, d); cache {'k','v'}: (B, S|w, Hkv, hd); pos: (B,) int32
    per-sequence positions. Returns (y, cache)."""
    b, d = x_t.shape
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd

    def proj(w, t, h):
        y = t @ w["kernel"]
        if "bias" in w:
            y = y + w["bias"]
        return y.reshape(b, h, hd)

    q = proj(p["wq"], x_t, hq)
    k = proj(p["wk"], x_t, hkv)
    v = proj(p["wv"], x_t, hkv)
    if cfg.use_qk_norm:
        q = _qk_norm(q, p["q_norm_scale"])
        k = _qk_norm(k, p["k_norm_scale"])
    cos, sin = rope_at(pos, hd, cfg.rope_theta)    # (B, 1, half)
    q = _rope_decode(q, cos, sin)                  # broadcast over heads
    k = _rope_decode(k, cos, sin)

    s = cache["k"].shape[1]
    k = k.astype(cache["k"].dtype)
    v = v.astype(cache["v"].dtype)
    rows = jnp.arange(b)
    if window is not None:
        slot = pos % s                             # per-row ring slot
        new_k = cache["k"].at[rows, slot].set(k)
        new_v = cache["v"].at[rows, slot].set(v)
        idx = jnp.arange(s)[None, :]
        posc = pos[:, None]
        entry_pos = posc - ((posc - idx) % s)
        mask = (entry_pos >= 0) & (entry_pos >= posc - window + 1)
        out = decode_attention(q, new_k, new_v, mask=mask)
    else:
        new_k = cache["k"].at[rows, pos].set(k)
        new_v = cache["v"].at[rows, pos].set(v)
        out = decode_attention(q, new_k, new_v, length=pos + 1)
    y = out.reshape(b, hq * hd) @ p["wo"]["kernel"]
    if "bias" in p["wo"]:
        y = y + p["wo"]["bias"]
    cache = dict(cache)
    cache["k"], cache["v"] = new_k, new_v
    return y, cache


def _cross_decode(p, x_t, xk, xv, cfg):
    b, d = x_t.shape
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd

    def proj(w, t, h):
        y = t @ w["kernel"]
        if "bias" in w:
            y = y + w["bias"]
        return y.reshape(b, h, hd)

    q = proj(p["wq"], x_t, hq)
    out = decode_attention(q, xk, xv)
    y = out.reshape(b, hq * hd) @ p["wo"]["kernel"]
    if "bias" in p["wo"]:
        y = y + p["wo"]["bias"]
    return y


def _mla_decode(p, x_t, cache, pos, cfg):
    """Absorbed-form MLA decode: attention runs in the latent space, the
    per-head up-projections are folded into q and the output (DeepSeek-V3
    inference trick) — the cache is (B, S, kv_rank + rope)."""
    b, d = x_t.shape
    h = cfg.n_heads
    nope, rope_d, vd = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    kvr = cfg.kv_lora_rank

    cq = rms_norm(x_t @ p["wq_a"]["kernel"], p["q_norm_scale"])
    q = (cq @ p["wq_b"]["kernel"]).reshape(b, h, nope + rope_d)
    q_nope, q_rope = q[..., :nope], q[..., nope:]

    ckv = x_t @ p["wkv_a"]["kernel"]
    c_kv, k_rope = ckv[..., :kvr], ckv[..., kvr:]
    c_kv = rms_norm(c_kv, p["kv_norm_scale"])

    cos, sin = rope_at(pos, rope_d, cfg.rope_theta)
    q_rope = _rope_decode(q_rope, cos, sin)
    k_rope = _rope_decode(k_rope[:, None, :], cos, sin)[:, 0]

    wkv_b = p["wkv_b"]["kernel"].reshape(kvr, h, nope + vd)
    w_uk, w_uv = wkv_b[..., :nope], wkv_b[..., nope:]

    # absorb W_uk into q: q_lat (B, H, kvr)
    q_lat = jnp.einsum("bhn,khn->bhk", q_nope.astype(jnp.float32),
                       w_uk.astype(jnp.float32))

    rows = jnp.arange(b)
    new_ckv = cache["ckv"].at[rows, pos].set(
        c_kv.astype(cache["ckv"].dtype))
    new_kr = cache["krope"].at[rows, pos].set(
        k_rope.astype(cache["krope"].dtype))

    s = new_ckv.shape[1]
    cdt = new_ckv.dtype
    # bf16 dots with fp32 accumulation — no fp32 copy of the latent cache
    scores = (jnp.einsum("bhk,bsk->bhs", q_lat.astype(cdt), new_ckv,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bhr,bsr->bhs", q_rope.astype(cdt), new_kr,
                           preferred_element_type=jnp.float32))
    scores = scores / math.sqrt(nope + rope_d)
    mask = jnp.arange(s)[None] <= pos[:, None]
    scores = jnp.where(mask[:, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(cdt)
    ctx_lat = jnp.einsum("bhs,bsk->bhk", probs, new_ckv,
                         preferred_element_type=jnp.float32)
    v = jnp.einsum("bhk,khv->bhv", ctx_lat, w_uv.astype(jnp.float32))
    y = v.reshape(b, h * vd).astype(x_t.dtype) @ p["wo"]["kernel"]
    return y, {"ckv": new_ckv, "krope": new_kr}


def block_decode(kind: str, p, x_t, cache, pos, cfg):
    """x_t: (B, d); pos: (B,) int32 per-sequence positions. Returns
    (x_t, new_cache_entry)."""
    if kind in ("attn", "local", "attn_moe"):
        h = rms_norm(x_t, p["ln1"]["scale"], cfg.norm_eps)
        window = cfg.sliding_window if kind == "local" else None
        a, cache = _gqa_decode(p["attn"], h, cache, pos, cfg, window=window)
        x_t = x_t + a
        h = rms_norm(x_t, p["ln2"]["scale"], cfg.norm_eps)
        if kind == "attn_moe":
            m, _ = moe_ffn(p["moe"], h[:, None, :], cfg)
            m = m[:, 0]
        else:
            m = swiglu(h, p["mlp"]["wg"]["kernel"], p["mlp"]["wu"]["kernel"],
                       p["mlp"]["wd"]["kernel"])
        return x_t + m, cache
    if kind in MLA_KINDS:
        h = rms_norm(x_t, p["ln1"]["scale"], cfg.norm_eps)
        a, cache = _mla_decode(p["attn"], h, cache, pos, cfg)
        x_t = x_t + a
        h = rms_norm(x_t, p["ln2"]["scale"], cfg.norm_eps)
        if kind == "mla_moe":
            m, _ = moe_ffn(p["moe"], h[:, None, :], cfg)
            m = m[:, 0]
        else:
            m = swiglu(h, p["mlp"]["wg"]["kernel"], p["mlp"]["wu"]["kernel"],
                       p["mlp"]["wd"]["kernel"])
        return x_t + m, cache
    if kind in ("mamba_dense", "mamba_moe"):
        h = rms_norm(x_t, p["ln1"]["scale"], cfg.norm_eps)
        a, new_mc = mamba_step(p["mamba"], h, cache, cfg)
        x_t = x_t + a
        h = rms_norm(x_t, p["ln2"]["scale"], cfg.norm_eps)
        if kind == "mamba_moe":
            m, _ = moe_ffn(p["moe"], h[:, None, :], cfg)
            m = m[:, 0]
        else:
            m = swiglu(h, p["mlp"]["wg"]["kernel"], p["mlp"]["wu"]["kernel"],
                       p["mlp"]["wd"]["kernel"])
        return x_t + m, new_mc
    if kind == "rwkv":
        h = layer_norm(x_t, p["ln1"]["scale"], p["ln1"]["bias"], cfg.norm_eps)
        tm_out, new_xp, new_st = time_mix_step(
            p["tm"], h, cache["x_prev_tm"].astype(h.dtype), cache["wkv"], cfg)
        x_t = x_t + tm_out
        h2 = layer_norm(x_t, p["ln2"]["scale"], p["ln2"]["bias"], cfg.norm_eps)
        cm_out, new_xp_cm = channel_mix_step(
            p["cm"], h2, cache["x_prev_cm"].astype(h2.dtype))
        x_t = x_t + cm_out
        return x_t, {"x_prev_tm": new_xp.astype(cache["x_prev_tm"].dtype),
                     "x_prev_cm": new_xp_cm.astype(cache["x_prev_cm"].dtype),
                     "wkv": new_st}
    if kind == "cross":
        h = rms_norm(x_t, p["ln1"]["scale"], cfg.norm_eps)
        a = _cross_decode(p["xattn"], h, cache["xk"], cache["xv"], cfg)
        x_t = x_t + jnp.tanh(p["gate_attn"]) * a
        h = rms_norm(x_t, p["ln2"]["scale"], cfg.norm_eps)
        m = swiglu(h, p["mlp"]["wg"]["kernel"], p["mlp"]["wu"]["kernel"],
                   p["mlp"]["wd"]["kernel"])
        return x_t + jnp.tanh(p["gate_mlp"]) * m, cache
    if kind == "dec":
        h = layer_norm(x_t, p["ln1"]["scale"], p["ln1"]["bias"], cfg.norm_eps)
        a, cache = _gqa_decode(p["attn"], h, cache, pos, cfg)
        x_t = x_t + a
        h = layer_norm(x_t, p["ln2"]["scale"], p["ln2"]["bias"], cfg.norm_eps)
        a = _cross_decode(p["xattn"], h, cache["xk"], cache["xv"], cfg)
        x_t = x_t + a
        h = layer_norm(x_t, p["ln3"]["scale"], p["ln3"]["bias"], cfg.norm_eps)
        m = gelu_mlp(h, p["mlp"]["wi"]["kernel"], p["mlp"]["wi"]["bias"],
                     p["mlp"]["wo"]["kernel"], p["mlp"]["wo"]["bias"])
        return x_t + m, cache
    raise ValueError(kind)


def _lm_head(x_t, params, cfg):
    """Final norm + unembedding shared by every decode entry point.
    x_t: (..., d) -> logits (..., vocab)."""
    cdt = jnp.dtype(cfg.compute_dtype)
    if cfg.family == "encdec":
        fn = params["final_norm"]
        x_t = layer_norm(x_t, fn["scale"], fn["bias"], cfg.norm_eps)
    else:
        x_t = rms_norm(x_t, params["final_norm"]["scale"], cfg.norm_eps)
    unemb = (params["embed"] if cfg.tie_embeddings else params["unembed"])
    return x_t @ unemb["kernel"].astype(cdt).T


def decode_step(params, cache, token, pos, cfg):
    """token: (B,) int32; pos: scalar int32 or (B,) int32 per-sequence
    positions of this token. Returns (logits (B, vocab), new_cache)."""
    cdt = jnp.dtype(cfg.compute_dtype)
    params = cast_params(params, cfg)
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim == 0:
        pos = jnp.full((token.shape[0],), pos, jnp.int32)
    x_t = params["embed"]["kernel"][token]
    x_t = shard(x_t, "batch", None)

    new_caches = []
    for (pattern, repeats), seg_p, seg_c in zip(
            cfg.schedule, params["segments"], cache):

        def body(x_t, sc):
            layer_p, layer_c = sc
            new_entries = {}
            for j, kind in enumerate(pattern):
                x_t, new_entries[f"p{j}"] = block_decode(
                    kind, layer_p[f"p{j}"], x_t, layer_c[f"p{j}"], pos, cfg)
                x_t = x_t.astype(cdt)   # pin carry dtype
            return x_t, new_entries

        x_t, new_seg = jax.lax.scan(body, x_t, (seg_p, seg_c))
        new_caches.append(new_seg)

    return _lm_head(x_t, params, cfg), new_caches


# ===========================================================================
# Prefill: forward with cache emission, then reshape into decode layout
# ===========================================================================
def prefill(params, batch, cfg, max_len: int | None = None):
    """Run the full prompt, build the decode cache. Returns (last_logits,
    cache, n_prompt). The emitted per-layer K/V are padded to ``max_len``."""
    tokens = batch["tokens"]
    b, s = tokens.shape
    max_len = max_len or s
    logits, aux, raw_caches, ctx = forward(params, batch, cfg,
                                           return_cache=True)
    cdt = jnp.dtype(cfg.compute_dtype)

    caches = []
    for (pattern, repeats), seg_cache in zip(cfg.schedule, raw_caches):
        seg = {}
        for j, kind in enumerate(pattern):
            kv = seg_cache[f"p{j}"]
            seg[f"p{j}"] = _prefill_entry(kind, kv, cfg, b, s, max_len, cdt,
                                          ctx)
        caches.append(seg)
    return logits[:, -1], caches, s


def _pad_seq(x, max_len):
    """(R, B, S, ...) -> (R, B, max_len, ...) zero-padded."""
    pad = max_len - x.shape[2]
    if pad <= 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[2] = (0, pad)
    return jnp.pad(x, widths)


def _prefill_entry(kind, kv, cfg, b, s, max_len, cdt, ctx):
    if kind in ("attn", "attn_moe"):
        k, v = kv
        return {"k": _pad_seq(k.astype(cdt), max_len),
                "v": _pad_seq(v.astype(cdt), max_len)}
    if kind == "local":
        k, v = kv
        w = min(cfg.sliding_window, max_len)
        if s >= w:
            # keep the last `w` positions, laid out ring-buffer style:
            # position p lives at slot p % w
            tail_k, tail_v = k[:, :, -w:], v[:, :, -w:]
            slots = (jnp.arange(s - w, s)) % w
            order = jnp.argsort(slots)
            return {"k": tail_k[:, :, order].astype(cdt),
                    "v": tail_v[:, :, order].astype(cdt)}
        return {"k": _pad_seq(k.astype(cdt), w),
                "v": _pad_seq(v.astype(cdt), w)}
    if kind in MLA_KINDS:
        ckv, krope = kv
        return {"ckv": _pad_seq(ckv.astype(cdt), max_len),
                "krope": _pad_seq(krope.astype(cdt), max_len)}
    if kind == "cross":
        xk, xv = kv
        return {"xk": xk.astype(cdt), "xv": xv.astype(cdt)}
    if kind == "dec":
        (k, v), (xk, xv) = kv
        return {"k": _pad_seq(k.astype(cdt), max_len),
                "v": _pad_seq(v.astype(cdt), max_len),
                "xk": xk.astype(cdt), "xv": xv.astype(cdt)}
    if kind in ("mamba_dense", "mamba_moe"):
        return {"conv": kv["conv"].astype(cdt), "ssm": kv["ssm"]}
    if kind == "rwkv":
        return {"x_prev_tm": kv["x_prev_tm"].astype(cdt),
                "x_prev_cm": kv["x_prev_cm"].astype(cdt),
                "wkv": kv["wkv"]}
    raise ValueError(f"no prefill cache layout for block kind {kind!r}")


# ===========================================================================
# Paged serving path (DESIGN.md §12)
#
# The dense decode cache above charges every slot for max_len tokens.
# The serving engine replaces it with a global pool of fixed-size token
# blocks (serve/kv_cache.py) addressed through per-slot block tables;
# attention runs in the Pallas flash-decode kernel which gathers K/V
# straight through the table. Three entry points:
#
#   init_paged_pools(cfg, NB, bs)                  zeroed per-layer pools
#   prefill_chunk(params, scratch, tokens, ...)    one prompt chunk into a
#                                                  dense prefill scratch
#   write_prefill_to_pools(pools, scratch, ...)    scatter scratch -> blocks
#   decode_step_paged(params, pools, ...)          one token for every slot
#
# Only pure-attention schedules (attn / local / attn_moe) have a paged
# layout; recurrent-state families (Mamba, RWKV), MLA latents and
# encoder-decoder keep the dense engine.
# ===========================================================================
PAGED_KINDS = ("attn", "local", "attn_moe")


def paged_supported(cfg) -> bool:
    """True when every block in ``cfg.schedule`` has a paged layout."""
    return all(kind in PAGED_KINDS
               for pattern, _ in cfg.schedule for kind in pattern)


def _check_paged(cfg):
    if not paged_supported(cfg):
        bad = sorted({k for pattern, _ in cfg.schedule for k in pattern
                      if k not in PAGED_KINDS})
        raise ValueError(
            f"paged serving supports kinds {PAGED_KINDS}; {cfg.name!r} "
            f"has {bad} — use the dense ServeEngine for this family")


def init_paged_pools(cfg, num_blocks: int, block_size: int) -> list:
    """Zeroed paged K/V pools matching the segment/scan structure:
    ``pools[seg]['p{j}'] = {'k','v': (R, NB, bs, Hkv, hd)}``. Block ids
    are shared across layers — entry ``i`` of a block table addresses
    block ``i`` of every layer's pool."""
    _check_paged(cfg)
    cdt = jnp.dtype(cfg.compute_dtype)
    hkv, hd = cfg.n_kv_heads, cfg.hd
    pools = []
    for pattern, repeats in cfg.schedule:
        seg = {}
        for j, kind in enumerate(pattern):
            seg[f"p{j}"] = {
                "k": jnp.zeros((repeats, num_blocks, block_size, hkv, hd),
                               cdt),
                "v": jnp.zeros((repeats, num_blocks, block_size, hkv, hd),
                               cdt),
            }
        pools.append(seg)
    return pools


def init_prefill_scratch(cfg, max_prefill_len: int) -> list:
    """Dense per-layer K/V scratch used while chunk-prefilling ONE
    sequence; scattered into the paged pools afterwards. Unlike the
    decode cache, ``local`` layers get the full length here (the window
    is enforced by masks, not a ring buffer, so the scatter into blocks
    stays position-indexed)."""
    _check_paged(cfg)
    cdt = jnp.dtype(cfg.compute_dtype)
    hkv, hd = cfg.n_kv_heads, cfg.hd
    scratch = []
    for pattern, repeats in cfg.schedule:
        seg = {}
        for j, kind in enumerate(pattern):
            seg[f"p{j}"] = {
                "k": jnp.zeros((repeats, 1, max_prefill_len, hkv, hd), cdt),
                "v": jnp.zeros((repeats, 1, max_prefill_len, hkv, hd), cdt),
            }
        scratch.append(seg)
    return scratch


def _paged_ffn(kind, p, x_t, cfg):
    """Post-attention half of a paged block: norm + SwiGLU or MoE.
    x_t: (B, d) (decode) or (B, C, d) (prefill chunk)."""
    h = rms_norm(x_t, p["ln2"]["scale"], cfg.norm_eps)
    if kind == "attn_moe":
        squeeze = h.ndim == 2
        m, _ = moe_ffn(p["moe"], h[:, None, :] if squeeze else h, cfg)
        return x_t + (m[:, 0] if squeeze else m)
    m = swiglu(h, p["mlp"]["wg"]["kernel"], p["mlp"]["wu"]["kernel"],
               p["mlp"]["wd"]["kernel"])
    return x_t + m


def _paged_gqa_decode(p, x_t, pool, block_table, pos, active, cfg, *,
                      window, num_splits):
    """One token of paged GQA attention. x_t: (B, d); pool {'k','v'}:
    (NB, bs, Hkv, hd); block_table: (B, MAXB); pos/active: (B,). The
    new K/V are scattered into each slot's current block (inactive
    slots scatter out-of-range and are dropped), then the flash-decode
    kernel attends through the table."""
    from repro.kernels.ops import flash_decode_op

    b, d = x_t.shape
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    nb, bs = pool["k"].shape[0], pool["k"].shape[1]

    def proj(w, t, h):
        y = t @ w["kernel"]
        if "bias" in w:
            y = y + w["bias"]
        return y.reshape(b, h, hd)

    q = proj(p["wq"], x_t, hq)
    k = proj(p["wk"], x_t, hkv)
    v = proj(p["wv"], x_t, hkv)
    if cfg.use_qk_norm:
        q = _qk_norm(q, p["q_norm_scale"])
        k = _qk_norm(k, p["k_norm_scale"])
    cos, sin = rope_at(pos, hd, cfg.rope_theta)
    q = _rope_decode(q, cos, sin)
    k = _rope_decode(k, cos, sin)

    rows = jnp.arange(b)
    blk = block_table[rows, pos // bs]
    dest = jnp.where(active, blk, nb)              # OOB -> dropped
    off = pos % bs
    new_k = pool["k"].at[dest, off].set(k.astype(pool["k"].dtype),
                                        mode="drop")
    new_v = pool["v"].at[dest, off].set(v.astype(pool["v"].dtype),
                                        mode="drop")
    lengths = jnp.where(active, pos + 1, 0)
    out = flash_decode_op(q, new_k, new_v, block_table, lengths,
                          window=window, num_splits=num_splits)
    y = out.reshape(b, hq * hd) @ p["wo"]["kernel"]
    if "bias" in p["wo"]:
        y = y + p["wo"]["bias"]
    return y, {"k": new_k, "v": new_v}


def decode_step_paged(params, pools, token, pos, block_table, active, cfg,
                      *, num_splits: int = 1):
    """One decode token for every scheduler slot against the paged pools.

    token/pos/active: (B,) — per-slot lanes (B = slot capacity, fixed);
    block_table: (B, MAXB) int32. Inactive slots cost compute but write
    nothing and read length-0 caches (zero attention output), so batch
    composition can churn without retracing. Returns (logits (B, vocab),
    new_pools)."""
    cdt = jnp.dtype(cfg.compute_dtype)
    params = cast_params(params, cfg)
    pos = jnp.asarray(pos, jnp.int32)
    active = jnp.asarray(active, bool)
    x_t = params["embed"]["kernel"][token]

    new_pools = []
    for (pattern, repeats), seg_p, seg_pool in zip(
            cfg.schedule, params["segments"], pools):

        def body(x_t, sc):
            layer_p, layer_pool = sc
            new_entries = {}
            for j, kind in enumerate(pattern):
                p, pool = layer_p[f"p{j}"], layer_pool[f"p{j}"]
                window = cfg.sliding_window if kind == "local" else None
                h = rms_norm(x_t, p["ln1"]["scale"], cfg.norm_eps)
                a, new_entries[f"p{j}"] = _paged_gqa_decode(
                    p["attn"], h, pool, block_table, pos, active, cfg,
                    window=window, num_splits=num_splits)
                x_t = _paged_ffn(kind, p, x_t + a, cfg).astype(cdt)
            return x_t, new_entries

        x_t, new_seg = jax.lax.scan(body, x_t, (seg_p, seg_pool))
        new_pools.append(new_seg)

    return _lm_head(x_t, params, cfg), new_pools


def prefill_chunk(params, scratch, tokens, start, take_idx, cfg):
    """Run one prompt chunk through the model, extending the prefill
    scratch. tokens: (1, C) (right-padded garbage is fine — causal
    masking keeps it out of valid positions); start: scalar int32
    absolute position of tokens[:, 0]; take_idx: scalar int32 chunk-
    local index whose logits to return (the prompt's last token on the
    final chunk; ignored otherwise). Returns (logits (1, vocab),
    new_scratch)."""
    _check_paged(cfg)
    cdt = jnp.dtype(cfg.compute_dtype)
    params = cast_params(params, cfg)
    b, c = tokens.shape
    start = jnp.asarray(start, jnp.int32)
    x = params["embed"]["kernel"][tokens]          # (1, C, d)
    hkv, hd = cfg.n_kv_heads, cfg.hd

    def attn_chunk(p, h, scr, window):
        q = (h @ p["wq"]["kernel"])
        k = (h @ p["wk"]["kernel"])
        v = (h @ p["wv"]["kernel"])
        if "bias" in p["wq"]:
            q, k, v = (q + p["wq"]["bias"], k + p["wk"]["bias"],
                       v + p["wv"]["bias"])
        q = q.reshape(b, c, cfg.n_heads, hd)
        k = k.reshape(b, c, hkv, hd)
        v = v.reshape(b, c, hkv, hd)
        if cfg.use_qk_norm:
            q = _qk_norm(q, p["q_norm_scale"])
            k = _qk_norm(k, p["k_norm_scale"])
        qpos = start + jnp.arange(c, dtype=jnp.int32)
        cos, sin = rope_tables_at(qpos, hd, cfg.rope_theta)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
        new_k = jax.lax.dynamic_update_slice(
            scr["k"], k.astype(scr["k"].dtype), (0, start, 0, 0))
        new_v = jax.lax.dynamic_update_slice(
            scr["v"], v.astype(scr["v"].dtype), (0, start, 0, 0))
        s = scr["k"].shape[1]
        kpos = jnp.arange(s, dtype=jnp.int32)[None, :]
        mask = kpos <= qpos[:, None]               # causal w/ offset
        if window is not None:
            mask &= kpos >= qpos[:, None] - window + 1
        out = chunk_attention(q, new_k, new_v, mask)
        y = out.reshape(b, c, cfg.n_heads * hd) @ p["wo"]["kernel"]
        if "bias" in p["wo"]:
            y = y + p["wo"]["bias"]
        return y, {"k": new_k, "v": new_v}

    new_scratch = []
    for (pattern, repeats), seg_p, seg_scr in zip(
            cfg.schedule, params["segments"], scratch):

        def body(x, sc):
            layer_p, layer_scr = sc
            new_entries = {}
            for j, kind in enumerate(pattern):
                p = layer_p[f"p{j}"]
                window = cfg.sliding_window if kind == "local" else None
                h = rms_norm(x, p["ln1"]["scale"], cfg.norm_eps)
                a, new_entries[f"p{j}"] = attn_chunk(
                    p["attn"], h, layer_scr[f"p{j}"], window)
                x = _paged_ffn(kind, p, x + a, cfg).astype(cdt)
            return x, new_entries

        x, new_seg = jax.lax.scan(body, x, (seg_p, seg_scr))
        new_scratch.append(new_seg)

    take_idx = jnp.asarray(take_idx, jnp.int32)
    x_last = jnp.take_along_axis(x, take_idx.reshape(1, 1, 1), axis=1)[:, 0]
    return _lm_head(x_last, params, cfg), new_scratch


def write_prefill_to_pools(pools, scratch, block_ids, length,
                           block_size: int):
    """Scatter a finished prefill scratch into the paged pools.

    block_ids: (MAXB,) int32 — the sequence's block table (padded);
    length: scalar int32 valid tokens. Whole blocks are written (the
    tail of the last block holds garbage that stays masked by
    ``length``); entries past ``ceil(length / bs)`` scatter out of
    range and are dropped."""
    length = jnp.asarray(length, jnp.int32)
    nblocks = (length + block_size - 1) // block_size

    def write(pool, scr):
        r, nb_pool, bs = pool.shape[0], pool.shape[1], pool.shape[2]
        s = scr.shape[2]
        ncols = s // bs
        blocks = scr.reshape(r, ncols, bs, *scr.shape[3:])
        dest = jnp.where(jnp.arange(ncols) < nblocks,
                         block_ids[:ncols].astype(jnp.int32), nb_pool)
        return pool.at[:, dest].set(blocks.astype(pool.dtype), mode="drop")

    return jax.tree.map(write, pools, scratch)
