"""Plain reference for the dense decoder cells (qwen2.5-32b, phi3-mini):
the model's loss and gradients, and DCT-AdamW, written from their
published descriptions in ``jax.numpy`` and float32. It imports nothing of
the program and takes nothing the program made: it draws its own weights
and tokens from the seed with the benchmark's ``weights`` and
``synthetic`` modules.

Model (per layer, pre-norm): ``x += Wo attn(rope(h Wq + bq), rope(h Wk +
bk), h Wv + bv)``, ``x += Wd (silu(h Wg) * h Wu)``, with ``h = rms(x) (1 +
s)``; causal grouped-query softmax attention with rotate-half RoPE; final
norm, untied LM head, mean next-token cross-entropy. Gradients are clipped
to global norm ``grad_clip``.

DCT-AdamW (paper Alg. 2, update interval 1): matrices with both sides >= 8
that are not embeddings, norms or biases are oriented so the projected
side ``n`` is the shorter, the int8 error feedback is added, ``S = G Q``
with ``Q`` the orthonormal DCT-II, the ``r`` columns of largest norm are
kept (in ascending index order), the moments follow the kept columns
(``R = [i_prev == i_new]``), Adam runs on ``g = S[:, idx]``, the update
``u Q_r^T`` and the residual ``G - g Q_r^T`` (stored as int8 with a
per-row scale) leave the subspace. Every other leaf takes AdamW. The step
is ``-lr (d + wd p)``, added to the weights in their stored dtype.

It runs layer by layer so that it fits beside nothing: the forward keeps
each layer's input, the backward takes one layer's ``vjp`` at a time, and
the optimizer updates one layer's slice of a leaf at a time.

``mode`` sets the precision of every matmul: ``"fp32"`` at HIGHEST
precision (the reference), or ``"fp8"``, the operands of the forward and
of both backward matmuls rounded to float8 e4m3 with a per-tensor scale
(the control, one step below the bf16 passes the configuration states).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from .. import synthetic, weights

HIGHEST = jax.lax.Precision.HIGHEST
# faults planted in the reference to read what the check sees of them:
# one token of each batch altered where it is made, and half of each
# batch left out (the mean taken over the other half)
FAULTS = (None, "token", "half_batch")
FULL_HINTS = ("embed", "unembed", "lm_head", "vocab", "norm", "scale",
              "bias", "pos_emb", "a_log", "dt", "decay", "conv")
LAYER = "segments/0/p0/"
Q_CHUNK = 512


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------
def param_specs(model: dict) -> dict:
    """``{path: (shape, dtype)}``; layer leaves stacked over layers."""
    d, f, v = model["d_model"], model["d_ff"], model["vocab_size"]
    hq, hkv, hd, n = (model["n_heads"], model["n_kv_heads"],
                      model["head_dim"], model["n_layers"])
    dt = model["param_dtype"]
    specs = {
        "embed/kernel": ((v, d), dt),
        "unembed/kernel": ((v, d), dt),
        "final_norm/scale": ((d,), "float32"),
        LAYER + "ln1/scale": ((n, d), "float32"),
        LAYER + "ln2/scale": ((n, d), "float32"),
        LAYER + "attn/wq/kernel": ((n, d, hq * hd), dt),
        LAYER + "attn/wk/kernel": ((n, d, hkv * hd), dt),
        LAYER + "attn/wv/kernel": ((n, d, hkv * hd), dt),
        LAYER + "attn/wo/kernel": ((n, hq * hd, d), dt),
        LAYER + "mlp/wg/kernel": ((n, d, f), dt),
        LAYER + "mlp/wu/kernel": ((n, d, f), dt),
        LAYER + "mlp/wd/kernel": ((n, f, d), dt),
    }
    if model["qkv_bias"]:
        specs[LAYER + "attn/wq/bias"] = ((n, hq * hd), dt)
        specs[LAYER + "attn/wk/bias"] = ((n, hkv * hd), dt)
        specs[LAYER + "attn/wv/bias"] = ((n, hkv * hd), dt)
    return {p: (tuple(s), str(jnp.dtype(t))) for p, (s, t) in specs.items()}


def matmul_params(model: dict) -> int:
    """Parameters that take part in a matmul per token: every layer's
    projections and MLP, and the LM head. The embedding is a gather."""
    d, f, v = model["d_model"], model["d_ff"], model["vocab_size"]
    hq, hkv, hd = model["n_heads"], model["n_kv_heads"], model["head_dim"]
    layer = d * hq * hd + 2 * d * hkv * hd + hq * hd * d + 3 * d * f
    return model["n_layers"] * layer + v * d


def model_flops_per_token(model: dict, seq_len: int) -> float:
    """Forward and backward: ``6 N`` for the matmul parameters, plus causal
    attention, ``6 S hq hd`` per layer (``QK^T`` and ``PV`` over the
    lower triangle, backward twice the forward)."""
    attn = 6 * seq_len * model["n_heads"] * model["head_dim"]
    return 6.0 * matmul_params(model) + model["n_layers"] * attn


def is_lowrank(path: str, shape) -> bool:
    if any(h in path.lower() for h in FULL_HINTS):
        return False
    return len(shape) >= 2 and min(shape[-2:]) >= 8


def split_layers(flat: dict) -> dict:
    """Layer leaves become per-layer lists, so that one layer's slice is
    read and written without copying the stack."""
    return {p: ([x[i] for i in range(x.shape[0])] if p.startswith(LAYER)
                else x) for p, x in flat.items()}


# ---------------------------------------------------------------------------
# matmuls at the mode's precision
# ---------------------------------------------------------------------------
def _fp8(x):
    """Per-tensor-scaled float8 e4m3 rounding."""
    xf = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(xf)), 1e-30) / 448.0
    return (xf / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _exact(spec, a, b):
    return jnp.einsum(spec, a, b, precision=HIGHEST)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _ein_fp8(spec, a, b):
    return _exact(spec, _fp8(a), _fp8(b))


def _ein_fp8_fwd(spec, a, b):
    qa, qb = _fp8(a), _fp8(b)
    return _exact(spec, qa, qb), (qa, qb)


def _ein_fp8_bwd(spec, res, g):
    # the backward matmuls take float8 operands too: the rounded forward
    # operands and the rounded cotangent
    _, vjp = jax.vjp(functools.partial(_exact, spec), *res)
    return vjp(_fp8(g))


_ein_fp8.defvjp(_ein_fp8_fwd, _ein_fp8_bwd)


def ein(spec, a, b, mode):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if mode == "fp8":
        return _ein_fp8(spec, a, b)
    return _exact(spec, a, b)


# ---------------------------------------------------------------------------
# model
# ---------------------------------------------------------------------------
def rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale)


def rope(x, theta):
    """Rotate-half RoPE on (B, S, H, hd)."""
    s, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    freqs = theta ** (-np.arange(half, dtype=np.float64) / half)
    ang = np.arange(s, dtype=np.float64)[:, None] * freqs[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[:, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, mode):
    """Causal GQA softmax attention, one block of queries at a time.
    q: (B, S, Hq, hd); k, v: (B, S, Hkv, hd)."""
    b, s, hq, hd = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    qc = min(Q_CHUNK, s)
    nq = s // qc
    qg = q.reshape(b, nq, qc, hkv, g, hd)
    kpos = jnp.arange(s)

    def block(i):
        qi = qg[:, i]                                   # (B, qc, Hkv, G, hd)
        sc = ein("bqhgd,bkhd->bhgqk", qi, k, mode) / math.sqrt(hd)
        qpos = i * qc + jnp.arange(qc)
        mask = qpos[:, None] >= kpos[None, :]
        sc = jnp.where(mask, sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return ein("bhgqk,bkhd->bqhgd", p, v, mode)      # (B, qc, Hkv, G, hd)

    # recomputed in the backward pass: the saved softmax of every block
    # would not fit beside the rest
    out = jax.lax.map(jax.checkpoint(block), jnp.arange(nq))
    out = jnp.moveaxis(out, 0, 1).reshape(b, s, hq * hd)
    return out


def layer_fwd(lp, x, model, mode):
    hq, hkv, hd = model["n_heads"], model["n_kv_heads"], model["head_dim"]
    eps, theta = model["norm_eps"], model["rope_theta"]
    b, s, _ = x.shape
    h = rms(x, lp["ln1/scale"], eps)

    def proj(name, heads):
        y = ein("bsd,de->bse", h, lp[f"attn/{name}/kernel"], mode)
        if f"attn/{name}/bias" in lp:
            y = y + lp[f"attn/{name}/bias"]
        return y.reshape(b, s, heads, hd)

    q = rope(proj("wq", hq), theta)
    k = rope(proj("wk", hkv), theta)
    v = proj("wv", hkv)
    x = x + ein("bse,ed->bsd", attention(q, k, v, mode),
                lp["attn/wo/kernel"], mode)
    h = rms(x, lp["ln2/scale"], eps)
    gate = ein("bsd,df->bsf", h, lp["mlp/wg/kernel"], mode)
    up = ein("bsd,df->bsf", h, lp["mlp/wu/kernel"], mode)
    return x + ein("bsf,fd->bsd", jax.nn.silu(gate) * up,
                   lp["mlp/wd/kernel"], mode)


def head_loss(final_scale, unembed, x, targets, model, mode):
    h = rms(x, final_scale, model["norm_eps"])
    logits = ein("bsd,vd->bsv", h, unembed, mode)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return nll.mean()


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _layer_fwd(lp, x, model_items, mode):
    return layer_fwd(_f32(lp), x, dict(model_items), mode)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _layer_bwd(lp, x, dy, model_items, mode):
    _, vjp = jax.vjp(lambda p, xx: layer_fwd(p, xx, dict(model_items), mode),
                     _f32(lp), x)
    return vjp(dy)


@functools.partial(jax.jit, static_argnums=(4, 5))
def _head(final_scale, unembed, x, targets, model_items, mode):
    return jax.value_and_grad(
        lambda fs, ue, xx: head_loss(fs, ue, xx, targets, dict(model_items),
                                     mode), argnums=(0, 1, 2))(
        final_scale.astype(jnp.float32), unembed.astype(jnp.float32), x)


@functools.partial(jax.jit, static_argnums=(2,))
def _embed_grad(tokens, dx, vocab):
    d = dx.shape[-1]
    return jnp.zeros((vocab, d), jnp.float32).at[tokens.reshape(-1)].add(
        dx.reshape(-1, d))


def loss_and_grads(params, tokens, targets, model, mode, acc=None,
                   scale=1.0):
    """Mean loss of one micro-batch and its fp32 gradients times
    ``scale``, layer by layer, added into ``acc`` as each is made (so
    that one micro-batch's gradients never exist beside the sum)."""
    items = tuple(sorted(model.items()))
    n = model["n_layers"]
    layer_paths = [p for p in params if p.startswith(LAYER)]
    x = params["embed/kernel"].astype(jnp.float32)[tokens]
    xs = []
    for i in range(n):
        lp = {p[len(LAYER):]: params[p][i] for p in layer_paths}
        xs.append(x)
        x = _layer_fwd(lp, x, items, mode)
    loss, (g_fs, g_ue, dx) = _head(params["final_norm/scale"],
                                   params["unembed/kernel"], x, targets,
                                   items, mode)
    if acc is None:
        acc = {p: ([None] * n if p.startswith(LAYER) else None)
               for p in params}

    def add(a, g):
        return g * scale if a is None else a + g * scale

    acc["final_norm/scale"] = add(acc["final_norm/scale"], g_fs)
    acc["unembed/kernel"] = add(acc["unembed/kernel"], g_ue)
    del g_fs, g_ue
    for i in reversed(range(n)):
        lp = {p[len(LAYER):]: params[p][i] for p in layer_paths}
        glp, dx = _layer_bwd(lp, xs[i], dx, items, mode)
        xs[i] = None
        for name in list(glp):
            acc[LAYER + name][i] = add(acc[LAYER + name][i], glp.pop(name))
    acc["embed/kernel"] = add(acc["embed/kernel"],
                              _embed_grad(tokens, dx, model["vocab_size"]))
    return loss, acc


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------
def dct2_basis(n: int) -> jax.Array:
    """Orthonormal DCT-II: ``(x @ Q)[k] = sum_j x[j] Q[j, k]``, float64
    on the host, then float32."""
    j = np.arange(n, dtype=np.float64)[:, None]
    k = np.arange(n, dtype=np.float64)[None, :]
    q = np.sqrt(2.0 / n) * np.cos(np.pi * k * (2.0 * j + 1.0) / (2.0 * n))
    q[:, 0] /= np.sqrt(2.0)
    return jnp.asarray(q, jnp.float32)


def lr_at(job: dict, t: int) -> float:
    """Cosine decay with linear warm-up, final fraction 0.1."""
    lr, warm, total = job["lr"], job["warmup_steps"], job["schedule_steps"]
    if t < warm:
        return lr * min(1.0, t / max(warm, 1))
    frac = min(max((t - warm) / max(total - warm, 1), 0.0), 1.0)
    return lr * (0.1 + 0.9 * 0.5 * (1.0 + math.cos(math.pi * frac)))


def quant_q8(x):
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.maximum(amax / 127.0, np.finfo(np.float32).tiny)
    return (jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8),
            scale)


@functools.partial(jax.jit, static_argnums=(10, 11), donate_argnums=(2, 3))
def _lowrank_slice(g, p, m, v, ef_q, ef_s, idx_prev, q, t, hyper, r, mode):
    """One layer's slice of a projected leaf. ``hyper`` = (lr, wd, b1,
    b2, eps, clip scale)."""
    lr, wd, b1, b2, eps, clip = (hyper[i] for i in range(6))
    gf = g.astype(jnp.float32) * clip
    transposed = gf.shape[-1] > gf.shape[-2]
    if transposed:
        gf = gf.T
    gf = gf + ef_q.astype(jnp.float32) * ef_s
    s = ein("mn,nk->mk", gf, q, mode)
    norms = jnp.sum(s * s, axis=0)
    _, idx = jax.lax.top_k(norms, r)
    idx = jnp.sort(idx).astype(jnp.int32)
    g_low = jnp.take(s, idx, axis=1)
    rot = (idx_prev[:, None] == idx[None, :]).astype(jnp.float32)
    m = jnp.einsum("mr,rs->ms", m, rot, precision=HIGHEST)
    v = jnp.abs(jnp.einsum("mr,rs->ms", v, rot, precision=HIGHEST))
    m = b1 * m + (1.0 - b1) * g_low
    v = b2 * v + (1.0 - b2) * g_low * g_low
    u = (m / (1.0 - b1 ** t)) / (jnp.sqrt(v / (1.0 - b2 ** t)) + eps)
    qr_t = jnp.take(q.T, idx, axis=0)
    d = ein("mr,rn->mn", u, qr_t, mode)
    recon = ein("mr,rn->mn", g_low, qr_t, mode)
    ef_q, ef_s = quant_q8(gf - recon)
    if transposed:
        d = d.T
    upd = -lr * d - lr * wd * p.astype(jnp.float32)
    return ((p + upd.astype(p.dtype)), m, v, ef_q, ef_s, idx)


@functools.partial(jax.jit, donate_argnums=(2, 3))
def _adamw(g, p, m, v, t, hyper):
    lr, wd, b1, b2, eps, clip = (hyper[i] for i in range(6))
    gf = g.astype(jnp.float32) * clip
    m = b1 * m + (1.0 - b1) * gf
    v = b2 * v + (1.0 - b2) * gf * gf
    u = (m / (1.0 - b1 ** t)) / (jnp.sqrt(v / (1.0 - b2 ** t)) + eps)
    upd = -lr * u - lr * wd * p.astype(jnp.float32)
    return p + upd.astype(p.dtype), m, v


def _sq(x):
    return jnp.sum(jnp.square(x.astype(jnp.float32)))


class Reference:
    """Trains the cell's configuration for ``steps`` steps from the seed
    and reports what the check compares."""

    def __init__(self, model: dict, job: dict, mode: str = "fp32",
                 fault: str | None = None, init: dict | None = None):
        if fault not in FAULTS:
            raise ValueError(f"unknown fault {fault!r}; have {FAULTS}")
        self.model, self.job, self.mode, self.fault = model, job, mode, fault
        self.init = init
        self.args = {"rank": 128, "weight_decay": 0.0, "b1": 0.9,
                     "b2": 0.999, "eps": 1e-8, **job["optimizer_args"]}
        self.specs = param_specs(model)

    def _init_state(self, params):
        r = self.args["rank"]
        state = {}
        for path, (shape, _) in self.specs.items():
            layers = path.startswith(LAYER)
            one = shape[1:] if layers else shape
            if is_lowrank(path, one):
                rows, cols = max(one[-2:]), min(one[-2:])
                rr = min(r, cols)
                mk = lambda: {  # noqa: E731
                    "m": jnp.zeros((rows, rr), jnp.float32),
                    "v": jnp.zeros((rows, rr), jnp.float32),
                    "ef_q": jnp.zeros((rows, cols), jnp.int8),
                    "ef_s": jnp.ones((rows, 1), jnp.float32),
                    "idx": jnp.arange(rr, dtype=jnp.int32), "t": 0}
                state[path] = [mk() for _ in range(shape[0])] if layers \
                    else mk()
            else:
                state[path] = {"m": jnp.zeros(shape, jnp.float32),
                               "v": jnp.zeros(shape, jnp.float32)}
        return state

    def run(self, seed: int, steps: int) -> dict:
        model, job, mode = self.model, self.job, self.mode
        a = self.args
        key = synthetic.seed_key(seed, 0)
        params = split_layers(weights.draw_all(key, self.specs, self.init))
        state = self._init_state(params)
        batch = synthetic.make_batch_fn(model["vocab_size"], job["seq_len"],
                                        job["rows_per_step"], seed,
                                        job.get("data", {}))
        # one row at a time: the mean over the step's rows is the mean of
        # the rows' means (every row has seq_len tokens), whatever the
        # program's micro-batches
        micro, n_micro = 1, job["rows_per_step"]
        bases = {}
        out = {"losses": [], "grad_norms": {}, "true_grad_norms": {},
               "delta_norms": {}}
        for t in range(1, steps + 1):
            b = batch(jnp.int32(t - 1))
            if self.fault == "token":
                mid = job["seq_len"] // 2
                tok = b["tokens"]
                b = dict(b, tokens=tok.at[0, mid].set(
                    (tok[0, mid] - 1) % (model["vocab_size"] - 2) + 2))
            elif self.fault == "half_batch":
                half = job["seq_len"] // 2
                b = {k: v[:, :half] for k, v in b.items()}
            losses, grads = [], None
            for i in range(n_micro):
                sl = slice(i * micro, (i + 1) * micro)
                loss, grads = loss_and_grads(
                    params, b["tokens"][sl], b["targets"][sl], model, mode,
                    grads, 1.0 / n_micro)
                losses.append(float(loss))
            out["losses"].append(float(np.mean(losses)))
            total = math.sqrt(sum(float(_sq(x)) for g in grads.values()
                                  for x in (g if isinstance(g, list)
                                            else [g])))
            clip = min(1.0, job["grad_clip"] / max(total, 1e-9))
            hyper = jnp.asarray([lr_at(job, t), a["weight_decay"], a["b1"],
                                 a["b2"], a["eps"], clip], jnp.float32)
            if t == 1:
                out["true_grad_norms"] = {
                    p: clip * math.sqrt(sum(float(_sq(x)) for x in (
                        g if isinstance(g, list) else [g])))
                    for p, g in grads.items()}
            for path in sorted(grads):
                g = grads.pop(path)
                if isinstance(state[path], list):
                    for i, gi in enumerate(g):
                        params[path][i], state[path][i] = self._update(
                            path, gi, params[path][i], state[path][i],
                            t, hyper, bases)
                        g[i] = None
                else:
                    params[path], state[path] = self._update(
                        path, g, params[path], state[path], t, hyper, bases)
            if t == 1:
                out["grad_norms"] = self._grad_norms(state)
        for path, (shape, dtype) in self.specs.items():
            x0 = weights.draw_one(key, path, shape, dtype, self.init)
            x = params[path]
            if isinstance(x, list):
                x = jnp.stack(x)
            out["delta_norms"][path] = float(jnp.linalg.norm(
                x.astype(jnp.float32) - x0.astype(jnp.float32)))
        return out

    def _update(self, path, g, p, s, t, hyper, bases):
        if "ef_q" in s:
            cols = min(g.shape[-2:])
            if cols not in bases:
                bases[cols] = dct2_basis(cols)
            s["t"] += 1
            p, m, v, ef_q, ef_s, idx = _lowrank_slice(
                g, p, s["m"], s["v"], s["ef_q"], s["ef_s"], s["idx"],
                bases[cols], jnp.float32(s["t"]), hyper, s["m"].shape[-1],
                self.mode)
            return p, {"m": m, "v": v, "ef_q": ef_q, "ef_s": ef_s,
                       "idx": idx, "t": s["t"]}
        layered = isinstance(p, list)
        if layered:
            g, p = jnp.stack(g), jnp.stack(p)
        p, m, v = _adamw(g, p, s["m"], s["v"], jnp.float32(t), hyper)
        return (list(p) if layered else p), {"m": m, "v": v}

    def _grad_norms(self, state) -> dict:
        b1 = self.args["b1"]
        out = {}
        for path, s in state.items():
            parts = s if isinstance(s, list) else [s]
            sq = 0.0
            for x in parts:
                sq += float(_sq(x["m"])) / (1.0 - b1) ** 2
                if "ef_q" in x:
                    sq += float(_sq(x["ef_q"].astype(jnp.float32)
                                    * x["ef_s"]))
            out[path] = math.sqrt(sq)
        return out

