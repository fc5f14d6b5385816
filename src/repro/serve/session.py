"""Sessions, requests, and per-sequence sampling state.

The serving engine is multi-tenant: every generation is a
:class:`Request` carrying its own prompt, token budget, stop condition
and :class:`SamplingParams`. Requests live in fixed-capacity *slots*
while decoding (serve/scheduler.py); everything per-sequence that the
jit'd step needs — temperature, top-k, top-p, the PRNG key lane — rides
in slot-indexed device arrays so batch composition can change without
retracing.

Sampling itself is in-trace (:func:`sample_tokens`): one (B, V) logits
block in, one (B,) token lane out, with per-row temperature / top-k /
top-p masking and per-row PRNG keys. Greedy rows (temperature <= 0)
take the argmax; the key lanes are folded with the row's position
in-trace so a sequence's sample stream depends only on its own seed and
positions, never on which slot it landed in or who else is in the
batch.

:class:`Session` is the tenant-facing wrapper: it namespaces request
ids, applies tenant-default sampling, and hands out
:class:`GenerationHandle` objects for streaming (callback or iterator)
and cancellation.
"""
from __future__ import annotations

import dataclasses
import itertools
import time
from typing import Callable, Iterator, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

_NEG = np.float32(-1e30)   # numpy: building it touches no device


# ---------------------------------------------------------------------------
# sampling
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Per-request sampling knobs. ``temperature <= 0`` means greedy
    (top_k / top_p are then ignored). ``top_k <= 0`` disables top-k;
    ``top_p >= 1`` disables nucleus filtering."""
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    seed: int = 0

    def key_data(self) -> np.ndarray:
        """Raw uint32 key lane for this request's PRNG stream."""
        return np.asarray(jax.random.key_data(
            jax.random.PRNGKey(self.seed)), np.uint32)


def _mask_top_k(scaled: jax.Array, top_k: jax.Array) -> jax.Array:
    v = scaled.shape[-1]
    desc = -jnp.sort(-scaled, axis=-1)
    kth = jnp.take_along_axis(
        desc, jnp.clip(top_k - 1, 0, v - 1)[:, None], axis=-1)
    keep = (top_k <= 0)[:, None] | (scaled >= kth)
    return jnp.where(keep, scaled, _NEG)


def _mask_top_p(scaled: jax.Array, top_p: jax.Array) -> jax.Array:
    b = scaled.shape[0]
    probs = jax.nn.softmax(scaled, axis=-1)
    order = jnp.argsort(-probs, axis=-1)
    sp = jnp.take_along_axis(probs, order, axis=-1)
    # keep the smallest prefix whose mass reaches top_p (always >= 1 token)
    keep_sorted = (jnp.cumsum(sp, axis=-1) - sp) < top_p[:, None]
    keep = jnp.zeros_like(keep_sorted).at[
        jnp.arange(b)[:, None], order].set(keep_sorted)
    return jnp.where(keep, scaled, _NEG)


def sample_tokens(logits: jax.Array, keys: jax.Array,
                  temperature: jax.Array, top_k: jax.Array,
                  top_p: jax.Array) -> jax.Array:
    """In-trace batched sampling with per-row parameters.

    logits (B, V) f32; keys (B, 2) uint32 raw key lanes; temperature /
    top_p (B,) f32; top_k (B,) int32. Returns (B,) int32 tokens. Every
    row is computed independently (vmap'd categorical over the row's own
    key), so a row's sample never depends on its neighbours.
    """
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    t = jnp.maximum(temperature, 1e-6)[:, None]
    scaled = (logits.astype(jnp.float32)) / t
    scaled = _mask_top_k(scaled, top_k)
    scaled = _mask_top_p(scaled, top_p)
    sampled = jax.vmap(
        lambda k, row: jax.random.categorical(
            jax.random.wrap_key_data(k), row))(keys, scaled)
    return jnp.where(temperature <= 0.0, greedy,
                     sampled.astype(jnp.int32))


def fold_keys(keys: jax.Array, pos: jax.Array) -> jax.Array:
    """Fold each row's position into its key lane (in-trace), so step t
    of a sequence uses the same key no matter when it was admitted."""
    def one(k, p):
        folded = jax.random.fold_in(jax.random.wrap_key_data(k), p)
        return jax.random.key_data(folded)
    return jax.vmap(one)(keys, pos)


# ---------------------------------------------------------------------------
# requests and handles
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Request:
    """One generation job. ``prompt`` is a 1-D int token array/list."""
    request_id: str
    prompt: np.ndarray
    max_new_tokens: int = 32
    sampling: SamplingParams = dataclasses.field(
        default_factory=SamplingParams)
    eos_id: Optional[int] = None

    def __post_init__(self):
        self.prompt = np.asarray(self.prompt, np.int32).reshape(-1)
        if self.prompt.size == 0:
            raise ValueError("empty prompt")
        if self.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")


class GenerationHandle:
    """Live view of one request: collected tokens, completion state,
    streaming, cancellation. Produced by ``PagedServeEngine.submit``.

    Lifecycle wall-clock timestamps (``time.perf_counter`` seconds) are
    stamped by the engine at its existing host boundaries — submit,
    admission, each token's host readback, finish — so per-request
    latencies (queue wait, TTFT, inter-token, end-to-end) are always
    reconstructable from the handle, with or without the obs layer:
    ``t_submit`` / ``t_admit`` / ``t_finish`` plus ``token_times[i]``
    (the emission time of ``tokens[i]``).
    """

    def __init__(self, request: Request, engine,
                 on_token: Optional[Callable[[Request, int], None]] = None):
        self.request = request
        self.tokens: list[int] = []
        self.finish_reason: Optional[str] = None
        self.t_submit: Optional[float] = None
        self.t_admit: Optional[float] = None
        self.t_finish: Optional[float] = None
        self.token_times: list[float] = []
        self._engine = engine
        self._on_token = on_token

    @property
    def done(self) -> bool:
        return self.finish_reason is not None

    # called by the engine ------------------------------------------------
    def _emit(self, token: int) -> None:
        self.tokens.append(token)
        self.token_times.append(time.perf_counter())
        if self._on_token is not None:
            self._on_token(self.request, token)

    def _finish(self, reason: str) -> None:
        if self.finish_reason is None:
            self.finish_reason = reason
            self.t_finish = time.perf_counter()

    # latency views --------------------------------------------------------
    @property
    def queue_wait(self) -> Optional[float]:
        """Seconds between submission and admission (None until admitted
        — e.g. a request cancelled while still queued)."""
        if self.t_admit is None or self.t_submit is None:
            return None
        return self.t_admit - self.t_submit

    @property
    def ttft(self) -> Optional[float]:
        """Time to first token, measured from submission (includes queue
        wait and prefill)."""
        if not self.token_times or self.t_submit is None:
            return None
        return self.token_times[0] - self.t_submit

    def inter_token_latencies(self) -> list[float]:
        """Gaps between consecutive token emissions (empty for <2
        tokens). The engine emits at decode-step boundaries, so each gap
        is quantized to whole decode steps."""
        tt = self.token_times
        return [b - a for a, b in zip(tt, tt[1:])]

    @property
    def e2e(self) -> Optional[float]:
        """End-to-end seconds from submission to finish."""
        if self.t_finish is None or self.t_submit is None:
            return None
        return self.t_finish - self.t_submit

    def latency_summary(self) -> dict:
        """Per-request latency record (the ``--metrics`` table row)."""
        itl = self.inter_token_latencies()
        return {
            "request_id": self.request.request_id,
            "finish_reason": self.finish_reason,
            "n_tokens": len(self.tokens),
            "queue_wait": self.queue_wait,
            "ttft": self.ttft,
            "itl_mean": sum(itl) / len(itl) if itl else None,
            "e2e": self.e2e,
        }

    # called by the tenant -------------------------------------------------
    def cancel(self) -> None:
        """Stop this request at the next step boundary; its cache blocks
        return to the pool. Queued requests leave the queue immediately."""
        self._engine.cancel(self.request.request_id)

    def stream(self) -> Iterator[int]:
        """Yield this request's tokens as they are produced, pumping the
        engine while other tenants' requests make progress too."""
        seen = 0
        while True:
            while seen < len(self.tokens):
                yield self.tokens[seen]
                seen += 1
            if self.done:
                return
            self._engine.step()


class Session:
    """A tenant's view of a shared engine: namespaced request ids plus
    default sampling params. Multiple sessions submit into the same
    engine and their requests interleave in the continuous batch."""

    _ids = itertools.count()

    def __init__(self, engine, name: Optional[str] = None,
                 default_sampling: SamplingParams = SamplingParams()):
        self.engine = engine
        self.name = name or f"session{next(Session._ids)}"
        self.default_sampling = default_sampling
        self._req_ids = itertools.count()
        self.handles: dict[str, GenerationHandle] = {}

    def submit(self, prompt: Sequence[int], *, max_new_tokens: int = 32,
               sampling: Optional[SamplingParams] = None,
               eos_id: Optional[int] = None,
               on_token: Optional[Callable[[Request, int], None]] = None,
               ) -> GenerationHandle:
        rid = f"{self.name}/r{next(self._req_ids)}"
        req = Request(rid, np.asarray(prompt, np.int32),
                      max_new_tokens=max_new_tokens,
                      sampling=sampling or self.default_sampling,
                      eos_id=eos_id)
        handle = self.engine.submit(req, on_token=on_token)
        self.handles[rid] = handle
        return handle
