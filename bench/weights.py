"""Seeded weights for a cell, shared by the program's state and the
reference: each leaf is drawn from its own key (the seed folded with a
hash of the leaf's path), so one leaf can be drawn again alone.

Common rules by path: embedding and LM head N(0, 0.02^2); a ``kernel`` of
shape (..., d_in, d_out) N(0, 1/d_in); norm scales N(0, 0.1^2) around the
``1 + scale`` convention; biases N(0, 0.02^2). Non-zero norms and biases
keep every parameter on a path that the gradient reaches.

A leaf that no common rule knows takes its std from the configuration
file's ``init``: ``{leaf-path suffix: "fan_in" | std}``, the suffix matched
by whole path components and ``"fan_in"`` meaning ``1/sqrt(shape[-2])``.
A suffix that matches a leaf of the common rules is an error, so a file
cannot change the draw of a leaf they cover.
"""
from __future__ import annotations

import functools
import math
import zlib

import jax
import jax.numpy as jnp


def _common_std(name: list, shape) -> float | None:
    if name[0] in ("embed", "unembed"):
        return 0.02
    if name[-1] == "kernel":
        return 1.0 / math.sqrt(shape[-2])
    if name[-1] == "scale":
        return 0.1
    if name[-1] == "bias":
        return 0.02
    return None


def leaf_std(path: str, shape, init: dict | None = None) -> float:
    name = path.split("/")
    rules = [s for s in (init or {})
             if name[-len(s.split("/")):] == s.split("/")]
    std = _common_std(name, shape)
    if std is not None:
        if rules:
            raise ValueError(f"init rule {rules[0]!r} would change leaf "
                             f"{path!r}, which a common rule covers")
        return std
    if not rules:
        raise ValueError(f"no init rule for leaf {path!r}")
    if len(rules) > 1:
        raise ValueError(f"init rules {sorted(rules)} all match leaf {path!r}")
    rule = init[rules[0]]
    return 1.0 / math.sqrt(shape[-2]) if rule == "fan_in" else float(rule)


def leaf_key(key: jax.Array, path: str) -> jax.Array:
    return jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)


def draw_leaf(key, path: str, shape, dtype, init: dict | None = None):
    x = jax.random.normal(leaf_key(key, path), shape, jnp.float32)
    return (x * leaf_std(path, shape, init)).astype(dtype)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _draw(key, salt, shape, dtype, std):
    x = jax.random.normal(jax.random.fold_in(key, salt), shape, jnp.float32)
    return (x * std).astype(dtype)


def draw_one(key, path: str, shape, dtype, init: dict | None = None):
    """One leaf alone, equal to its entry of :func:`draw_all`; compiled
    once per shape, not per path."""
    salt = jnp.uint32(zlib.crc32(path.encode()) & 0x7FFFFFFF)
    return _draw(key, salt, tuple(shape), jnp.dtype(dtype),
                 leaf_std(path, shape, init))


def draw_all(key, specs: dict, init: dict | None = None) -> dict:
    """``{path: (shape, dtype)} -> {path: array}`` in one jitted call."""
    items = sorted(specs.items())

    @jax.jit
    def make(k):
        return {p: draw_leaf(k, p, tuple(s), jnp.dtype(d), init)
                for p, (s, d) in items}

    return make(key)
