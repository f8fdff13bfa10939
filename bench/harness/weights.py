"""Seeded weights of a dense GQA decoder, made by the benchmark.

Every leaf of layer ``l`` depends on the seed and ``l`` alone, so the plain
reference remakes one layer at a time after the program's state is freed,
bit for bit the values the program was given. The program receives the
whole tree, made on the device in one jitted call in the served dtype.

Matrices are N(0, 0.02) (``initializer_range`` of both configurations'
``config.json``); norm scales are 1 + N(0, 0.1), so a path that ignores a
norm's scale disagrees with the reference.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from harness.spec import Dims

INIT_STD = 0.02
NORM_STD = 0.1
_TOP = 0xFFFFFFF0  # fold-in index of the non-layer leaves


def layer_shapes(d: Dims) -> dict:
    D, F = d.d_model, d.d_ff
    q, kv = d.heads * d.head_dim, d.kv_heads * d.head_dim
    return {"ln_attn": (D,), "wq": (D, q), "wk": (D, kv), "wv": (D, kv),
            "wo": (q, D), "ln_mlp": (D,), "w_gate": (D, F), "w_up": (D, F),
            "w_down": (F, D)}


def top_shapes(d: Dims) -> dict:
    out = {"embed": (d.padded_vocab, d.d_model), "final_norm": (d.d_model,)}
    if not d.tied:
        out["lm_head"] = (d.d_model, d.padded_vocab)
    return out


def seed_key(seed: int):
    """A key for any whole number up to 64 bits."""
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _leaves(key, shapes: dict, dtype) -> dict:
    out = {}
    for i, (name, shape) in enumerate(shapes.items()):
        z = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        is_norm = name.startswith("ln") or name.endswith("norm")
        out[name] = ((1.0 + NORM_STD * z) if is_norm
                     else INIT_STD * z).astype(dtype)
    return out


def layer(d: Dims, key, index) -> dict:
    """Leaves of layer ``index`` (a traced or a Python int)."""
    return _leaves(jax.random.fold_in(key, index), layer_shapes(d),
                   jnp.dtype(d.dtype))


def top(d: Dims, key) -> dict:
    return _leaves(jax.random.fold_in(key, _TOP), top_shapes(d),
                   jnp.dtype(d.dtype))


def make(d: Dims, seed: int) -> dict:
    """The whole tree ``{"layers": {leaf: [L, ...]}, **top}``, made on the
    default device in one call; layers are made one after another so that
    no more than one layer's float32 draw is alive at a time."""
    def build(key):
        layers = jax.lax.map(lambda i: layer(d, key, i),
                             jnp.arange(d.layers, dtype=jnp.uint32))
        return {"layers": layers, **top(d, key)}
    return jax.jit(build)(seed_key(seed))


def layer_fn(d: Dims, seed: int):
    """``f(index) -> leaves`` of one layer, jitted once for every index."""
    key = seed_key(seed)
    fn = jax.jit(lambda k, i: layer(d, k, i))
    return lambda index: fn(key, jnp.uint32(index))


def top_of(d: Dims, seed: int) -> dict:
    return jax.jit(lambda k: top(d, k))(seed_key(seed))
