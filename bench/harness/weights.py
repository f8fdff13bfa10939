"""Seeded weights of a configuration, made by the benchmark.

The family module declares the tree: leaves outside the layers
(``top_shapes``) and an ordered list of layer groups (``layer_groups``),
each ``(tree key, number of layers, {leaf: shape})``, with layers numbered
globally across the groups. Layer ``l`` is drawn from ``fold_in(key, l)``
and its leaf ``i`` from ``fold_in(., i)`` in declaration order; the top
leaves from ``fold_in(key, _TOP)``. So every leaf of a layer depends on the
seed and its global index alone, and the plain reference remakes one layer
at a time after the program's state is freed, bit for bit the values the
program was given. The program receives the whole tree, made on the device
in one jitted call in the served dtype, each group stacked ``[n, ...]``
under its key.

Matrices are N(0, 0.02) (``initializer_range`` of the configurations'
``config.json``); norm scales (a leaf named ``ln*`` or ``*norm``) are
1 + N(0, 0.1), so a path that ignores a norm's scale disagrees with the
reference.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

INIT_STD = 0.02
NORM_STD = 0.1
_TOP = 0xFFFFFFF0  # fold-in index of the non-layer leaves


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


def _groups(d) -> list:
    """``(tree key, first global layer, number of layers, leaf shapes)`` of
    each of the family's layer groups, in order."""
    out, first = [], 0
    for name, n, shapes in d.family.layer_groups(d):
        out.append((name, first, n, shapes))
        first += n
    return out


def _layer(key, index, shapes: dict, dtype) -> dict:
    """Leaves of layer ``index`` (a traced or a Python int)."""
    return _leaves(jax.random.fold_in(key, index), shapes, dtype)


def top(d, key) -> dict:
    return _leaves(jax.random.fold_in(key, _TOP), d.family.top_shapes(d),
                   jnp.dtype(d.dtype))


def make(d, seed: int) -> dict:
    """The whole tree ``{group: {leaf: [n, ...]}, **top}``, made on the
    default device in one call; a group's layers are made one after another
    so that no more than one layer's float32 draw is alive at a time."""
    dtype = jnp.dtype(d.dtype)

    def build(key):
        tree = {}
        for name, first, n, shapes in _groups(d):
            tree[name] = jax.lax.map(
                lambda i, shapes=shapes: _layer(key, i, shapes, dtype),
                jnp.arange(first, first + n, dtype=jnp.uint32))
        return {**tree, **top(d, key)}
    return jax.jit(build)(seed_key(seed))


def layer_fn(d, seed: int):
    """``f(index) -> leaves`` of one layer, whichever group holds it;
    jitted once for each group."""
    key = seed_key(seed)
    dtype = jnp.dtype(d.dtype)
    fns = [(first, n, jax.jit(lambda k, i, shapes=shapes:
                              _layer(k, i, shapes, dtype)))
           for _, first, n, shapes in _groups(d)]

    def layer(index: int) -> dict:
        for first, n, fn in fns:
            if first <= index < first + n:
                return fn(key, jnp.uint32(index))
        raise IndexError(f"no layer {index}")
    return layer


def top_of(d, seed: int) -> dict:
    return jax.jit(lambda k: top(d, k))(seed_key(seed))
