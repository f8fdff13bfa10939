"""Topology keys for compiled-graph templating (paper §4.2.1).

The paper groups CUDA graphs by "node types in the same order with the same
dependency structure", treating kernel arguments and launch dimensions as
per-node *parameters* outside the key. The JAX analogue of a graph's
topology is the jaxpr structure; the analogue of launch dims / pointer args
is concrete shapes. A topology key therefore hashes:

  * the primitive sequence and dataflow arity (jaxpr eqn order encodes a
    deterministic topological order of the DAG),
  * dtypes and *ranks* (not sizes) of all operands/results,
  * structural params (dimension_numbers, scan structure, shardings,
    shard_map specs, custom-call targets, a Pallas kernel's body and index
    maps), recursing into sub-jaxprs,

and excludes dimension sizes (a Pallas kernel's grid among them: it is a
launch dimension), so serve-step graphs for different batch-size buckets
collapse to one key — unless batching changes the *program* (e.g. a bucket
stops dividing the data axis and the sharding spec changes), which is
precisely when the paper would also need a new template.
"""
from __future__ import annotations

import hashlib
import re
from functools import partial
from typing import Any

import jax
import numpy as np
from jax.extend import core as jex_core


def _norm_param(v: Any, h) -> None:
    """Feed a normalized representation of one eqn param into the hash."""
    # recurse into sub-jaxprs (scan/cond/custom_vjp bodies)
    if isinstance(v, jex_core.ClosedJaxpr):
        _hash_jaxpr(v.jaxpr, h)
        return
    if isinstance(v, jex_core.Jaxpr):
        _hash_jaxpr(v, h)
        return
    if isinstance(v, (tuple, list)):
        h.update(b"(")
        for x in v:
            _norm_param(x, h)
        h.update(b")")
        return
    if isinstance(v, dict):
        for k in sorted(v, key=str):
            h.update(str(k).encode())
            _norm_param(v[k], h)
        return
    if isinstance(v, (bool, str, bytes)):
        h.update(str(v).encode())
        return
    if isinstance(v, (np.dtype, type)):
        h.update(str(v).encode())
        return
    if isinstance(v, (int, np.integer)):
        # sizes are per-node parameters, not topology -> rank-only marker.
        # Small ints (< 16) are structural (dim indices, axis ids, arity).
        h.update(b"i" if int(v) >= 16 else str(int(v)).encode())
        return
    if isinstance(v, (float, np.floating)):
        h.update(b"f")
        return
    if v is None:
        h.update(b"N")
        return
    if hasattr(v, "shape") and hasattr(v, "dtype"):  # an aval: rank only
        _hash_aval(v, h)
        return
    if hasattr(v, "grid") and hasattr(v, "block_mappings"):
        # a Pallas kernel's grid and block shapes are its launch dimensions:
        # per-node parameters like any other size. Their ranks and the
        # index maps are structure.
        h.update(b"G")
        h.update(bytes([len(v.grid)]))
        for bm in v.block_mappings:
            h.update(bytes([len(bm.block_shape)]))
            _norm_param(bm.index_map_jaxpr, h)
        return
    if isinstance(v, jax.tree_util.PyTreeDef):
        # a Pallas ref indexer carries the ref's shape in its tree: sizes
        h.update(re.sub(r"\d+", "#", str(v)).encode())
        return
    # partition specs, shardings, callables: use stable str forms
    h.update(type(v).__name__.encode())
    try:
        h.update(str(v).encode())
    except Exception:
        pass


def _hash_aval(aval, h) -> None:
    dt = getattr(aval, "dtype", None)
    shape = getattr(aval, "shape", ())
    h.update(str(dt).encode())
    h.update(bytes([len(shape) & 0xFF]))


_PARAM_SKIP = {
    # purely size-like params that scale with the bucket
    "shape", "new_sizes", "sizes", "limit_indices", "start_indices",
    "strides", "broadcast_sizes", "slice_sizes", "padding_config",
    "dimensions_to_pad",
}


def _hash_jaxpr(jaxpr, h) -> None:
    h.update(b"J")
    for v in jaxpr.invars:
        _hash_aval(v.aval, h)
    for eqn in jaxpr.eqns:
        h.update(eqn.primitive.name.encode())
        h.update(bytes([len(eqn.invars) & 0xFF, len(eqn.outvars) & 0xFF]))
        for v in eqn.invars:
            if hasattr(v, "aval"):
                _hash_aval(v.aval, h)
        for v in eqn.outvars:
            _hash_aval(v.aval, h)
        for k in sorted(eqn.params):
            if k in _PARAM_SKIP:
                continue
            h.update(k.encode())
            _norm_param(eqn.params[k], h)
    for v in jaxpr.outvars:
        if hasattr(v, "aval"):
            _hash_aval(v.aval, h)


def jaxpr_topology_key(closed_jaxpr) -> str:
    h = hashlib.blake2b(digest_size=16)
    _hash_jaxpr(closed_jaxpr.jaxpr, h)
    return h.hexdigest()


def topology_key(fn, *args, extra: Any = None, **kwargs) -> str:
    """Topology key of ``fn`` traced at the given (Shape/DtypeStruct or
    concrete) args. ``extra`` folds deployment identity (mesh shape, sharding
    mode) into the key — the paper's analogue is that graphs from different
    parallelism configs never share templates."""
    jaxpr = jax.make_jaxpr(fn)(*args, **kwargs)
    h = hashlib.blake2b(digest_size=16)
    _hash_jaxpr(jaxpr.jaxpr, h)
    if extra is not None:
        h.update(str(extra).encode())
    return h.hexdigest()
