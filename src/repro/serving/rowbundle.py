"""Dense KV-row interchange shared by both pool layouts.

``RowBundle`` is the one format in which decode state (attention KV, SSM
state, lengths) travels between serving engines — live reshard cutover
(docs/architecture.md §8), crash salvage (§12), and the prefill->decode
handoff of phase-disaggregated pools (§14) all speak it. Both pool layouts
(``serving/kvcache.KVCachePool``, slot rows; ``serving/blockpool.
PagedKVCachePool``, block tables densified on export) implement
``export_rows``/``import_rows`` against this module so the migration path
cannot fork per layout:

  * rows stay committed to the *source* pool's mesh on export; the
    importing pool calls ``reshard_rows`` to ``device_put`` them onto its
    own cache specs (possibly a different mesh — that is the §4.3 story:
    one capture, many topologies, KV free to move between them);
  * the export/import guard errors (inactive slot, row/request count
    mismatch, capacity) are defined HERE once, so every caller sees the
    same failure surface regardless of which layout raised it.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, List, Optional

import jax
import jax.numpy as jnp


def _fits(sharding, shape) -> bool:
    """Whether a ``NamedSharding`` applies to an array of ``shape``: the
    spec names no more dims than it has, and every dim splits evenly over
    the mesh axes the spec assigns to it."""
    if len(sharding.spec) > len(shape):
        return False
    mesh_shape = sharding.mesh.shape
    for size, axes in zip(shape, sharding.spec):
        names = (axes,) if isinstance(axes, str) else (axes or ())
        if size % math.prod(mesh_shape[a] for a in names):
            return False
    return True


def reshard_rows(rows, sd, mesh):
    """Commit migrated rows to a destination pool's devices: the leaf's spec
    sharding when it fits the rows (a paged pool's spec has a block dim the
    rows lack, and the row count may not divide the data axes), replicated
    on the mesh otherwise, first local device when un-meshed (eager update
    ops reject operands committed to a different mesh's device set). Shared
    by both pool layouts (slot and paged)."""
    if sd.sharding is not None and _fits(sd.sharding, rows.shape):
        return jax.device_put(rows, sd.sharding)
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec
        return jax.device_put(rows, NamedSharding(mesh, PartitionSpec()))
    return jax.device_put(rows, jax.devices()[0])


@dataclass
class RowBundle:
    """Device-resident export of pool rows for cross-pool migration.

    One entry per cache leaf, in tree-leaf order; ``rows[i]`` holds the
    exported requests' rows stacked along that leaf's batch dim (``None``
    for batch-invariant leaves — the importing pool keeps its own). The
    arrays stay committed to the *source* pool's mesh; ``import_rows``
    reshards them onto the destination's cache specs with ``device_put``
    (live-reshard KV migration, docs/architecture.md §8).
    """
    rows: List[Optional[Any]]
    bdims: List[Optional[int]]
    n: int

    def select(self, idx) -> "RowBundle":
        """Sub-bundle of the given row indices (e.g. the remainder after a
        partial adopt)."""
        idx = list(idx)
        if idx == list(range(self.n)):
            return self
        j = jnp.asarray(idx, jnp.int32)
        rows = [None if (r is None or bd is None) else jnp.take(r, j, axis=bd)
                for r, bd in zip(self.rows, self.bdims)]
        return RowBundle(rows, list(self.bdims), len(idx))


def check_export_slots(slots, pool_slots) -> None:
    """Shared export precondition: every requested slot must be active.
    Raises the layout-independent guard error both pools used to duplicate."""
    for s in slots:
        if not (0 <= s < len(pool_slots)) or pool_slots[s] is None:
            raise ValueError(f"export of slot {s}: not an active slot")


def check_import(bundle: RowBundle, req_ids, n_active: int,
                 max_batch: int) -> None:
    """Shared import preconditions: one bundle row per request, and the
    destination pool must have capacity for all of them (partial adoption is
    the *caller's* job, via ``bundle.select``)."""
    if len(req_ids) != bundle.n:
        raise ValueError(f"import of {bundle.n} rows for {len(req_ids)} "
                         f"requests")
    if n_active + bundle.n > max_batch:
        raise RuntimeError(
            f"pool cannot host {bundle.n} imported rows "
            f"({n_active} active, max_batch {max_batch})")
