"""Rank-stamping LOAD: one capture serves every rank (paper §4.3).

The paper's headline distributed result is that a *single-GPU* offline
capture can materialize the serving context of every rank of a multi-GPU
deployment: the compiled graph is rank-invariant, and only communication
state — NCCL peer tables, mesh coordinates, communication-buffer offsets —
differs per rank, so LOAD patches ("stamps") those deltas into the shared
template instead of recompiling per deployment shape.

The JAX analogue implemented here:

  * ``RankDelta`` is the per-rank record of rank-dependent state: the rank's
    mesh coordinates, its collective peer group per mesh axis (the
    communicator membership; core/collective_stub.py derives it from the
    mesh), and its rank-relative buffer table (core/memory_plan.py
    ``rank_extents``). SAVE writes the *capture* deltas into the archive
    manifest (v2, ``rank_delta`` section); LOAD re-derives them for the
    deployment mesh.
  * ``StampedExecutable`` wraps the template executable deserialized from
    the archive and rebinds it to the deployment: dispatch re-lays inputs
    onto the template's recorded shardings (the XLA counterpart of patching
    kernel pointer arguments in cuGraphExecUpdate) and carries the
    deployment's rank deltas. No compiler or trace work happens — the
    template's serialized program is reused byte-identically, which is why
    shape-compatible rebinds keep ``LoadReport.fallback_compiles == 0``.

Stamp compatibility (``collective_stub.stamp_compatible``): a 1-rank capture
stamps onto any deployment shape, and an N-rank capture stamps onto any
N-rank re-arrangement (TP<->EP style switches). A true scale change of a
multi-rank capture still takes the compile-from-StableHLO fallback.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax

from repro.core.collective_stub import (identity_device_count, mesh_identity,
                                        peer_groups, rank_coords)
from repro.core.memory_plan import MemoryPlan


@dataclass
class RankDelta:
    """Everything about one rank that the shared template does NOT encode.

    Fields:
        rank          flat rank id in row-major mesh order.
        coords        this rank's coordinates in the deployment mesh.
        peer_groups   mesh axis -> the peer group (flat ranks) this rank
                      performs collectives with over that axis.
        comm_buffers  rank-relative buffer table: [{name, offset, size,
                      scope}] where "per_rank"-scoped allocations are this
                      rank's 1/n shard of the capture-recorded buffer.
    """
    rank: int
    coords: Tuple[int, ...] = ()
    peer_groups: Dict[str, List[int]] = field(default_factory=dict)
    comm_buffers: List[dict] = field(default_factory=list)

    def to_manifest(self) -> dict:
        return {"rank": self.rank, "coords": list(self.coords),
                "peer_groups": {k: list(v) for k, v in self.peer_groups.items()},
                "comm_buffers": [dict(b) for b in self.comm_buffers]}

    @classmethod
    def from_manifest(cls, m: dict) -> "RankDelta":
        return cls(rank=int(m["rank"]), coords=tuple(m.get("coords", ())),
                   peer_groups={str(k): [int(r) for r in v]
                                for k, v in m.get("peer_groups", {}).items()},
                   comm_buffers=[dict(b) for b in m.get("comm_buffers", [])])


def build_rank_deltas(identity: dict,
                      memory_plan: Optional[MemoryPlan] = None) -> List[RankDelta]:
    """Derive the per-rank deltas for a mesh identity ({"axes", "shape"}).

    SAVE calls this with the capture mesh (recording which state is
    rank-dependent); LOAD calls it with the deployment mesh (producing the
    state to stamp). An empty/absent mesh yields the single rank 0.
    """
    shape = list(identity.get("shape") or [])
    axes = list(identity.get("axes") or [])
    n = identity_device_count(identity)
    coords = rank_coords(shape)
    groups = peer_groups(shape, axes)
    buffers = memory_plan.rank_extents(n) if memory_plan is not None else []
    deltas = []
    for r in range(n):
        mine = {ax: next(g for g in rows if r in g)
                for ax, rows in groups.items()}
        deltas.append(RankDelta(rank=r, coords=coords[r],
                                peer_groups=mine, comm_buffers=buffers))
    return deltas


def deployment_deltas(mesh, manifest: dict) -> List[RankDelta]:
    """Re-derive rank deltas for the deployment mesh from an archive
    manifest (uses the archived memory plan for rank-relative offsets)."""
    plan = None
    if manifest.get("memory_plan"):
        plan = MemoryPlan.from_manifest(manifest["memory_plan"])
    return build_rank_deltas(mesh_identity(mesh), plan)


class ReshardingExecutable:
    """Dispatch wrapper that re-lays positional args onto the shardings the
    wrapped executable was compiled with (``Compiled.input_shardings``)
    before calling it — the thing that lets an executable compiled under one
    mesh accept deployment-mesh-committed arrays under another.

    Donated args are handed over as they arrive, exactly as a freshly jitted
    step donates them: the caller gives up the buffer (the engine replaces
    its KV cache with the step's output). An arg already on its recorded
    sharding passes through ``device_put`` unchanged, so steady-state decode
    donates the cache in place; a re-laid arg is donated as the new copy.
    """

    is_stamped = False

    def __init__(self, executable: Any):
        self._exe = executable
        self._in_shardings = executable.input_shardings[0]

    def __call__(self, *args):
        return self._exe(*(jax.device_put(a, s)
                           for a, s in zip(args, self._in_shardings)))


class StampedExecutable(ReshardingExecutable):
    """A template executable rebound to a deployment mesh by rank stamping.

    Dispatch re-lays each positional argument onto the sharding the template
    was compiled with, then replays the template program unchanged — the
    data-movement analogue of patching pointer arguments into a captured
    CUDA graph, with zero compiler work. The deployment's ``rank_deltas``
    ride along for introspection and for the serving engine's cold-start
    report.
    """

    is_stamped = True

    def __init__(self, executable: Any, rank_deltas: Sequence[RankDelta],
                 capture_identity: dict, deploy_identity: dict):
        super().__init__(executable)
        self.rank_deltas = list(rank_deltas)
        self.capture_identity = dict(capture_identity)
        self.deploy_identity = dict(deploy_identity)
        self.stamp_dispatches = 0

    @property
    def n_ranks(self) -> int:
        return len(self.rank_deltas)

    def __call__(self, *args):
        self.stamp_dispatches += 1
        return super().__call__(*args)


def stamp_template(executable: Any, rank_deltas: Sequence[RankDelta],
                   capture_identity: dict, mesh) -> StampedExecutable:
    """Stamp a deserialized template for the deployment ``mesh``."""
    return StampedExecutable(executable, rank_deltas, capture_identity,
                             mesh_identity(mesh) if mesh is not None
                             else {"axes": [], "shape": []})
