"""Single-host offline capture topology (paper §4.2.2).

The paper captures multi-GPU graphs on ONE GPU by stubbing NCCL/NVSHMEM with
dummy communication, then patches rank state at LOAD. On TPU/JAX the stub is
structural: SPMD programs are traced/lowered/compiled against a *device
topology*, not live communicators, so a single CPU host with
``--xla_force_host_platform_device_count=N`` placeholder devices produces the
byte-identical SPMD program a real N-chip pod would compile — collectives are
real HLO ops that are simply never executed offline. Rank identity
(partition-id / channel assignment) is resolved by the runtime at execution,
which is exactly the "patch only rank-dependent communication state" step.

This module holds the helpers that make that explicit and testable: the
placeholder-device capture environment, mesh-identity predicates used by the
LOAD decision (exact / stamped / fallback; core/restore.py), and the
rank-parameterized peer state — per-axis collective peer groups and per-rank
mesh coordinates — that core/rank_stamp.py records at SAVE and re-derives for
the deployment mesh at LOAD (paper §4.3: "patch only rank-dependent
communication state").
"""
from __future__ import annotations

import math
import os
import subprocess
import sys
from typing import Dict, List, Optional, Sequence

import numpy as np

PLACEHOLDER_FLAG = "--xla_force_host_platform_device_count"


def placeholder_env(n_devices: int, extra_env: Optional[dict] = None) -> dict:
    """Environment of a child process that sees ``n_devices`` CPU
    placeholder devices. The child is pinned to the CPU backend: its parent
    has usually imported JAX already and may hold the accelerator, which
    belongs to one process at a time. The placeholder flag is appended to
    any inherited ``XLA_FLAGS``."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = " ".join(
        f for f in (env.get("XLA_FLAGS", ""), f"{PLACEHOLDER_FLAG}={n_devices}")
        if f)
    env["JAX_PLATFORMS"] = "cpu"
    env.update(extra_env or {})
    return env


def capture_devices_available(n: int) -> bool:
    """True if this process was started with >= n placeholder devices."""
    import jax
    return len(jax.devices()) >= n


def run_in_capture_process(script: str, n_devices: int, *,
                           timeout: float = 1200.0,
                           pythonpath: str = "src") -> subprocess.CompletedProcess:
    """Run a python snippet in a fresh CPU process with the capture topology
    (``placeholder_env``). JAX pins the device count at first init, so the
    topology must be set before the child imports JAX."""
    env = placeholder_env(n_devices)
    env["PYTHONPATH"] = pythonpath + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=timeout)


def mesh_identity(mesh) -> dict:
    return {"axes": list(mesh.axis_names), "shape": list(mesh.devices.shape)}


def same_topology(identity: dict, mesh) -> bool:
    return (list(mesh.axis_names) == identity["axes"]
            and list(mesh.devices.shape) == identity["shape"])


# ---------------------------------------------------------------------------
# rank-parameterized peer state (paper §4.3)
# ---------------------------------------------------------------------------
def identity_device_count(identity: dict) -> int:
    """Total ranks of a recorded mesh identity ({} / no mesh counts as 1)."""
    return math.prod(identity.get("shape") or [1])


def stamp_compatible(capture_identity: dict, mesh) -> bool:
    """True when a capture taken under ``capture_identity`` can serve ``mesh``
    by rank stamping instead of recompilation (paper §4.3):

      * single-capture -> many ranks: a 1-device offline capture serves any
        deployment shape (the SPMD program is rank-independent; only peer
        tables / coordinates / buffer offsets differ per rank), or
      * axis re-arrangement at fixed rank count (TP<->EP style switches,
        e.g. (2,4) <-> (4,2)): same device set, different collective peers.

    A genuine scale change of a multi-rank capture (8-rank capture -> 2-rank
    deployment) is NOT stampable — the per-rank program shape itself changes —
    and must take the compile-from-StableHLO fallback.
    """
    if mesh is None:
        return False
    n_cap = identity_device_count(capture_identity)
    n_dep = mesh.devices.size
    return n_cap == 1 or n_cap == n_dep


def rank_coords(shape: Sequence[int]) -> List[tuple]:
    """rank -> mesh coordinates, ranks enumerated in row-major mesh order."""
    if not shape:
        return [()]
    grid = np.arange(math.prod(shape)).reshape(tuple(shape))
    coords = [None] * grid.size
    for idx in np.ndindex(grid.shape):
        coords[int(grid[idx])] = tuple(int(i) for i in idx)
    return coords


def peer_groups(shape: Sequence[int], axes: Sequence[str]) -> Dict[str, List[List[int]]]:
    """Per-mesh-axis collective peer tables: for each axis, the groups of
    flat ranks that participate in a collective over that axis (the NCCL
    communicator membership the paper patches per rank). Row-major rank
    order, matching ``jax.make_mesh``'s device assignment."""
    if not shape:
        return {}
    grid = np.arange(math.prod(shape)).reshape(tuple(shape))
    out: Dict[str, List[List[int]]] = {}
    for i, axis in enumerate(axes):
        moved = np.moveaxis(grid, i, -1).reshape(-1, grid.shape[i])
        out[str(axis)] = [[int(r) for r in row] for row in moved]
    return out
