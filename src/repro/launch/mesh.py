"""Production mesh + logical-axis sharding rules.

``make_production_mesh`` builds the assignment's target meshes:
  single-pod  (16, 16)      axes ("data", "model")        — 256 chips
  multi-pod   (2, 16, 16)   axes ("pod", "data", "model") — 512 chips

Importing this module never touches jax device state; meshes are built by
functions only (placeholder-device counts are set by the dry-run entrypoint
before any jax initialization).

Sharding is expressed through *logical axes* (MaxText-style): model code tags
tensor dims with names like "batch" / "heads" / "experts"; ``ShardCtx``
resolves them to mesh axes with divisibility fallbacks, so one model
implementation serves every (arch x mesh) combination.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Optional, Sequence, Union

import jax
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P


def make_mesh(shape: Sequence[int], axes: Sequence[str]) -> Mesh:
    """Every multi-device mesh of the repo is built here. Axes are ``Auto``:
    the model code places arrays through sharding constraints and lets the
    compiler propagate the rest (``jax.make_mesh`` defaults to ``Explicit``
    axes, under which e.g. a gather from a vocab-sharded table must name its
    output sharding)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(shape))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh() -> Mesh:
    """1-device mesh for smoke tests / examples on this host."""
    return Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1), ("data", "model"))


def make_capture_mesh() -> Mesh:
    """The paper's single-device offline capture topology (§4.3): a (1, 1)
    ("data", "model") mesh on this host's first device. Archives captured on
    it are rank-stampable onto any shape-compatible deployment mesh
    (core/rank_stamp.py)."""
    return make_host_mesh()


def make_tp_mesh(n_model: int, n_data: int = 1) -> Mesh:
    """Tensor-parallel deployment mesh: (n_data, n_model) over
    ("data", "model")."""
    return make_mesh((n_data, n_model), ("data", "model"))


def mesh_device_count(mesh: Optional[Mesh]) -> int:
    """Total ranks of a deployment mesh (None -> 1: single-process serving)."""
    return 1 if mesh is None else int(mesh.devices.size)


@dataclass(frozen=True)
class MeshSpec:
    """Buildable description of a deployment mesh: (shape, axes) without
    committed devices. Policies that *switch* parallelism at runtime (the
    router's ``ReshardPolicy``, ``Fleet.reshard``) hold specs rather than
    concrete meshes so a topology can be named before — and independently
    of — the moment its devices are claimed. ``shape=()`` describes the
    un-meshed single-process topology (builds to ``None``)."""

    shape: tuple = ()
    axes: tuple = ("data", "model")

    def build(self) -> Optional[Mesh]:
        if not self.shape:
            return None
        return make_mesh(self.shape, self.axes[:len(self.shape)])

    def describe(self) -> str:
        if not self.shape:
            return "unmeshed"
        return "x".join(str(s) for s in self.shape)


def resolve_mesh(mesh_or_spec) -> Optional[Mesh]:
    """Accept a concrete ``Mesh``, a ``MeshSpec``, or ``None`` (un-meshed)
    wherever a deployment topology is taken (``Fleet.reshard``,
    router reshard policies)."""
    if isinstance(mesh_or_spec, MeshSpec):
        return mesh_or_spec.build()
    return mesh_or_spec


def describe_mesh(mesh: Optional[Mesh]) -> str:
    """Human-readable topology tag for reports ("unmeshed", "1x2", ...)."""
    if mesh is None:
        return "unmeshed"
    if isinstance(mesh, MeshSpec):
        return mesh.describe()
    return "x".join(str(s) for s in mesh.devices.shape)


# Default logical-axis -> mesh-axis candidates. Each entry is a tuple of mesh
# axes the logical axis WANTS to occupy; axes missing from the mesh or failing
# divisibility are dropped (in order), falling back to replication.
DEFAULT_RULES: dict[str, tuple[str, ...]] = {
    "batch": ("pod", "data"),     # data parallelism
    "seq": (),                     # activations: unsharded by default
    "kv_seq": ("model",),          # KV-cache sequence (seqpar decode fallback)
    "embed": (),                   # d_model of activations
    "heads": ("model",),           # attention heads (tensor parallel)
    "kv_heads": ("model",),
    "mlp": ("model",),             # FFN intermediate
    "experts": ("model",),         # expert parallelism
    "vocab": ("model",),           # embedding / logits vocab
    "layers": (),                  # stacked-scan leading axis
    "fsdp": ("data",),             # ZeRO-3 param shard (contraction dim)
    "ssm_inner": ("model",),       # mamba d_inner channels
    "ssm_heads": ("model",),       # mamba2 heads
    "none": (),
}


@dataclass
class ShardCtx:
    """Resolves logical axes to shardings for a concrete mesh.

    mesh=None (or 1-device) degrades to no-op constraints so the same model
    code runs in smoke tests.
    """

    mesh: Optional[Mesh] = None
    rules: dict[str, tuple[str, ...]] = field(default_factory=lambda: dict(DEFAULT_RULES))

    def axis_size(self, names: tuple[str, ...]) -> int:
        if self.mesh is None:
            return 1
        return math.prod(self.mesh.shape.get(a, 1) for a in names)

    def _resolve_dim(self, logical: Optional[str], size: int):
        if self.mesh is None or logical is None:
            return None
        want = self.rules.get(logical, ())
        axes = [a for a in want if a in self.mesh.axis_names]
        # drop trailing axes until the product divides the dim size
        while axes and size % math.prod(self.mesh.shape[a] for a in axes):
            axes.pop()
        if not axes:
            return None
        return tuple(axes) if len(axes) > 1 else axes[0]

    def spec(self, logical_axes: Sequence[Optional[str]], shape: Sequence[int]) -> P:
        assert len(logical_axes) == len(shape), (logical_axes, shape)
        resolved = [self._resolve_dim(l, s) for l, s in zip(logical_axes, shape)]
        # a mesh axis may appear at most once in a PartitionSpec
        seen: set[str] = set()
        out = []
        for r in resolved:
            names = (r,) if isinstance(r, str) else (r or ())
            if any(n in seen for n in names):
                out.append(None)
                continue
            seen.update(names)
            out.append(r)
        return P(*out)

    def sharding(self, logical_axes: Sequence[Optional[str]], shape: Sequence[int]):
        if self.mesh is None:
            return None
        return NamedSharding(self.mesh, self.spec(logical_axes, shape))

    def constrain(self, x, *logical_axes: Optional[str]):
        """with_sharding_constraint keyed by logical axes (no-op off-mesh)."""
        if self.mesh is None:
            return x
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, self.spec(logical_axes, x.shape)))

    # convenience predicates used by the model to pick attention modes
    def divides(self, logical: str, size: int) -> bool:
        want = self.rules.get(logical, ())
        axes = [a for a in want if self.mesh is not None and a in self.mesh.axis_names]
        if not axes:
            return False
        return size % math.prod(self.mesh.shape[a] for a in axes) == 0

    @property
    def model_axis_size(self) -> int:
        if self.mesh is None or "model" not in self.mesh.axis_names:
            return 1
        return self.mesh.shape["model"]

    @property
    def data_axes(self) -> tuple[str, ...]:
        if self.mesh is None:
            return ()
        return tuple(a for a in ("pod", "data") if a in self.mesh.axis_names)
