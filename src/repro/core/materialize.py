"""SAVE: offline context materialization (paper §3, Figure 4 left).

Runs the engine's capture set once — on the *offline capture topology*
(single host, placeholder devices; core/collective_stub.py) — and produces a
portable archive containing:

  * per-bucket topology keys and topology groups (templates),
  * the template buckets' *instantiated executables*
    (jax.experimental.serialize_executable — topology + execution context),
  * every bucket's pre-lowered StableHLO (jax.export) for on-demand exact
    reconstruction without Python re-tracing,
  * the kernel catalog (content-hash-keyed lowered kernel artifacts),
  * the memory plan (deterministic arena layout incl. capture-window events),
  * the rank-delta section (manifest v2, paper §4.3): the capture topology's
    per-rank communication state — peer tables, mesh coordinates,
    rank-relative buffer offsets — plus an index of which manifest fields
    are rank-dependent, so LOAD can stamp a shape-compatible deployment's
    deltas into the shared templates instead of recompiling
    (core/rank_stamp.py),
  * a manifest binding all of it to (arch, step name, mesh shape, dtype).

Phase timings are recorded for the paper's Figure 8 breakdown.
"""
from __future__ import annotations

import dataclasses
import logging
import pickle
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax

from repro.core.archive import Archive
from repro.core.memory_plan import MemoryPlan
from repro.core.rank_stamp import build_rank_deltas
from repro.core.templates import TopologyGroup, group_buckets
from repro.core.topology import topology_key

log = logging.getLogger("repro.core.materialize")


@dataclass
class CaptureSpec:
    """One family of graphs to capture (e.g. the decode step).

    make_args(bucket) must return the positional arg specs
    (ShapeDtypeStructs with shardings) for ``step_fn`` at that bucket.
    ``tags`` is an arbitrary json-able dict persisted into the manifest spec
    entry — the engine records the step's calling convention there (e.g.
    ``decode_loop``/``fused_sampling``: whether sampling is fused into the
    captured graph and the step returns token ids instead of logits), so a
    LOADing engine can bind the right serving loop without re-tracing.
    """
    name: str
    step_fn: Callable
    make_args: Callable[[int], tuple]
    buckets: Sequence[int]
    donate_argnums: tuple = ()
    tags: dict = field(default_factory=dict)


def _mesh_identity(mesh) -> dict:
    if mesh is None:
        return {"axes": [], "shape": []}
    return {"axes": list(mesh.axis_names), "shape": list(mesh.devices.shape)}


def canonical_export_bytes(exp) -> bytes:
    """Serialize a ``jax.export.Exported`` with MLIR debug locations
    stripped from its StableHLO module.

    The raw serialization embeds the full call-site location chain of the
    export (file:line of every frame), so the same program exported from two
    places — two SAVE invocations, two engines, even two statements in one
    script — differs by a few location bytes. That defeats content-addressed
    dedup in the TemplateDepot (core/depot.py), where identical bucket
    programs across archives/ladders/versions should collapse to one blob.
    Round-tripping the module through its location-free textual form makes
    the blob a pure function of the program; ``jax.export.deserialize``
    accepts it unchanged (locations become "unknown").

    Uses private jax internals (the Exported dataclass layout and
    ``_module_to_bytecode``) of the pinned JAX (``requirements.txt``).
    """
    from jax._src.export import _export
    from jax._src.interpreters import mlir as _mlir
    from jax._src.lib.mlir import ir as _ir
    with _mlir.make_ir_context():
        mod = _ir.Module.parse(exp.mlir_module())
        text = mod.operation.get_asm(enable_debug_info=False)
        ser = _export._module_to_bytecode(_ir.Module.parse(text))
    return dataclasses.replace(exp, mlir_module_serialized=ser).serialize()


def foundry_save(specs: Sequence[CaptureSpec], mesh, *,
                 memory_plan: Optional[MemoryPlan] = None,
                 kernel_catalog=None,
                 meta: Optional[dict] = None,
                 serialize_all_executables: bool = False,
                 verbose: bool = False) -> tuple[Archive, dict]:
    """Capture + materialize. Returns (archive, save_report).

    serialize_all_executables=True is the "no templating" ablation (the
    CUDA-checkpoint-like baseline): every bucket's executable goes into the
    archive. Default stores executables only for templates.
    """
    ar = Archive()
    report: Dict[str, Any] = {"phases": {}, "specs": {}}
    t_all = time.perf_counter()
    manifest_specs = {}

    for spec in specs:
        srep: Dict[str, Any] = {}
        t0 = time.perf_counter()
        # --- capture: trace every bucket, compute topology keys ----------
        keys: Dict[int, str] = {}
        lowered: Dict[int, Any] = {}
        extra = _mesh_identity(mesh)
        for b in spec.buckets:
            args = spec.make_args(b)
            keys[b] = topology_key(spec.step_fn, *args, extra=extra)
        srep["trace_s"] = time.perf_counter() - t0

        # --- group ------------------------------------------------------
        t0 = time.perf_counter()
        groups = group_buckets(keys)
        srep["group_s"] = time.perf_counter() - t0
        srep["n_buckets"] = len(spec.buckets)
        srep["n_templates"] = len(groups)

        # --- lower + export every bucket (graph metadata) ----------------
        t0 = time.perf_counter()
        jitted = jax.jit(spec.step_fn, donate_argnums=spec.donate_argnums)
        for g in groups:
            for b in g.buckets:
                args = spec.make_args(b)
                exp = jax.export.export(jitted)(*args)
                g.bucket_export_blobs[b] = ar.add_blob(
                    canonical_export_bytes(exp))
        srep["export_s"] = time.perf_counter() - t0

        # --- compile + serialize template executables ---------------------
        t0 = time.perf_counter()
        from jax.experimental import serialize_executable as se
        for g in groups:
            todo = g.buckets if serialize_all_executables else [g.template_bucket]
            for b in todo:
                args = spec.make_args(b)
                compiled = jitted.lower(*args).compile()
                payload = se.serialize(compiled)
                blob = ar.add_blob(pickle.dumps(payload))
                if b == g.template_bucket:
                    g.executable_blob = blob
                if serialize_all_executables:
                    g.bucket_executable_blobs[b] = blob
        srep["compile_serialize_s"] = time.perf_counter() - t0

        manifest_specs[spec.name] = {
            "buckets": list(spec.buckets),
            "donate_argnums": list(spec.donate_argnums),
            "tags": dict(spec.tags),
            "groups": [g.to_manifest() for g in groups],
        }
        report["specs"][spec.name] = srep
        if verbose:
            from repro.obs import configure_logging
            configure_logging()
            log.info("[SAVE:%s] %d buckets -> %d templates "
                     "(trace %.2fs export %.2fs compile+ser %.2fs)",
                     spec.name, len(spec.buckets), len(groups),
                     srep["trace_s"], srep["export_s"],
                     srep["compile_serialize_s"])

    capture_identity = _mesh_identity(mesh)
    ar.manifest = {
        "version": 2,
        "mesh": capture_identity,
        "meta": meta or {},
        "specs": manifest_specs,
        "memory_plan": memory_plan.to_manifest() if memory_plan else None,
        "kernel_catalog": (kernel_catalog.to_manifest()
                           if kernel_catalog is not None else None),
        # §4.3: per-rank communication state of the capture topology, plus
        # an index of the manifest fields LOAD must re-derive per deployment
        # rank (everything else in the archive is rank-invariant and reused
        # byte-identically by the stamped restore path).
        "rank_delta": {
            "capture_ranks": [d.to_manifest() for d in
                              build_rank_deltas(capture_identity, memory_plan)],
            "rank_dependent_fields": [
                "mesh",
                "rank_delta.capture_ranks[*].coords",
                "rank_delta.capture_ranks[*].peer_groups",
                "memory_plan.allocations[scope=per_rank]",
            ],
        },
    }
    report["total_s"] = time.perf_counter() - t_all
    return ar, report
