"""LOAD: online graph reconstruction from a Foundry archive (paper Figure 4,
right side).

Critical-path work, run as a pipelined stage graph:

    parse ─▶ rebind decision ─▶ rank deltas
                 │
                 ├─▶ [fetch worker]   blob read + decompress + verify
                 │        │                    (stage 1, thread)
                 ├─▶ prealloc          overlaps stage 1
                 ├─▶ kernel prime      overlaps stage 1
                 │        │
                 │   [deserialize worker]  pickle + deserialize_and_load
                 │        │                    (stage 2, thread)
                 └─▶ install           stamp + hot-swap into ProgramSet
                                           (stage 3, caller thread)

The stages are connected by bounded queues (``pipeline_depth`` groups in
flight), so group k's template is installed — and its buckets servable —
while group k+1 deserializes and group k+2's blob is still being fetched.
With a lazy v2 archive (core/archive.py) the fetch stage is also where the
blob is decompressed for the first (and only) time; concurrent LOADs of one
shared archive de-duplicate that work through the archive's blob cache.
``LoadReport.phases`` keeps the same keys as the sequential implementation
(parse_s, prealloc_s, kernel_load_s, rank_delta_s, templates_s): overlap
shows up as a smaller ``templates_s``, and per-stage busy time is reported
separately in ``LoadReport.pipeline``.

Off the critical path, worker threads realize exact-bucket executables from
the archived StableHLO (no Python re-trace) and hot-swap them into the
ProgramSet — template construction and on-demand specialization run
concurrently exactly as in the paper (§4.2.1), except the "driver
contention" (here: compiler) stays off the serving path entirely. A
background compile that fails is recorded in
``LoadReport.background_errors`` (count) and ``background_first_error``
(first message) — never swallowed silently; the affected bucket simply
stays pad-served through its template.

Mesh rebinding (paper §4.2.2 + §4.3): the archive stores the capture mesh
identity; LOAD binds programs to the deployment's concrete device mesh by a
three-way decision (docs/architecture.md has the full diagram):

    exact     deployment mesh == capture mesh: deserialize templates,
              zero trace, zero compile;
    stamped   shape-compatible rebind (1-rank capture -> any deployment, or
              same rank count with re-arranged axes, e.g. TP<->EP): reuse the
              template program byte-identically and stamp only rank-dependent
              state — peer tables, mesh coordinates, rank-relative buffer
              offsets (core/rank_stamp.py). Still zero compile;
    fallback  incompatible topology (true scale change of a multi-rank
              capture): compile-from-StableHLO, counted in
              ``LoadReport.fallback_compiles``.
"""
from __future__ import annotations

import logging
import pickle
import queue
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax

from repro.core.archive import Archive
from repro.core.collective_stub import (identity_device_count,
                                        same_topology, stamp_compatible)
from repro.core.memory_plan import MemoryPlan
from repro.core.rank_stamp import (ReshardingExecutable, deployment_deltas,
                                   stamp_template)
from repro.core.templates import ProgramSet, TopologyGroup
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.obs.trace import span
from repro.serving.faults import fault_point

log = logging.getLogger("repro.core.restore")

# docs/architecture.md §13 has the full metric catalog
_M_LOADS = obs_metrics.counter(
    "foundry_load_total", "Completed LOADs by mesh-rebind decision.",
    ("rebind",))
_M_PHASE = obs_metrics.histogram(
    "foundry_load_phase_seconds",
    "Critical-path LOAD phase durations (same measurement as "
    "LoadReport.phases).", ("phase",))
_M_PIPE_BUSY = obs_metrics.counter(
    "foundry_load_pipeline_busy_seconds_total",
    "Busy seconds per LOAD template stage-graph stage.", ("stage",))
_M_FALLBACK = obs_metrics.counter(
    "foundry_load_fallback_compiles_total",
    "Critical-path compile-from-StableHLO events (template economics lost).")
_M_STAMPED = obs_metrics.counter(
    "foundry_load_rank_stamped_total",
    "Template x deployment-rank stampings on the stamped rebind path.")
_M_BG_ERRORS = obs_metrics.counter(
    "foundry_load_background_errors_total",
    "Background exact-bucket realizations that failed (bucket stays "
    "pad-served).")
_M_TEMPLATES_REUSED = obs_metrics.counter(
    "foundry_load_templates_reused_total",
    "Templates served from the archive's deserialized-template cache "
    "(no fetch, no deserialize).")


@dataclass
class LoadReport:
    """What LOAD did and what it cost.

    Fields:
        phases            phase name -> seconds. Keys not prefixed
                          "background" are on the cold-start critical path
                          (parse_s, verify_s, prealloc_s, kernel_load_s,
                          rank_delta_s, templates_s — verify_s is the strict
                          pre-flight of repro.analysis.checker, metadata-only
                          and negligible); background_spawn_s only covers thread
                          spawn, not the background compiles themselves.
                          templates_s is the caller-thread wall time of the
                          install stage — fetch/deserialize work hidden under
                          prealloc/kernel-prime by the pipeline shrinks it.
        pipeline          per-stage busy seconds of the template stage graph
                          (fetch_s, deserialize_s, install_s) + "depth".
        restore_path      the mesh-rebind decision taken for this archive:
                          "exact" | "stamped" | "fallback" (module docstring).
        n_templates       topology-group templates processed.
        n_buckets         total capture buckets covered by those templates.
        rank_stamped      number of (template x deployment-rank) stampings
                          performed on the stamped path — every rank's
                          ProgramSet reconstructed without touching the
                          compiler. 0 on the exact path.
        fallback_compiles critical-path compile-from-StableHLO events; the
                          template economics are lost for each one. Stays 0
                          on exact and shape-compatible stamped loads.
        background_exact  exact-bucket executables realized off the critical
                          path by worker threads (join via
                          ``wait_for_background``).
        background_errors background exact-bucket realizations that FAILED.
                          The bucket stays pad-served through its template,
                          but a systematically failing compile must be
                          visible: happy-path tests assert this is 0.
        background_first_error
                          message of the first background failure (or None).
        warm              this was a LOAD into an already-warm serving
                          process (live reshard): prealloc was skipped —
                          the plan extent is already mapped — and templates
                          deserialized by an earlier LOAD of the same
                          Archive object were reused.
        templates_reused  templates taken from the archive's deserialized-
                          template cache instead of being fetched +
                          deserialized again (counted toward n_templates).
    """
    phases: Dict[str, float] = field(default_factory=dict)
    pipeline: Dict[str, float] = field(default_factory=dict)
    restore_path: str = "exact"
    n_templates: int = 0
    n_buckets: int = 0
    rank_stamped: int = 0
    fallback_compiles: int = 0
    background_exact: int = 0
    background_errors: int = 0
    background_first_error: Optional[str] = None
    warm: bool = False
    templates_reused: int = 0

    @property
    def critical_path_s(self) -> float:
        return sum(v for k, v in self.phases.items()
                   if not k.startswith("background"))


def _execution_devices(mesh, capture_identity: dict) -> list:
    """The devices a deserialized template runs on: the capture-shaped
    leading submesh of the deployment (the same ranks
    ``_compile_from_export`` binds to), or the first device when LOAD is
    un-meshed. ``deserialize_and_load`` would otherwise load the program onto
    every device of the backend, and a one-device capture would expect one
    shard per argument on each of them."""
    devices = (list(mesh.devices.flat) if mesh is not None
               else jax.devices())
    return devices[:identity_device_count(capture_identity)]


def _deserialize_template(blob: bytes, devices=None):
    """Load a pickled ``serialize_executable`` payload onto ``devices``
    (default: the first device, for an un-meshed LOAD)."""
    from jax.experimental import serialize_executable as se
    fault_point("archive.deserialize")
    serialized, in_tree, out_tree = pickle.loads(blob)
    return se.deserialize_and_load(
        serialized, in_tree, out_tree,
        execution_devices=devices or jax.devices()[:1])


def _template_cache(archive: Archive) -> dict:
    """Per-Archive cache of *unwrapped* deserialized template executables,
    keyed by blob hash. Scoped to the Archive object on purpose: a fleet (or
    a live reshard) shares ONE archive across every replica LOAD, so the
    second and later LOADs skip fetch + deserialize entirely, while separate
    Archive instances (benchmark legs, tests) stay independent. Sharing the
    underlying loaded executable is safe — calls are functional and each
    LOAD wraps it in its own Resharding/StampedExecutable — and a racing
    first-LOAD pair at worst deserializes twice (last write wins)."""
    cache = getattr(archive, "_loaded_template_cache", None)
    if cache is None:
        cache = archive._loaded_template_cache = {}
    return cache


# ---------------------------------------------------------------------------
# template stage graph
# ---------------------------------------------------------------------------
@dataclass
class _TemplateJob:
    """One topology group flowing through the LOAD pipeline."""
    ps: ProgramSet
    group: TopologyGroup
    donate: Any
    blob_hash: Optional[str]      # blob stage 1 must fetch (None: no exe)
    deserialize: bool             # stage 2 work (False on the fallback path)
    blob: Optional[bytes] = None  # stage 1 -> 2
    exe: Any = None               # stage 2 -> 3
    error: Optional[BaseException] = None
    error_stage: Optional[str] = None  # "fetch" | "deserialize" | "stamp"


_DONE = object()


class _TemplatePipeline:
    """fetch (thread) -> deserialize (thread) -> install (caller).

    Bounded queues cap in-flight groups at ``depth``; jobs come out in
    submission order per stage, so installation order (and therefore
    LoadReport accounting) is deterministic. Stage exceptions ride on the
    job — the caller decides (deserialize failure -> fallback compile),
    nothing is swallowed.
    """

    def __init__(self, archive: Archive, jobs: Sequence[_TemplateJob],
                 devices: Sequence, depth: int = 4):
        self.archive = archive
        self.jobs = list(jobs)
        self.devices = list(devices)
        self.busy = {"fetch_s": 0.0, "deserialize_s": 0.0, "install_s": 0.0}
        self._fetched: "queue.Queue" = queue.Queue(maxsize=max(1, depth))
        self._ready: "queue.Queue" = queue.Queue(maxsize=max(1, depth))
        self._aborted = False
        self._threads = [
            threading.Thread(target=self._fetch_stage, daemon=True),
            threading.Thread(target=self._deserialize_stage, daemon=True),
        ]

    def start(self) -> "_TemplatePipeline":
        for t in self._threads:
            t.start()
        return self

    def abort(self):
        """Unblock and wind down the stage threads after a consumer-side
        failure (without this they would sit on the bounded queues forever,
        pinning fetched blobs)."""
        self._aborted = True

    def _put(self, q: "queue.Queue", item) -> bool:
        """Bounded put that gives up once the pipeline is aborted."""
        while not self._aborted:
            try:
                q.put(item, timeout=0.05)
                return True
            except queue.Full:
                continue
        return False

    def _fetch_stage(self):
        obs_trace.set_thread_name("load.fetch")
        for job in self.jobs:
            if self._aborted:
                return
            with span("load.fetch", cat="load",
                      group=job.group.key[:12]) as sp:
                try:
                    if job.blob_hash is not None:
                        job.blob = self.archive.get_blob(job.blob_hash)
                except BaseException as e:
                    job.error, job.error_stage = e, "fetch"
            self.busy["fetch_s"] += sp.seconds
            if not self._put(self._fetched, job):
                return
        self._put(self._fetched, _DONE)

    def _deserialize_stage(self):
        obs_trace.set_thread_name("load.deserialize")
        while True:
            try:
                job = self._fetched.get(timeout=0.05)
            except queue.Empty:
                if self._aborted:
                    return
                continue
            if job is _DONE:
                self._put(self._ready, _DONE)
                return
            with span("load.deserialize", cat="load",
                      group=job.group.key[:12]) as sp:
                if job.error is None and job.deserialize and \
                        job.blob is not None:
                    try:
                        job.exe = _deserialize_template(job.blob,
                                                        self.devices)
                    except BaseException as e:
                        job.error, job.error_stage = e, "deserialize"
                job.blob = None  # stage 2 owns the last ref to the bytes
            self.busy["deserialize_s"] += sp.seconds
            if not self._put(self._ready, job):
                return

    def __iter__(self):
        """Yield jobs in submission order as stage 2 completes them."""
        while True:
            job = self._ready.get()
            if job is _DONE:
                return
            yield job


def foundry_load(archive: Archive, mesh, *,
                 make_args: Optional[Dict[str, Callable[[int], tuple]]] = None,
                 spec_names: Optional[Sequence[str]] = None,
                 background_exact: bool = True,
                 background_threads: int = 2,
                 kernel_catalog=None,
                 allow_stamping: bool = True,
                 pipeline_depth: int = 4,
                 warm: bool = False,
                 reuse_templates: bool = True,
                 strict: bool = True,
                 verbose: bool = False,
                 trace_path: Optional[str] = None) -> tuple[Dict[str, ProgramSet], LoadReport, Optional[MemoryPlan]]:
    """Restore executables from an archive. Returns
    ({spec_name: ProgramSet}, report, load_side_memory_plan).

    ``allow_stamping=False`` disables the rank-stamping rebind path, forcing
    mesh mismatches down the compile-from-StableHLO fallback (the paper's
    no-stamping ablation; benchmarks/fig12_rank_stamp.py).
    ``pipeline_depth`` bounds how many topology groups the LOAD stage graph
    keeps in flight (module docstring); 0 degrades to depth 1.
    ``warm=True`` is the live-reshard case — a LOAD racing an already-warm
    serving process (paper §4.3 "dynamic parallelism switching"): the
    memory-plan extent is already mapped by the serving replicas, so
    preallocation is skipped (the plan itself is still parsed and returned
    for verification). ``reuse_templates`` (default on) consults the
    archive's deserialized-template cache so repeat LOADs of one shared
    Archive — fleet scale-out, reshard — skip fetch + deserialize for
    templates an earlier LOAD already realized.

    ``strict`` (default on) runs the static pre-flight verification of
    ``repro.analysis.checker.verify_for_load`` over the manifest before any
    restore work: a structurally-bad archive raises
    ``ArchiveVerificationError`` with the findings instead of silently
    degrading into per-template fallback compiles, and a blob whose bytes
    fail content verification during the fetch stage raises instead of
    fallback-compiling that template. The pre-flight is metadata-only (no
    blob fetches, no IR deserialization) so its cost — recorded as
    ``phases["verify_s"]`` — is negligible next to the LOAD critical path
    (the fig13 --quick gate asserts < 5%).

    ``trace_path`` writes a Chrome/Perfetto trace-event JSON file of this
    LOAD on return (starting tracing for the call if it was not already
    active); load it at https://ui.perfetto.dev to see the fetch /
    deserialize / install stages overlap on their threads."""
    if verbose:
        from repro.obs import configure_logging
        configure_logging()
    trace_started_here = False
    if trace_path is not None and not obs_trace.active():
        obs_trace.start()
        trace_started_here = True
    try:
        return _foundry_load(
            archive, mesh, make_args=make_args, spec_names=spec_names,
            background_exact=background_exact,
            background_threads=background_threads,
            kernel_catalog=kernel_catalog, allow_stamping=allow_stamping,
            pipeline_depth=pipeline_depth, warm=warm,
            reuse_templates=reuse_templates, strict=strict)
    finally:
        if trace_path is not None:
            obs_trace.save(trace_path)
        if trace_started_here:
            obs_trace.stop()


def _foundry_load(archive: Archive, mesh, *, make_args, spec_names,
                  background_exact, background_threads, kernel_catalog,
                  allow_stamping, pipeline_depth, warm, reuse_templates,
                  strict):
    rep = LoadReport(warm=warm)
    obs_trace.set_thread_name("load.install+main")
    with span("load.parse", cat="load") as sp:
        manifest = archive.manifest
    rep.phases["parse_s"] = sp.seconds

    if strict:
        from repro.analysis.checker import (ArchiveVerificationError, errors,
                                            verify_for_load)
        with span("load.verify", cat="load") as sp:
            findings = verify_for_load(archive)
        rep.phases["verify_s"] = sp.seconds
        if errors(findings):
            raise ArchiveVerificationError(findings, rep)

    # --- mesh-rebind decision (module docstring: exact/stamped/fallback) --
    capture_identity = manifest.get("mesh") or {"axes": [], "shape": []}
    if mesh is None or same_topology(capture_identity, mesh):
        rep.restore_path = "exact"
    elif allow_stamping and stamp_compatible(capture_identity, mesh):
        rep.restore_path = "stamped"
    else:
        rep.restore_path = "fallback"

    rank_deltas = None
    if rep.restore_path == "stamped":
        with span("load.rank_delta", cat="load") as sp:
            rank_deltas = deployment_deltas(mesh, manifest)
        rep.phases["rank_delta_s"] = sp.seconds

    # --- enumerate template jobs and start the stage graph ----------------
    # (fetch + deserialize overlap the prealloc / kernel-prime phases below)
    program_sets: Dict[str, ProgramSet] = {}
    names = spec_names or list(manifest["specs"])
    jobs: List[_TemplateJob] = []
    pending_exact: List[tuple] = []
    tcache = _template_cache(archive) if reuse_templates else {}
    for name in names:
        spec_m = manifest["specs"][name]
        donate = spec_m.get("donate_argnums")
        groups = [TopologyGroup.from_manifest(g) for g in spec_m["groups"]]
        ps = ProgramSet(groups)
        rep.n_buckets += len(ps.buckets)
        for g in groups:
            blob_hash = None
            deserialize = False
            cached = None
            if g.executable_blob:
                if rep.restore_path == "fallback":
                    # prefetch the StableHLO the fallback compile will read
                    blob_hash = g.bucket_export_blobs[g.template_bucket]
                elif reuse_templates and (cached := tcache.get(
                        g.executable_blob)) is not None:
                    rep.templates_reused += 1  # no fetch, no deserialize
                else:
                    blob_hash = g.executable_blob
                    deserialize = True
            job = _TemplateJob(ps, g, donate, blob_hash, deserialize)
            job.exe = cached
            jobs.append(job)
            for b in g.buckets:
                if b != g.template_bucket and b in g.bucket_export_blobs:
                    pending_exact.append((ps, g, b, donate))
        program_sets[name] = ps
    pipe = _TemplatePipeline(
        archive, jobs, _execution_devices(mesh, capture_identity),
        depth=max(1, pipeline_depth)).start()

    try:
        # --- memory plan: preallocate + capture-window replay -------------
        with span("load.prealloc", cat="load") as sp:
            plan = None
            if manifest.get("memory_plan"):
                plan = MemoryPlan.for_load(manifest["memory_plan"])
                if not warm:
                    # a warm process (live reshard) already has the recorded
                    # extent mapped; re-preallocating would double the
                    # footprint
                    plan.preallocate()
        rep.phases["prealloc_s"] = sp.seconds

        # --- kernel catalog prime -----------------------------------------
        with span("load.kernel_load", cat="load") as sp:
            if kernel_catalog is not None and manifest.get("kernel_catalog"):
                kernel_catalog.prime(manifest["kernel_catalog"], archive)
        rep.phases["kernel_load_s"] = sp.seconds

        # --- install stage: stamp + hot-swap as groups leave the pipe -----
        t0 = time.perf_counter()
        for job in pipe:
            g, exe = job.group, job.exe
            with span("load.install", cat="load", group=g.key[:12]):
                fault_point("restore.install", tag=g.key)
                if g.executable_blob:
                    if (reuse_templates and job.deserialize
                            and exe is not None
                            and g.executable_blob not in tcache):
                        tcache[g.executable_blob] = exe  # unwrapped: wrappers
                        # below are per-LOAD (rank deltas per deployment)
                    if exe is not None and rep.restore_path == "stamped":
                        try:
                            exe = stamp_template(exe, rank_deltas,
                                                 capture_identity, mesh)
                            rep.rank_stamped += len(rank_deltas)
                        except Exception as e:
                            job.error, job.error_stage = e, "stamp"
                            exe = None  # degrade to fallback below
                    if exe is None:
                        if strict and job.error_stage == "fetch":
                            # a fetch failure is the archive lying about its
                            # own contents (hash mismatch, truncated section,
                            # missing depot blob) — strict LOAD refuses it
                            # rather than hiding the corruption behind a
                            # fallback compile. Deserialize/stamp failures
                            # still degrade: they are environment-side
                            # (capture devices unavailable).
                            from repro.analysis.checker import (
                                ArchiveVerificationError, Finding)
                            raise ArchiveVerificationError([Finding(
                                "blob-integrity", "error",
                                f"blob/{(job.blob_hash or '?')[:12]}",
                                f"template blob for group {g.key[:12]} "
                                f"failed to fetch: "
                                f"{type(job.error).__name__}: {job.error}",
                                "the archive is corrupt; re-run SAVE")], rep)
                        # fallback decision, deserialize/stamp failure, or
                        # capture devices unavailable: last-resort rebind via
                        # compile-from-StableHLO (the blob is already
                        # cache-hot when the fetch stage prefetched it)
                        if job.error is not None:
                            log.warning(
                                "template for group %s unusable (%s: %s); "
                                "falling back to compile", g.key[:12],
                                type(job.error).__name__, job.error)
                        rep.fallback_compiles += 1
                        _M_FALLBACK.inc()
                        exe = ReshardingExecutable(_compile_from_export(
                            archive,
                            g.bucket_export_blobs[g.template_bucket],
                            mesh, capture_identity,
                            donate_argnums=job.donate))
                    elif not isinstance(exe, ReshardingExecutable):
                        # exact path: re-lay args onto the template's
                        # recorded shardings (an un-meshed engine loading a
                        # capture-mesh archive holds equivalent placements
                        # under another description)
                        exe = ReshardingExecutable(exe)
                    job.ps.set_template(g.key, exe)
                rep.n_templates += 1
        rep.phases["templates_s"] = time.perf_counter() - t0
    except BaseException:
        pipe.abort()  # unblock stage threads; they exit, dropping blobs
        raise
    pipe.busy["install_s"] = rep.phases["templates_s"]
    rep.pipeline = dict(pipe.busy, depth=float(max(1, pipeline_depth)))

    # --- background exact-bucket realization --------------------------------
    if background_exact and pending_exact:
        t_bg = time.perf_counter()
        err_lock = threading.Lock()

        def worker(chunk):
            obs_trace.set_thread_name("load.background")
            for ps, g, b, donate in chunk:
                try:
                    exe = _compile_from_export(
                        archive, g.bucket_export_blobs[b],
                        mesh, capture_identity, donate_argnums=donate)
                    if rep.restore_path != "exact":
                        # exact exes must accept deployment-sharded args too
                        exe = ReshardingExecutable(exe)
                    ps.set_exact(b, exe)
                    rep.background_exact += 1
                except Exception as e:
                    # bucket stays pad-served through its template, but the
                    # failure must be visible (LoadReport.background_errors)
                    with err_lock:
                        rep.background_errors += 1
                        if rep.background_first_error is None:
                            rep.background_first_error = (
                                f"bucket {b}: {type(e).__name__}: {e}")
                    _M_BG_ERRORS.inc()
                    log.warning("background exact realization FAILED for "
                                "bucket %s: %s: %s", b, type(e).__name__, e)

        chunks = [pending_exact[i::background_threads]
                  for i in range(background_threads)]
        threads = [threading.Thread(target=worker, args=(c,), daemon=True)
                   for c in chunks if c]
        for t in threads:
            t.start()
        rep._bg_threads = threads  # joinable by callers/tests
        rep.phases["background_spawn_s"] = time.perf_counter() - t_bg

    # --- registry feed: same measurements the report just recorded --------
    if obs_metrics.enabled():
        _M_LOADS.inc(rebind="stamped" if rep.rank_stamped else "compatible")
        for k, v in rep.phases.items():
            _M_PHASE.observe(v, phase=k[:-2] if k.endswith("_s") else k)
        for stage in ("fetch", "deserialize", "install"):
            _M_PIPE_BUSY.inc(rep.pipeline[f"{stage}_s"], stage=stage)
        if rep.rank_stamped:
            _M_STAMPED.inc(rep.rank_stamped)
        if rep.templates_reused:
            _M_TEMPLATES_REUSED.inc(rep.templates_reused)

    log.info("[LOAD:%s] %d templates over %d buckets in %.1f ms "
             "(parse %.1f ms, install %.1f ms, pipeline fetch %.1f ms / "
             "deserialize %.1f ms, rank_stamped=%d, fallback_compiles=%d)",
             rep.restore_path, rep.n_templates, rep.n_buckets,
             rep.critical_path_s * 1e3, rep.phases["parse_s"] * 1e3,
             rep.phases["templates_s"] * 1e3, rep.pipeline["fetch_s"] * 1e3,
             rep.pipeline["deserialize_s"] * 1e3, rep.rank_stamped,
             rep.fallback_compiles)
    return program_sets, rep, plan


def _compile_from_export(archive: Archive, blob_hash: str, mesh,
                         capture_identity: Optional[dict] = None,
                         donate_argnums: Optional[Sequence[int]] = None):
    """Exact-bucket reconstruction: deserialize pre-lowered StableHLO and
    compile — no Python tracing of the model (the paper's 'graph construction
    via driver APIs', 2-3x cheaper than stream capture; Figure 10).

    ``donate_argnums`` (the capture spec's, from the manifest) is re-applied
    so reconstructed executables keep the in-place buffer discipline of the
    capture — without it, the decode cache would be copied every step on any
    bucket served by an exact realization.

    A jax.export program is pinned to its capture-time device count. When the
    deployment mesh's count differs, the program is bound onto a
    capture-shaped submesh of the deployment (serving from a subset of ranks;
    a true re-shape needs a fresh SAVE for that topology). A deployment
    smaller than the capture cannot host the program at all and raises."""
    exp = jax.export.deserialize(bytearray(archive.get_blob(blob_hash)))
    call_mesh = mesh
    n_exp = getattr(exp, "nr_devices", 1)
    if mesh is not None and n_exp != mesh.devices.size and capture_identity:
        devs = mesh.devices.reshape(-1)[:n_exp]
        if len(devs) < n_exp:
            raise RuntimeError(
                f"archive was captured for {n_exp} ranks but the deployment "
                f"mesh has only {mesh.devices.size}; a multi-rank capture "
                f"cannot be scaled down — re-run SAVE for this topology")
        import numpy as np
        from jax.sharding import Mesh
        shape = capture_identity.get("shape") or [n_exp]
        call_mesh = Mesh(np.asarray(devs).reshape(tuple(shape)),
                         tuple(capture_identity.get("axes") or ["devices"]))
    fn = jax.jit(exp.call, donate_argnums=tuple(donate_argnums or ()))
    # the export's recorded HloShardings, rebound onto the deployment mesh
    flat = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s)
            for a, s in zip(exp.in_avals, exp.in_shardings_jax(call_mesh))]
    args, kwargs = jax.tree.unflatten(exp.in_tree, flat)
    return fn.lower(*args, **kwargs).compile()


def wait_for_background(rep: LoadReport, timeout: float = 300.0,
                        verbose: bool = False):
    """Join the background exact-bucket worker threads of a LOAD.

    Join contract: ``foundry_load`` returns while daemon workers may still be
    hot-swapping exact executables into the returned ProgramSets. Serving
    does NOT need this join — every bucket is already pad-servable through
    its (possibly stamped) template, and ``ProgramSet`` hot-swap is
    lock-protected. Call it only when you need completion of exact
    realization: deterministic tests, benchmarks measuring
    ``background_exact``, or before process exit if archive file handles
    must be released. ``timeout`` is per thread (seconds); on timeout the
    thread keeps running as a daemon and any buckets it has not yet swapped
    simply stay pad-served — there is no error and no partial state, so the
    call is safe to repeat. With ``verbose`` a summary of background
    failures (``LoadReport.background_errors``) is printed after the join.
    """
    for t in getattr(rep, "_bg_threads", []):
        t.join(timeout)
    if verbose and rep.background_errors:
        log.warning("%d background exact realization(s) failed; first: %s",
                    rep.background_errors, rep.background_first_error)
