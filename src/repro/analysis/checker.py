"""foundry-check: offline static verifier for Foundry state (no execution).

A serialized graph context is only valid if its invariants hold — a
deterministic memory layout, complete rank-delta coverage of every piece of
rank-dependent state, and a calling convention the serving engine actually
speaks (paper §4.1-4.3). Enforcing those only dynamically means a corrupted
blob, an incomplete ``RankDelta`` or a tag drift surfaces as a silent
fallback compile, a wedged LOAD, or token divergence at serve time. This
module analyzes archives, depots and capture manifests *statically* and
emits machine-readable findings; ``python -m repro.analysis.check`` is the
CLI front end and ``foundry_load(strict=True)`` (core/restore.py) runs the
manifest-level subset as a pre-flight pass on every LOAD.

Pass families (docs/architecture.md §11 has the full table):

    container / manifest    ``container-structure`` ``manifest-schema``
                            ``blob-index`` ``blob-integrity`` ``tags-schema``
    StableHLO IR lint       ``ir-parse`` ``donation-aliasing``
                            ``ir-determinism`` ``rank-delta-coverage``
    memory plan             ``memory-plan-overlap`` ``memory-plan-alignment``
                            ``memory-plan-extent`` ``memory-plan-leak``
                            ``memory-plan-scope`` ``capture-window-order``
    depot fsck              ``depot-index`` ``depot-missing-blob``
                            ``depot-blob-size`` ``depot-orphan-blob``
                            ``depot-orphan-manifest`` ``depot-refcount``
                            ``depot-dangling-ref`` ``depot-manifest``
                            ``depot-missing-manifest``

Severity contract: ``error`` findings mean the artifact must not be served
(strict LOAD refuses it); ``warning`` means it serves but something is
degraded (dedup lost, exact realization impossible, stale refs pinning
storage); ``info`` is advisory. The CLI exit code is the worst severity
found: 0 clean, 1 warnings only, 2 errors, 3 fatal (unreadable target /
bad invocation).

Everything here is read-only (the one exception: ``check_depot(...,
gc_orphans=True)`` deletes *unreferenced* blob files, the depot analogue of
``git fsck`` + ``git prune``). No pass executes archived programs.
"""
from __future__ import annotations

import json
import os
import re
import struct
import zlib
from dataclasses import asdict, dataclass, field
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

from repro.core.archive import (MAGIC, MAGIC2, Archive, _decompress,
                                content_hash)
from repro.core.collective_stub import (identity_device_count, peer_groups,
                                        rank_coords)
from repro.core.memory_plan import MemoryPlan

SEVERITIES = ("info", "warning", "error")

#: pass id -> one-line description (the docs/CLI pass table; stable ids —
#: CI gates and tests match on them, so renames are breaking changes)
PASSES: Dict[str, str] = {
    "container-structure": "container magic/header/section structure",
    "manifest-schema": "manifest required fields, spec/group consistency",
    "blob-index": "every referenced blob resolvable, extents sane",
    "blob-integrity": "blob bytes match their content hash",
    "tags-schema": "CaptureSpec.tags vs the engine convention matrix",
    "ir-parse": "exported StableHLO deserializes",
    "donation-aliasing": "spec donate_argnums vs exported donor/alias attrs",
    "ir-determinism": "no call-site debug locations (depot dedup)",
    "rank-delta-coverage": "rank-dependent state covered by RankDeltas",
    "memory-plan-overlap": "no overlapping arena allocations",
    "memory-plan-alignment": "offsets respect the recorded alignment",
    "memory-plan-extent": "recorded extent covers the allocation sequence",
    "memory-plan-leak": "no unaccounted gaps beyond alignment padding",
    "memory-plan-scope": "scoped extents vs rank_extents/comm_buffers",
    "capture-window-order": "capture-phase allocations form the tail",
    "depot-index": "index.json readable, right version (torn writes)",
    "depot-missing-blob": "indexed blob file present on disk",
    "depot-blob-size": "blob file size matches indexed comp_len",
    "depot-orphan-blob": "on-disk blob unknown to the index",
    "depot-orphan-manifest": "manifest file unknown to the index",
    "depot-refcount": "archive blob references all ref-held",
    "depot-dangling-ref": "blob refs point at live archives",
    "depot-manifest": "thin manifests parse and resolve in this depot",
    "depot-missing-manifest": "indexed archive's manifest file present",
}


@dataclass(frozen=True)
class Finding:
    """One verifier finding: which pass, how bad, where, what, and how to
    fix it. ``location`` is ``<target>:<path.into.artifact>``."""
    pass_id: str
    severity: str
    location: str
    message: str
    fix_hint: str = ""

    def __post_init__(self):
        assert self.pass_id in PASSES, f"unknown pass id {self.pass_id!r}"
        assert self.severity in SEVERITIES, self.severity

    def render(self) -> str:
        hint = f" (fix: {self.fix_hint})" if self.fix_hint else ""
        return (f"{self.severity.upper():7s} {self.pass_id:22s} "
                f"{self.location}: {self.message}{hint}")


class ArchiveVerificationError(ValueError):
    """Raised by ``foundry_load(strict=True)`` when the pre-flight pass
    finds error-severity problems. Carries the findings and the partial
    ``LoadReport`` (so tests can assert ``fallback_compiles == 0`` was
    attempted before the refusal)."""

    def __init__(self, findings: Sequence[Finding], report=None):
        self.findings = list(findings)
        self.report = report
        lines = [f.render() for f in self.findings[:8]]
        more = len(self.findings) - len(lines)
        if more > 0:
            lines.append(f"... and {more} more")
        super().__init__(
            "archive failed static verification; refusing to serve it "
            "(run `python -m repro.analysis.check` for the full report):\n  "
            + "\n  ".join(lines))


def errors(findings: Sequence[Finding]) -> List[Finding]:
    return [f for f in findings if f.severity == "error"]


def summarize(findings: Sequence[Finding]) -> Dict[str, int]:
    out = {s: 0 for s in SEVERITIES}
    for f in findings:
        out[f.severity] += 1
    return out


# ---------------------------------------------------------------------------
# pass 1a: container structure (raw file/bytes level)
# ---------------------------------------------------------------------------
@dataclass
class ContainerInfo:
    """Parsed container header, as far as parsing got."""
    version: int = 0                  # 1 | 2; 0 = unparseable
    thin: bool = False
    manifest: Optional[dict] = None
    index: Dict[str, tuple] = field(default_factory=dict)
    blob_base: int = 0                # v2: file offset of the blob section


def check_container_bytes(raw: bytes, loc: str
                          ) -> Tuple[List[Finding], ContainerInfo]:
    """Structural validation of a raw container: magic, header framing,
    header decode, blob-extent sanity. Never raises — a truncated or
    bit-flipped header becomes a ``container-structure`` finding."""
    out: List[Finding] = []
    info = ContainerInfo()

    def bad(msg: str, hint: str = "re-run SAVE; the file is not a usable "
            "Foundry container") -> Tuple[List[Finding], ContainerInfo]:
        out.append(Finding("container-structure", "error", loc, msg, hint))
        return out, info

    if raw.startswith(MAGIC2):
        if len(raw) < len(MAGIC2) + 8:
            return bad(f"v2 container truncated at {len(raw)} bytes "
                       "(header length field missing)")
        (hlen,) = struct.unpack_from("<Q", raw, len(MAGIC2))
        base = len(MAGIC2) + 8
        if base + hlen > len(raw):
            return bad(f"v2 header claims {hlen} bytes but only "
                       f"{len(raw) - base} follow (truncated write?)")
        try:
            import msgpack
            head = msgpack.unpackb(_decompress(bytes(raw[base:base + hlen])),
                                   raw=False, strict_map_key=False)
        except Exception as e:
            return bad(f"v2 header does not decode: "
                       f"{type(e).__name__}: {e}")
        if not isinstance(head, dict) or "manifest" not in head \
                or "index" not in head:
            return bad("v2 header missing manifest/index sections")
        info.version = 2
        info.thin = bool(head.get("depot"))
        info.manifest = head["manifest"]
        info.blob_base = base + hlen
        section = len(raw) - info.blob_base
        spans = []
        for h, entry in head["index"].items():
            if (not isinstance(entry, (list, tuple)) or len(entry) != 3
                    or any(not isinstance(v, int) or v < 0 for v in entry)):
                out.append(Finding(
                    "blob-index", "error", f"{loc}:index[{h[:12]}]",
                    f"malformed index entry {entry!r} (want [offset, "
                    f"comp_len, raw_len] of non-negative ints)"))
                continue
            info.index[h] = tuple(entry)
            off, comp_len, _ = entry
            if not info.thin:
                if off + comp_len > section:
                    out.append(Finding(
                        "blob-index", "error", f"{loc}:index[{h[:12]}]",
                        f"blob extent [{off}, {off + comp_len}) exceeds the "
                        f"{section}-byte blob section (truncated file?)",
                        "re-copy or re-run SAVE"))
                else:
                    spans.append((off, off + comp_len, h))
        spans.sort()
        for (s0, e0, h0), (s1, _, h1) in zip(spans, spans[1:]):
            if s1 < e0:
                out.append(Finding(
                    "blob-index", "error", f"{loc}:index[{h1[:12]}]",
                    f"blob extents overlap ({h0[:12]} ends at {e0}, "
                    f"{h1[:12]} starts at {s1})", "re-run SAVE"))
        return out, info

    if raw.startswith(MAGIC):  # legacy v1: one compressed msgpack stream
        try:
            import msgpack
            obj = msgpack.unpackb(_decompress(raw[len(MAGIC):]),
                                  raw=False, strict_map_key=False)
            info.version = 1
            info.manifest = obj.get("manifest")
            if not isinstance(obj.get("blobs"), dict):
                return bad("v1 payload has no blob map")
            for h, data in obj["blobs"].items():
                if content_hash(data) != h:
                    out.append(Finding(
                        "blob-integrity", "error", f"{loc}:blob/{h[:12]}",
                        "v1 blob bytes do not match their content hash",
                        "the archive is corrupt; re-run SAVE"))
        except Exception as e:
            return bad(f"v1 payload does not decode: {type(e).__name__}: {e}")
        return out, info

    return bad("not a Foundry archive (bad magic)")


def check_container_file(path: str) -> Tuple[List[Finding], ContainerInfo]:
    try:
        with open(path, "rb") as f:
            raw = f.read()
    except OSError as e:
        return ([Finding("container-structure", "error", path,
                         f"unreadable: {e}")], ContainerInfo())
    return check_container_bytes(raw, os.path.basename(path))


# ---------------------------------------------------------------------------
# pass 1b: manifest schema + blob index completeness + tags
# ---------------------------------------------------------------------------
def _spec_entries(manifest: dict) -> Iterator[Tuple[str, dict]]:
    specs = manifest.get("specs")
    if isinstance(specs, dict):
        yield from specs.items()


def check_manifest_schema(manifest: dict, loc: str,
                          blobs=None) -> List[Finding]:
    """Manifest required fields + spec/group internal consistency + (when a
    blob mapping is given) completeness of every blob reference. ``blobs``
    only needs ``__contains__`` — membership is an index lookup, no fetch."""
    out: List[Finding] = []
    if not isinstance(manifest, dict):
        return [Finding("manifest-schema", "error", loc,
                        f"manifest is {type(manifest).__name__}, not a dict")]
    if not isinstance(manifest.get("version"), int):
        out.append(Finding("manifest-schema", "error", f"{loc}:version",
                           "missing/non-int manifest version",
                           "re-run SAVE with a current foundry_save"))
    mesh = manifest.get("mesh")
    if mesh is not None:
        axes, shape = mesh.get("axes"), mesh.get("shape")
        if (not isinstance(axes, list) or not isinstance(shape, list)
                or len(axes) != len(shape)):
            out.append(Finding(
                "manifest-schema", "error", f"{loc}:mesh",
                f"capture mesh identity malformed: axes={axes!r} "
                f"shape={shape!r} (want equal-length lists)"))
    specs = manifest.get("specs")
    if not isinstance(specs, dict) or not specs:
        out.append(Finding("manifest-schema", "error", f"{loc}:specs",
                           "no capture specs in manifest"))
        return out

    def ref(h: Optional[str], where: str, what: str, sev: str = "error"):
        if h is None or blobs is None:
            return
        if h not in blobs:
            out.append(Finding(
                "blob-index", sev, where,
                f"{what} references blob {h[:12]}… absent from the blob "
                f"index", "the container lost a blob; re-run SAVE (or pass "
                "the right --depot for a thin archive)"))

    for name, spec_m in _spec_entries(manifest):
        sloc = f"{loc}:specs.{name}"
        buckets = spec_m.get("buckets")
        if (not isinstance(buckets, list) or not buckets
                or any(not isinstance(b, int) or b < 1 for b in buckets)):
            out.append(Finding("manifest-schema", "error", f"{sloc}.buckets",
                               f"buckets must be a non-empty list of "
                               f"positive ints, got {buckets!r}"))
            continue
        if sorted(set(buckets)) != buckets:
            out.append(Finding("manifest-schema", "error", f"{sloc}.buckets",
                               "buckets must be strictly increasing "
                               f"(got {buckets})"))
        donate = spec_m.get("donate_argnums", [])
        if any(not isinstance(i, int) or i < 0 for i in donate):
            out.append(Finding("manifest-schema", "error",
                               f"{sloc}.donate_argnums",
                               f"donate_argnums must be non-negative ints, "
                               f"got {donate!r}"))
        out.extend(check_tags(spec_m.get("tags") or {}, f"{sloc}.tags"))

        groups = spec_m.get("groups")
        if not isinstance(groups, list) or not groups:
            out.append(Finding("manifest-schema", "error", f"{sloc}.groups",
                               "spec has no topology groups"))
            continue
        covered: Dict[int, int] = {}
        for gi, g in enumerate(groups):
            gloc = f"{sloc}.groups[{gi}]"
            gb = g.get("buckets") or []
            for b in gb:
                covered[b] = covered.get(b, 0) + 1
            tb = g.get("template_bucket")
            if tb not in gb:
                out.append(Finding(
                    "manifest-schema", "error", gloc,
                    f"template_bucket {tb} not a member of the group's "
                    f"buckets {gb}"))
            elif gb and tb != max(gb):
                out.append(Finding(
                    "manifest-schema", "error", gloc,
                    f"template_bucket {tb} < max group bucket {max(gb)}: "
                    f"larger buckets cannot be pad-served through the "
                    f"template", "re-run SAVE (group_buckets picks max)"))
            if g.get("executable_blob") is None:
                out.append(Finding(
                    "manifest-schema", "warning", gloc,
                    "group has no template executable; every bucket of it "
                    "LOADs via compile-from-StableHLO",
                    "re-run SAVE with template serialization on"))
            ref(g.get("executable_blob"), gloc, "template executable")
            exports = g.get("bucket_export_blobs") or {}
            for b, h in exports.items():
                ref(h, f"{gloc}.bucket_export_blobs[{b}]",
                    f"bucket {b} StableHLO export")
            for b, h in (g.get("bucket_executable_blobs") or {}).items():
                ref(h, f"{gloc}.bucket_executable_blobs[{b}]",
                    f"bucket {b} executable")
            missing = [b for b in gb if str(b) not in
                       {str(k) for k in exports}]
            if missing:
                out.append(Finding(
                    "blob-index", "warning", gloc,
                    f"buckets {missing} have no StableHLO export: exact "
                    f"realization and fallback compile are impossible for "
                    f"them", "re-run SAVE"))
        for b, n in sorted(covered.items()):
            if n > 1:
                out.append(Finding(
                    "manifest-schema", "error", f"{sloc}.groups",
                    f"bucket {b} appears in {n} topology groups"))
        uncovered = [b for b in buckets if b not in covered]
        if uncovered:
            out.append(Finding(
                "manifest-schema", "error", f"{sloc}.groups",
                f"spec buckets {uncovered} not covered by any group"))

    kc = manifest.get("kernel_catalog")
    if kc:
        for name, e in (kc.get("entries") or {}).items():
            ref(e.get("payload_hash"), f"{loc}:kernel_catalog.{name}",
                f"kernel {name} payload", sev="warning")
    return out


def check_tags(tags: dict, loc: str) -> List[Finding]:
    """``CaptureSpec.tags`` vs the engine's supported-convention matrix
    (serving/engine.py ``TAG_CONVENTIONS``). The tags version the captured
    calling convention; a key or value the engine does not speak means the
    archive would be served through the wrong loop/pool — token corruption,
    not a graceful fallback — so every violation is an error."""
    out: List[Finding] = []
    if not isinstance(tags, dict):
        return [Finding("tags-schema", "error", loc,
                        f"tags must be a dict, got {type(tags).__name__}")]
    from repro.serving.engine import TAG_CONVENTIONS, validate_tags
    for problem in validate_tags(tags):
        out.append(Finding(
            "tags-schema", "error", loc, problem,
            f"supported conventions: {sorted(TAG_CONVENTIONS)}; re-run SAVE "
            f"with a current engine or upgrade the serving engine"))
    if ("fused_sampling" in tags and "decode_loop" in tags
            and tags.get("fused_sampling")
            != (tags.get("decode_loop") == "device")):
        out.append(Finding(
            "tags-schema", "error", loc,
            f"fused_sampling={tags['fused_sampling']!r} inconsistent with "
            f"decode_loop={tags['decode_loop']!r} (device loop <=> fused)",
            "re-run SAVE; the engine always captures them together"))
    return out


# ---------------------------------------------------------------------------
# pass 1c: deep blob integrity
# ---------------------------------------------------------------------------
def check_blob_integrity(archive: Archive, loc: str) -> List[Finding]:
    """Fetch + hash-verify every blob (the deep pass: reads and decompresses
    the full container — offline cost, never on the LOAD critical path)."""
    out: List[Finding] = []
    for h in archive.blobs:
        try:
            archive.get_blob(h)
        except Exception as e:
            out.append(Finding(
                "blob-integrity", "error", f"{loc}:blob/{h[:12]}",
                f"blob fetch failed: {type(e).__name__}: {e}",
                "the container is corrupt; re-run SAVE or restore the blob "
                "from a replica/depot"))
    return out


# ---------------------------------------------------------------------------
# pass 2: StableHLO IR lint
# ---------------------------------------------------------------------------
_LOC_RE = re.compile(r'loc\("([^"]*)"')
_REPLICA_GROUPS_RE = re.compile(r"replica_groups\s*=\s*dense<([^>]*)>")
_ARG_RE = re.compile(r"%arg(\d+):")


def _main_signature(txt: str) -> str:
    """The argument list of ``@main`` (paren-matched: attrs contain
    parens in loc(...))."""
    i = txt.find("@main(")
    if i < 0:
        return ""
    j = i + len("@main(")
    depth = 1
    for k in range(j, len(txt)):
        c = txt[k]
        if c == "(":
            depth += 1
        elif c == ")":
            depth -= 1
            if depth == 0:
                return txt[j:k]
    return txt[j:]


def _donor_arg_indices(txt: str) -> set:
    """Module-arg indices carrying donation/aliasing attributes."""
    sig = _main_signature(txt)
    hits = list(_ARG_RE.finditer(sig))
    out = set()
    for m, nxt in zip(hits, hits[1:] + [None]):
        seg = sig[m.end(): nxt.start() if nxt else len(sig)]
        if "jax.buffer_donor" in seg or "tf.aliasing_output" in seg:
            out.add(int(m.group(1)))
    return out


def _expected_donated_flat(exp, donate_argnums) -> Optional[set]:
    """Flat in_aval indices covered by the spec's positional donate set."""
    import jax
    try:
        args, _kwargs = jax.tree_util.tree_unflatten(
            exp.in_tree, list(range(len(exp.in_avals))))
    except Exception:
        return None
    want = set()
    for i in donate_argnums or ():
        if i < len(args):
            want |= set(jax.tree_util.tree_leaves(args[i]))
    return want


def _parse_replica_groups(text: str) -> List[List[int]]:
    rows = []
    for row in re.findall(r"\[([0-9,\s]*?)\]", text):
        vals = [int(v) for v in row.replace(" ", "").split(",") if v != ""]
        if vals:
            rows.append(vals)
    return rows


def _covered_peer_rows(manifest: dict) -> set:
    """Every peer-group row any RankDelta in the manifest covers."""
    cover = set()
    rd = (manifest.get("rank_delta") or {}).get("capture_ranks") or []
    for d in rd:
        for rows in (d.get("peer_groups") or {}).values():
            cover.add(tuple(sorted(int(r) for r in rows)))
    return cover


def check_ir(archive: Archive, loc: str,
             manifest: Optional[dict] = None) -> List[Finding]:
    """Lint every archived StableHLO export (``canonical_export_bytes``
    output): determinism (no call-site debug locations — they make every
    blob byte-unique and defeat depot content-addressing), donation/aliasing
    consistency with the spec's ``donate_argnums``, and the §4.3 correctness
    condition — every multi-rank communication constant in the program text
    (replica groups, partition/replica id use) must be covered by a
    ``RankDelta``, else the stamped restore path would serve a program whose
    rank-dependent state was never patched."""
    import jax
    out: List[Finding] = []
    manifest = manifest if manifest is not None else archive.manifest
    cover = _covered_peer_rows(manifest)
    have_deltas = bool(
        (manifest.get("rank_delta") or {}).get("capture_ranks"))

    for name, spec_m in _spec_entries(manifest):
        donate = spec_m.get("donate_argnums") or []
        for gi, g in enumerate(spec_m.get("groups") or []):
            for b, h in sorted((g.get("bucket_export_blobs") or {}).items(),
                               key=lambda kv: str(kv[0])):
                bloc = f"{loc}:specs.{name}.groups[{gi}].export[{b}]"
                try:
                    blob = archive.get_blob(h)
                except Exception:
                    continue  # blob-index/integrity passes own this
                try:
                    exp = jax.export.deserialize(bytearray(blob))
                    txt = exp.mlir_module()
                except Exception as e:
                    out.append(Finding(
                        "ir-parse", "error", bloc,
                        f"export blob does not deserialize: "
                        f"{type(e).__name__}: {e}",
                        "re-run SAVE; the export is unusable for exact "
                        "realization or fallback compile"))
                    continue
                out.extend(_lint_one_module(exp, txt, donate, bloc,
                                            cover, have_deltas))
    return out


def _lint_one_module(exp, txt: str, donate, bloc: str, cover: set,
                     have_deltas: bool) -> List[Finding]:
    out: List[Finding] = []
    # determinism: canonical exports carry only synthetic locations; a
    # file/frame location means SAVE skipped canonical_export_bytes
    dirty = sorted({n for n in _LOC_RE.findall(txt)
                    if "/" in n or "\\" in n or ".py" in n
                    or "<" in n or n.startswith("jit(")})
    if dirty:
        out.append(Finding(
            "ir-determinism", "warning", bloc,
            f"module embeds call-site debug locations ({dirty[0]!r}"
            f"{' …' if len(dirty) > 1 else ''}): byte-identical programs "
            f"exported elsewhere will not dedup in the depot",
            "SAVE through materialize.canonical_export_bytes"))

    # donation/aliasing vs the manifest's donate_argnums
    want_flat = _expected_donated_flat(exp, donate)
    if want_flat is not None:
        kept = list(getattr(exp, "module_kept_var_idx", None)
                    or range(len(exp.in_avals)))
        expect = {k for k, flat in enumerate(kept) if flat in want_flat}
        have = {k for k in _donor_arg_indices(txt) if k < len(kept)}
        if expect != have:
            missing, extra = sorted(expect - have), sorted(have - expect)
            out.append(Finding(
                "donation-aliasing", "error", bloc,
                f"donation mismatch between spec donate_argnums={list(donate)} "
                f"and exported module "
                f"(args missing donor attrs: {missing}, unexpected donors: "
                f"{extra})", "re-run SAVE so the export and manifest agree; "
                "a LOAD would re-apply the manifest's donation onto a "
                "program compiled for a different aliasing contract"))

    # §4.3: rank/peer-table constants must be covered by a RankDelta
    for mtext in _REPLICA_GROUPS_RE.findall(txt):
        for row in _parse_replica_groups(mtext):
            if len(row) < 2:
                continue  # single-member group: no communication to patch
            if tuple(sorted(row)) not in cover:
                out.append(Finding(
                    "rank-delta-coverage", "error", bloc,
                    f"replica group {row} appears in the program but no "
                    f"RankDelta covers it: the stamped restore path would "
                    f"never patch this collective's peer state",
                    "re-run SAVE with the memory plan/mesh wired so "
                    "build_rank_deltas records every peer table"))
    if (("partition_id" in txt or "replica_id" in txt)
            and not have_deltas):
        out.append(Finding(
            "rank-delta-coverage", "error", bloc,
            "program reads partition/replica id but the archive has no "
            "rank_delta section", "re-run SAVE with a current foundry_save"))
    return out


# ---------------------------------------------------------------------------
# pass 3: memory plan
# ---------------------------------------------------------------------------
def check_memory_plan(mp: Optional[dict], loc: str) -> List[Finding]:
    """Deterministic-layout invariants of a recorded ``MemoryPlan`` manifest
    (§4.1.1): the allocation sequence must replay to the recorded offsets
    (overlap/alignment/extent), capture-window events must form the tail of
    the sequence (``replay_capture_window`` replays a contiguous suffix),
    and scope accounting must be internally consistent."""
    out: List[Finding] = []
    if mp is None:
        return out
    loc = f"{loc}:memory_plan"
    align = mp.get("align")
    if not isinstance(align, int) or align < 1:
        return [Finding("memory-plan-alignment", "error", loc,
                        f"bad alignment {align!r}")]
    allocs = mp.get("allocations")
    if not isinstance(allocs, list):
        return [Finding("memory-plan-extent", "error", loc,
                        "allocations section missing")]
    cursor = 0
    seen_capture = False
    prev = None
    for i, a in enumerate(allocs):
        aloc = f"{loc}.allocations[{i}]({a.get('name', '?')})"
        size, off = a.get("size"), a.get("offset")
        if (not isinstance(size, int) or size < 0
                or not isinstance(off, int) or off < 0):
            out.append(Finding("memory-plan-extent", "error", aloc,
                               f"malformed allocation size={size!r} "
                               f"offset={off!r}"))
            continue
        if a.get("scope") not in ("global", "per_rank"):
            out.append(Finding(
                "memory-plan-scope", "error", aloc,
                f"unknown scope {a.get('scope')!r} (want global|per_rank): "
                f"rank_extents cannot shard it", "re-run SAVE"))
        phase = a.get("phase")
        if phase not in ("init", "capture"):
            out.append(Finding("capture-window-order", "error", aloc,
                               f"unknown phase {phase!r}"))
        elif phase == "capture":
            seen_capture = True
        elif seen_capture:
            out.append(Finding(
                "capture-window-order", "error", aloc,
                "init-phase allocation after a capture-window allocation: "
                "LOAD's capture-window replay is a contiguous tail, so the "
                "replayed sequence would diverge from the recording",
                "keep init allocations before set_phase('capture')"))
        if off % align:
            out.append(Finding(
                "memory-plan-alignment", "error", aloc,
                f"offset {off} not {align}-byte aligned"))
        if prev is not None and off < prev[0] + prev[1]:
            out.append(Finding(
                "memory-plan-overlap", "error", aloc,
                f"allocation [{off}, {off + size}) overlaps "
                f"{prev[2]!r} ending at {prev[0] + prev[1]}",
                "the SAVE-side arena is monotonic; this record was "
                "hand-edited or corrupted — re-run SAVE"))
        elif off > cursor:
            out.append(Finding(
                "memory-plan-leak", "warning", aloc,
                f"{off - cursor} unaccounted bytes before this allocation "
                f"(beyond alignment padding): space LOAD premaps but "
                f"nothing owns"))
        cursor = max(cursor, off + size + ((-size) % align))
        prev = (off, size, a.get("name"))
    extent = mp.get("extent")
    if not isinstance(extent, int) or extent < (prev[0] + prev[1] if prev
                                                else 0):
        out.append(Finding(
            "memory-plan-extent", "error", f"{loc}.extent",
            f"recorded extent {extent!r} does not cover the allocation "
            f"sequence (ends at {prev[0] + prev[1] if prev else 0}): LOAD "
            f"would preallocate too little and fail mid-replay",
            "re-run SAVE"))
    return out


# ---------------------------------------------------------------------------
# pass 2/3 joint: rank-delta section vs mesh + memory plan
# ---------------------------------------------------------------------------
def check_rank_delta_section(manifest: dict, loc: str) -> List[Finding]:
    """Completeness of the archive's ``rank_delta`` section (§4.3): one
    delta per capture rank, a peer table per mesh axis containing the rank
    itself, coordinates matching the mesh, and ``comm_buffers`` equal to the
    memory plan's ``rank_extents`` re-derivation. Every drift here is state
    the stamped restore path would silently fail to patch."""
    out: List[Finding] = []
    rd = manifest.get("rank_delta")
    mesh = manifest.get("mesh") or {"axes": [], "shape": []}
    if not isinstance(rd, dict) or not rd.get("capture_ranks"):
        out.append(Finding(
            "rank-delta-coverage", "warning", f"{loc}:rank_delta",
            "archive has no rank_delta section (pre-§4.3 SAVE?): the "
            "stamped restore path is unavailable, every mesh rebind "
            "falls back to compile-from-StableHLO",
            "re-run SAVE with a current foundry_save"))
        return out
    shape = [int(s) for s in mesh.get("shape") or []]
    axes = [str(a) for a in mesh.get("axes") or []]
    n = identity_device_count(mesh)
    deltas = rd["capture_ranks"]
    rloc = f"{loc}:rank_delta.capture_ranks"
    got_ranks = [d.get("rank") for d in deltas]
    if sorted(got_ranks) != list(range(n)):
        out.append(Finding(
            "rank-delta-coverage", "error", rloc,
            f"capture mesh has {n} rank(s) but deltas cover {got_ranks}: "
            f"every rank's communication state must be recorded",
            "re-run SAVE; build_rank_deltas emits one delta per rank"))
    truth_groups = peer_groups(shape, axes)
    truth_coords = rank_coords(shape)
    plan_extents = None
    if manifest.get("memory_plan"):
        try:
            plan_extents = MemoryPlan.from_manifest(
                manifest["memory_plan"]).rank_extents(max(n, 1))
        except Exception:
            plan_extents = None  # memory-plan pass owns malformed plans
    for d in deltas:
        r = d.get("rank")
        dloc = f"{rloc}[{r}]"
        if not isinstance(r, int) or not 0 <= r < n:
            continue  # covered by the range check above
        coords = tuple(d.get("coords") or ())
        if shape and coords != truth_coords[r]:
            out.append(Finding(
                "rank-delta-coverage", "error", f"{dloc}.coords",
                f"rank {r} coords {coords} != mesh-derived "
                f"{truth_coords[r]}"))
        pg = d.get("peer_groups") or {}
        for ax in axes:
            if ax not in pg:
                out.append(Finding(
                    "rank-delta-coverage", "error", f"{dloc}.peer_groups",
                    f"rank {r} has no peer table for mesh axis {ax!r}: "
                    f"collectives over it would replay with unpatched "
                    f"peer state", "re-run SAVE; every axis needs a table"))
                continue
            mine = [int(x) for x in pg[ax]]
            want = next(g for g in truth_groups[ax] if r in g)
            if r not in mine:
                out.append(Finding(
                    "rank-delta-coverage", "error", f"{dloc}.peer_groups",
                    f"rank {r} missing from its own {ax!r} peer group "
                    f"{mine}"))
            elif sorted(mine) != sorted(want):
                out.append(Finding(
                    "rank-delta-coverage", "error", f"{dloc}.peer_groups",
                    f"{ax!r} peer group {mine} != mesh-derived {want}"))
        for ax in pg:
            if ax not in axes:
                out.append(Finding(
                    "rank-delta-coverage", "error", f"{dloc}.peer_groups",
                    f"peer table for unknown mesh axis {ax!r}"))
        if plan_extents is not None:
            got = [dict(b) for b in d.get("comm_buffers") or []]
            if got != plan_extents:
                out.append(Finding(
                    "memory-plan-scope", "error", f"{dloc}.comm_buffers",
                    f"rank {r} buffer table diverges from the memory "
                    f"plan's rank_extents({max(n, 1)}) re-derivation "
                    f"({len(got)} vs {len(plan_extents)} entries or "
                    f"offset/size drift)",
                    "re-run SAVE so deltas and plan agree"))
    fields = rd.get("rank_dependent_fields") or []
    if "mesh" not in fields:
        out.append(Finding(
            "rank-delta-coverage", "warning", f"{loc}:rank_delta",
            "rank_dependent_fields does not list 'mesh'",
            "re-run SAVE with a current foundry_save"))
    return out


# ---------------------------------------------------------------------------
# archive-level drivers
# ---------------------------------------------------------------------------
def verify_for_load(archive: Archive, loc: str = "archive") -> List[Finding]:
    """The strict-LOAD pre-flight: every metadata-level pass, no blob
    fetches and no IR deserialization — cost is microseconds to low
    milliseconds regardless of archive size, which is what lets
    ``foundry_load(strict=True)`` stay under the <5% LOAD budget
    (benchmarks/fig13_autoscale.py asserts it)."""
    m = archive.manifest
    out = check_manifest_schema(m, loc, blobs=archive.blobs)
    out += check_memory_plan(m.get("memory_plan"), loc)
    out += check_rank_delta_section(m, loc)
    return out


def check_archive(archive: Archive, loc: str = "archive", *,
                  deep: bool = True, ir: bool = True) -> List[Finding]:
    """Full offline verification of an (already opened) archive."""
    out = verify_for_load(archive, loc)
    if deep:
        out += check_blob_integrity(archive, loc)
    if ir:
        out += check_ir(archive, loc)
    return out


def check_archive_file(path: str, depot=None, *, deep: bool = True,
                       ir: bool = True) -> List[Finding]:
    """Full offline verification of an archive file: container structure
    first, then (if the container parses) every content pass. ``depot`` is
    required to resolve a thin archive's blobs; without it only the
    structural and manifest passes run."""
    loc = os.path.basename(path)
    out, info = check_container_file(path)
    if info.manifest is None:
        return out
    if info.thin and depot is None:
        out.append(Finding(
            "blob-index", "warning", loc,
            "thin (depot-backed) archive checked without --depot: blob "
            "presence/integrity not verifiable",
            "pass --depot <root>"))
        out += check_manifest_schema(info.manifest, loc, blobs=None)
        out += check_memory_plan(info.manifest.get("memory_plan"), loc)
        out += check_rank_delta_section(info.manifest, loc)
        return out
    try:
        archive = Archive.load(path, depot=depot)
    except Exception as e:
        out.append(Finding(
            "container-structure", "error", loc,
            f"container parses but Archive.load failed: "
            f"{type(e).__name__}: {e}"))
        return out
    return out + check_archive(archive, loc, deep=deep, ir=ir)


# ---------------------------------------------------------------------------
# pass 4: depot fsck
# ---------------------------------------------------------------------------
def check_depot(root: str, *, gc_orphans: bool = False,
                deep: bool = False) -> Tuple[List[Finding], Dict[str, int]]:
    """fsck for a ``TemplateDepot`` directory: ``index.json`` readability
    (the torn-write case), index-vs-disk agreement in both directions,
    refcount consistency between the archive and blob planes, and thin
    manifests that actually resolve. Read-only unless ``gc_orphans`` —
    which deletes only blob *files* the index does not know (the crash
    residue of a SAVE that died between blob deposit and index flush)."""
    loc = os.path.basename(os.path.abspath(root)) or root
    out: List[Finding] = []
    actions = {"gc_removed_blobs": 0, "gc_freed_bytes": 0}
    blob_dir = os.path.join(root, "blobs")
    manifest_dir = os.path.join(root, "manifests")
    index_path = os.path.join(root, "index.json")

    index = None
    if not os.path.exists(index_path):
        sev = ("error" if os.path.isdir(blob_dir) and os.listdir(blob_dir)
               else "warning")
        out.append(Finding(
            "depot-index", sev, f"{loc}/index.json",
            "index.json missing" + (" but blobs exist on disk" if
                                    sev == "error" else " (empty depot?)"),
            "re-put the archives to rebuild the index"))
    else:
        try:
            with open(index_path) as f:
                index = json.load(f)
        except ValueError as e:
            out.append(Finding(
                "depot-index", "error", f"{loc}/index.json",
                f"index.json does not parse ({e}): torn write — a crash "
                f"mid-flush, or a non-atomic writer",
                "restore index.json from backup or re-put every archive; "
                "TemplateDepot._flush writes tmp+rename exactly to prevent "
                "this"))
        except OSError as e:
            out.append(Finding("depot-index", "error", f"{loc}/index.json",
                               f"unreadable: {e}"))
    if index is not None and index.get("version") != 1:
        out.append(Finding(
            "depot-index", "error", f"{loc}/index.json",
            f"unknown index version {index.get('version')!r}",
            "upgrade this checker or the depot"))
        index = None

    blobs = (index or {}).get("blobs", {})
    archives = (index or {}).get("archives", {})
    known_refs = {os.path.abspath(os.path.join(root, e.get("file", "")))
                  for e in archives.values()}

    # blob plane: index -> disk
    for h, meta in sorted(blobs.items()):
        p = os.path.join(blob_dir, h)
        if not os.path.exists(p):
            out.append(Finding(
                "depot-missing-blob", "error", f"{loc}/blobs/{h[:12]}",
                f"indexed blob missing on disk (held by "
                f"{len(meta.get('refs', []))} ref(s))",
                "restore the blob file or remove+re-put the archives that "
                "reference it"))
            continue
        size = os.path.getsize(p)
        if size != meta.get("comp_len"):
            out.append(Finding(
                "depot-blob-size", "error", f"{loc}/blobs/{h[:12]}",
                f"file is {size} bytes, index says {meta.get('comp_len')} "
                f"(partial write?)", "delete the file and re-put an "
                "archive that carries this blob"))
        elif deep:
            try:
                with open(p, "rb") as f:
                    data = _decompress(f.read())
                if content_hash(data) != h:
                    raise ValueError("content hash mismatch")
            except Exception as e:
                out.append(Finding(
                    "blob-integrity", "error", f"{loc}/blobs/{h[:12]}",
                    f"blob does not verify: {type(e).__name__}: {e}",
                    "delete the file and re-put a carrying archive"))
        for ref in meta.get("refs", []):
            if ref not in known_refs:
                out.append(Finding(
                    "depot-dangling-ref", "warning",
                    f"{loc}/blobs/{h[:12]}",
                    f"ref {ref!r} does not match any indexed archive: the "
                    f"blob can never be garbage-collected",
                    "TemplateDepot.release_ref(ref) then gc()"))

    # blob plane: disk -> index (the SAVE-crash residue gc_orphans prunes)
    if os.path.isdir(blob_dir):
        for fn in sorted(os.listdir(blob_dir)):
            p = os.path.join(blob_dir, fn)
            if fn in blobs or not os.path.isfile(p):
                continue
            size = os.path.getsize(p)
            if gc_orphans:
                os.remove(p)
                actions["gc_removed_blobs"] += 1
                actions["gc_freed_bytes"] += size
                out.append(Finding(
                    "depot-orphan-blob", "info", f"{loc}/blobs/{fn[:12]}",
                    f"orphan blob ({size} bytes) removed"))
            else:
                out.append(Finding(
                    "depot-orphan-blob", "warning", f"{loc}/blobs/{fn[:12]}",
                    f"blob file not in the index ({size} bytes): dead "
                    f"space from a crashed SAVE or an index rollback",
                    "re-run with --gc-orphans to delete"))

    # archive plane
    for name, entry in sorted(archives.items()):
        aloc = f"{loc}/manifests/{name}"
        p = os.path.join(root, entry.get("file", ""))
        if not os.path.isfile(p):
            out.append(Finding(
                "depot-missing-manifest", "error", aloc,
                f"archive {name!r} indexed but its manifest file "
                f"{entry.get('file')!r} is missing",
                "remove_archive(name) or restore the file"))
            continue
        cf, cinfo = check_container_file(p)
        out += [f for f in cf if f.severity == "error"]
        if cinfo.manifest is None:
            continue
        if not cinfo.thin:
            out.append(Finding(
                "depot-manifest", "error", aloc,
                "manifest file is not a thin (depot-flagged) container"))
        missing = [h for h in cinfo.index if h not in blobs]
        if missing:
            out.append(Finding(
                "depot-refcount", "error", aloc,
                f"archive references {len(missing)} blob(s) the index does "
                f"not hold (first: {missing[0][:12]}…)",
                "re-put the archive"))
        me = os.path.abspath(p)
        unheld = [h for h in cinfo.index
                  if h in blobs and me not in blobs[h].get("refs", [])]
        if unheld:
            out.append(Finding(
                "depot-refcount", "error", aloc,
                f"{len(unheld)} blob(s) used by {name!r} hold no ref for "
                f"it (first: {unheld[0][:12]}…): gc() would delete state "
                f"a live archive needs",
                "re-put the archive to re-register its refs"))
        listed = set(entry.get("blob_hashes", []))
        if listed != set(cinfo.index):
            out.append(Finding(
                "depot-refcount", "error", aloc,
                f"index blob_hashes disagree with the manifest's own blob "
                f"index ({len(listed)} vs {len(cinfo.index)})",
                "re-put the archive"))

    # manifest plane: disk -> index
    if os.path.isdir(manifest_dir):
        indexed_files = {os.path.basename(e.get("file", ""))
                         for e in archives.values()}
        for fn in sorted(os.listdir(manifest_dir)):
            if fn not in indexed_files:
                out.append(Finding(
                    "depot-orphan-manifest", "warning",
                    f"{loc}/manifests/{fn}",
                    "manifest file not in the index: crash between "
                    "archive save and index flush",
                    "delete it or re-put the archive under its name"))
    return out, actions


# ---------------------------------------------------------------------------
# serialization for the CLI / CI gates
# ---------------------------------------------------------------------------
def findings_to_json(findings: Sequence[Finding],
                     actions: Optional[Dict[str, int]] = None) -> dict:
    doc = {"findings": [asdict(f) for f in findings],
           "summary": summarize(findings)}
    if actions:
        doc["actions"] = dict(actions)
    return doc


def exit_code(findings: Sequence[Finding]) -> int:
    s = summarize(findings)
    if s["error"]:
        return 2
    if s["warning"]:
        return 1
    return 0
