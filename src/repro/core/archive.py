"""Portable Foundry archive (paper §3: the output of SAVE).

One file, two container layouts:

    v2 (``FNDRYJX2``, written by ``save``/``to_bytes``)
        MAGIC + u64 header length + compressed msgpack header
        {manifest, blob index} + a blob section of individually-compressed
        blobs. The header is all LOAD has to parse up front; blobs are
        fetched by (offset, length) on demand. This is what makes a fleet of
        replicas cold-starting against ONE archive cheap: the manifest is
        parsed once, and each blob is read + decompressed + hash-verified at
        most once no matter how many concurrent LOADs share the ``Archive``
        object (``BlobStore`` is lock-protected and caches fetched blobs).

    v1 (``FNDRYJX1``, legacy)
        MAGIC + one compressed msgpack blob {manifest, blobs}. Still
        readable; necessarily eager (one stream, no random access).

Hashes are verified on first fetch (a corrupted archive must fail loudly,
not produce a silently-wrong engine). The binary format keeps parse time in
the milliseconds even for hundreds of graphs (paper §5.3 moved from JSON to
a binary format for exactly this reason; we benchmark both in
benchmarks/tab1_storage.py).

Compression codec: zstd when the ``zstandard`` package is available, stdlib
``zlib`` otherwise. The codec is sniffed from each compressed stream's own
magic on read (zstd frames begin with 0x28B52FFD; zlib streams with 0x78),
so archives written under either codec load under both, and the container
MAGIC stays stable.
"""
from __future__ import annotations

import hashlib
import json
import os
import struct
import threading
import time
import zlib
from dataclasses import dataclass, field
from typing import Any, Dict, Iterator, Optional

import msgpack

from repro.obs import metrics as obs_metrics
from repro.obs.trace import span
from repro.serving.faults import fault_point

try:
    import zstandard
except ImportError:  # archives remain readable/writable via stdlib zlib
    zstandard = None

MAGIC = b"FNDRYJX1"
MAGIC2 = b"FNDRYJX2"
_ZSTD_FRAME_MAGIC = b"\x28\xb5\x2f\xfd"

# docs/architecture.md §13 has the full metric catalog
_M_BLOB_FETCH = obs_metrics.counter(
    "depot_blob_fetch_total",
    "BlobStore reads by result: hit = served from the in-memory cache, "
    "miss = read + decompressed + verified from the backing source.",
    ("result",))


def io_retries(fn, what: str, *, attempts: int = 3,
               base_delay_s: float = 0.005, retry_on=(OSError,)):
    """Bounded exponential-backoff retry for transient IO (flaky NFS mount,
    depot blob mid-replication, torn read). Retries ``fn()`` on ``retry_on``
    up to ``attempts`` total tries with 1x/2x/4x... ``base_delay_s`` sleeps
    between them, then re-raises the last failure — bounded, so a genuinely
    dead backing store still fails fast enough for the caller's own
    degradation (strict-LOAD refusal, replica FAILED) to engage."""
    for k in range(attempts):
        try:
            return fn()
        except retry_on:
            if k + 1 >= attempts:
                raise
            time.sleep(base_delay_s * (2 ** k))
    raise AssertionError(f"unreachable: io_retries({what})")


def _compress(payload: bytes, level: int) -> bytes:
    if zstandard is not None:
        return zstandard.ZstdCompressor(level=level).compress(payload)
    return zlib.compress(payload, min(level, 9))


def _decompress(comp: bytes) -> bytes:
    if comp.startswith(_ZSTD_FRAME_MAGIC):
        if zstandard is None:
            raise ValueError(
                "archive is zstd-compressed but the zstandard package is "
                "not installed; re-save it with zlib or install zstandard")
        return zstandard.ZstdDecompressor().decompress(comp)
    return zlib.decompress(comp)


def content_hash(data: bytes) -> str:
    return hashlib.blake2b(data, digest_size=16).hexdigest()


# ---------------------------------------------------------------------------
# blob backing
# ---------------------------------------------------------------------------
class _BytesSource:
    """Random access over an in-memory v2 container."""

    def __init__(self, raw: bytes, base: int):
        self._raw = raw
        self._base = base

    def read(self, offset: int, length: int) -> bytes:
        return bytes(self._raw[self._base + offset:
                               self._base + offset + length])


class _FileSource:
    """Random access over an on-disk v2 container (handle opened lazily so a
    loaded Archive stays picklable/forkable until first fetch)."""

    def __init__(self, path: str, base: int):
        self._path = path
        self._base = base
        self._f = None
        self._lock = threading.Lock()

    def read(self, offset: int, length: int) -> bytes:
        with self._lock:
            if self._f is None:
                # held for the Archive's lifetime (positioned reads), not a
                # with-block scope
                self._f = open(self._path, "rb")  # noqa: SIM115
            if not hasattr(os, "pread"):  # no positioned read: serialize
                self._f.seek(self._base + offset)
                return self._f.read(length)
            fd = self._f.fileno()
        return os.pread(fd, length, self._base + offset)


class BlobStore:
    """Content-hash-keyed blob mapping with optional lazy backing.

    Composes an in-memory dict (SAVE-side additions, v1 archives, fetch
    cache) with an index ``{hash: (offset, comp_len, raw_len)}`` over a
    random-access source (v2 archives). A blob reachable only through the
    index is read, decompressed and hash-verified on first access, then
    cached — concurrent LOADs sharing one store each pay the fetch at most
    once fleet-wide.
    """

    def __init__(self, data: Optional[Dict[str, bytes]] = None, *,
                 index: Optional[Dict[str, Any]] = None, source=None):
        self._data: Dict[str, bytes] = dict(data or {})
        self._index: Dict[str, tuple] = {k: tuple(v)
                                         for k, v in (index or {}).items()}
        self._source = source
        self._lock = threading.Lock()
        self._inflight: Dict[str, threading.Event] = {}
        self._verified: set = set()  # hashes checked at fetch time

    # -- mapping protocol ------------------------------------------------
    def __getitem__(self, h: str) -> bytes:
        """Single-flight fetch: concurrent readers of an unfetched blob
        elect one fetcher (per-hash event); the rest wait for the cached
        result, so each blob is read + decompressed + verified at most once
        no matter how many LOADs share the store. I/O and decompression run
        OUTSIDE the lock, so distinct blobs fetch concurrently."""
        while True:
            with self._lock:
                if h in self._data:
                    _M_BLOB_FETCH.inc(result="hit")
                    return self._data[h]
                if h not in self._index:
                    raise KeyError(h)
                entry = self._index[h]
                event = self._inflight.get(h)
                if event is None:
                    event = threading.Event()
                    self._inflight[h] = event
                    fetching = True
                else:
                    fetching = False
            if not fetching:
                event.wait()
                continue  # cached now — or the fetcher failed and we retry
            try:
                def _fetch():
                    if hasattr(self._source, "read_hash"):
                        # content-addressed backing (core/depot.py): the hash
                        # IS the address; (offset, comp_len) are bookkeeping
                        comp = self._source.read_hash(h)
                    else:
                        offset, comp_len, _ = entry
                        comp = self._source.read(offset, comp_len)
                    comp = fault_point("depot.fetch", payload=comp, tag=h)
                    try:
                        data = _decompress(comp)
                    except ValueError:
                        raise  # zstd-missing diagnostic: not a torn read
                    except Exception as e:
                        raise ValueError(
                            f"archive blob {h} corrupt "
                            f"(undecompressable: {type(e).__name__})") from e
                    if content_hash(data) != h:
                        raise ValueError(f"archive blob {h} corrupt")
                    return data
                # transient IO (OSError) and torn/bit-rotted reads
                # (ValueError: the re-read may verify) retry with bounded
                # backoff; a persistently corrupt blob still fails loudly
                data = io_retries(_fetch, f"blob {h}",
                                  retry_on=(OSError, ValueError))
                with self._lock:
                    self._data[h] = data
                    self._verified.add(h)
                _M_BLOB_FETCH.inc(result="miss")
                return data
            finally:
                with self._lock:
                    self._inflight.pop(h, None)
                event.set()

    def __setitem__(self, h: str, data: bytes):
        with self._lock:
            self._data[h] = data
            self._index.pop(h, None)  # fresh bytes supersede the backing
            self._verified.discard(h)

    def __delitem__(self, h: str):
        with self._lock:
            found = h in self._data or h in self._index
            self._data.pop(h, None)
            self._index.pop(h, None)
        if not found:
            raise KeyError(h)

    def __contains__(self, h) -> bool:
        with self._lock:
            return h in self._data or h in self._index

    def __iter__(self) -> Iterator[str]:
        with self._lock:
            keys = list(self._data)
            keys += [k for k in self._index if k not in self._data]
        return iter(keys)

    def __len__(self) -> int:
        with self._lock:
            return len(set(self._data) | set(self._index))

    def keys(self):
        return list(self)

    def values(self):
        return [self[h] for h in self]

    def items(self):
        return [(h, self[h]) for h in self]

    def register(self, h: str, entry) -> None:
        """Add/refresh a lazy index entry ``(offset, comp_len, raw_len)``
        without touching cached bytes. Used by depot-shared stores when a new
        archive's blobs join the (already open) store."""
        with self._lock:
            if h not in self._data:
                self._index[h] = tuple(entry)

    # -- accounting ------------------------------------------------------
    def raw_bytes(self) -> int:
        """Total uncompressed blob bytes, WITHOUT fetching lazy blobs."""
        with self._lock:
            total = sum(raw_len for h, (_, _, raw_len) in self._index.items()
                        if h not in self._data)
            total += sum(len(b) for b in self._data.values())
        return total

    def fetched(self) -> int:
        """Blobs materialized in memory (cache hits are free below this)."""
        with self._lock:
            return len(self._data)

    def is_verified(self, h: str) -> bool:
        """True if ``h`` was hash-checked when fetched from the backing
        (repeat reads need no re-hash; directly-set bytes are not exempt)."""
        with self._lock:
            return h in self._verified


@dataclass
class Archive:
    manifest: Dict[str, Any] = field(default_factory=dict)
    blobs: BlobStore = field(default_factory=BlobStore)

    def __post_init__(self):
        if isinstance(self.blobs, dict):  # plain-dict construction (tests)
            self.blobs = BlobStore(self.blobs)

    def add_blob(self, data: bytes) -> str:
        h = content_hash(data)
        self.blobs[h] = data
        return h

    def get_blob(self, h: str) -> bytes:
        data = self.blobs[h]
        # source-fetched blobs were verified once at fetch; only bytes that
        # never passed through the backing need checking here
        if not self.blobs.is_verified(h) and content_hash(data) != h:
            raise ValueError(f"archive blob {h} failed content verification")
        return data

    # ------------------------------------------------------------------
    def to_bytes(self, level: int = 3) -> bytes:
        index: Dict[str, list] = {}
        parts = []
        offset = 0
        for h in self.blobs:
            data = self.blobs[h]
            comp = _compress(data, level)
            index[h] = [offset, len(comp), len(data)]
            parts.append(comp)
            offset += len(comp)
        header = _compress(msgpack.packb(
            {"manifest": self.manifest, "index": index}, use_bin_type=True),
            level)
        return b"".join([MAGIC2, struct.pack("<Q", len(header)), header]
                        + parts)

    @classmethod
    def from_bytes(cls, raw: bytes, lazy: bool = False) -> "Archive":
        if raw.startswith(MAGIC2):
            head, base = cls._parse_v2_header(raw)
            ar = cls(manifest=head["manifest"],
                     blobs=BlobStore(index=head["index"],
                                     source=_BytesSource(raw, base)))
            if not lazy:
                for h in ar.blobs:
                    ar.blobs[h]  # fetch + verify everything up front
            return ar
        if raw.startswith(MAGIC):  # legacy v1: one stream, necessarily eager
            payload = _decompress(raw[len(MAGIC):])
            obj = msgpack.unpackb(payload, raw=False, strict_map_key=False)
            ar = cls(manifest=obj["manifest"], blobs=BlobStore(obj["blobs"]))
            for h in ar.blobs:
                if content_hash(ar.blobs[h]) != h:
                    raise ValueError(f"archive blob {h} corrupt")
            return ar
        raise ValueError("not a Foundry archive (bad magic)")

    @staticmethod
    def _parse_v2_header(raw: bytes) -> tuple:
        (hlen,) = struct.unpack_from("<Q", raw, len(MAGIC2))
        base = len(MAGIC2) + 8
        head = msgpack.unpackb(_decompress(bytes(raw[base:base + hlen])),
                               raw=False, strict_map_key=False)
        return head, base + hlen

    def save(self, path: str, level: int = 3, depot=None) -> int:
        """Write the archive to ``path``. With ``depot`` (a
        ``core.depot.TemplateDepot``), the file is a *thin* manifest: the
        same v2 header (manifest + blob index) with a ``depot`` flag and NO
        blob section — every blob is deposited into the depot's
        content-addressed store instead, deduplicated against whatever other
        archives already live there. Thin archives are reopened with
        ``Archive.load(path, depot=...)``."""
        if depot is not None:
            data = self._to_bytes_thin(depot, level)
        else:
            data = self.to_bytes(level)
        tmp = path + ".tmp"
        with open(tmp, "wb") as f:
            f.write(data)
        os.replace(tmp, path)  # atomic
        if depot is not None:
            depot.register_ref(os.path.abspath(path), list(self.blobs))
        return len(data)

    def _to_bytes_thin(self, depot, level: int = 3) -> bytes:
        if self.blobs is depot.store:
            # a depot-opened archive shares the depot-wide store: iterating
            # it would sweep EVERY depot blob into this manifest
            raise ValueError(
                "cannot re-deposit an archive opened from this depot; "
                "its thin manifest already lives there")
        index: Dict[str, list] = {}
        for h in self.blobs:
            comp_len, raw_len = depot.ensure_blob(h, lambda h=h: self.blobs[h],
                                                  level=level)
            index[h] = [0, comp_len, raw_len]
        header = _compress(msgpack.packb(
            {"manifest": self.manifest, "index": index, "depot": True},
            use_bin_type=True), level)
        return b"".join([MAGIC2, struct.pack("<Q", len(header)), header])

    @classmethod
    def load(cls, path: str, lazy: bool = True, depot=None) -> "Archive":
        """Open an archive file. ``lazy=True`` (default) parses only the
        header; blobs are fetched on demand — the cheap path for N replicas
        LOADing one shared archive. ``lazy=False`` restores the old behavior
        of materializing and verifying every blob up front.

        A *thin* archive (written with ``save(..., depot=...)``) resolves its
        blobs through ``depot``'s shared store: pass the same (or an
        equivalent) depot, or opening fails. The returned Archive's blob
        store IS the depot store, so blobs shared across models are fetched
        at most once depot-wide."""
        with span("archive.open", cat="load"):
            # archive open is the first IO of every cold start: transient
            # failures (archive still replicating onto this host) retry with
            # bounded backoff before the replica is declared FAILED
            f = io_retries(lambda: open(path, "rb"),  # noqa: SIM115
                           f"archive {path}")
            with f:
                magic = f.read(len(MAGIC2))
                if magic == MAGIC2:
                    (hlen,) = struct.unpack("<Q", f.read(8))
                    head = msgpack.unpackb(_decompress(f.read(hlen)),
                                           raw=False, strict_map_key=False)
                    base = len(MAGIC2) + 8 + hlen
                    if head.get("depot"):
                        if depot is None:
                            raise ValueError(
                                f"{path} is a depot-backed (thin) archive; "
                                f"reopen it with Archive.load(path, depot=...)")
                        missing = [h for h in head["index"]
                                   if not depot.has_blob(h)]
                        if missing:
                            # fail at open with the real cause, not with a
                            # FileNotFoundError from some later blob fetch
                            raise ValueError(
                                f"{path} references {len(missing)} blob(s) the "
                                f"depot at {depot.root} does not hold (first: "
                                f"{missing[0]}); wrong depot?")
                        for h, entry in head["index"].items():
                            depot.store.register(h, entry)
                        ar = cls(manifest=head["manifest"], blobs=depot.store)
                        if not lazy:
                            for h in head["index"]:
                                ar.blobs[h]
                        return ar
                    ar = cls(manifest=head["manifest"],
                             blobs=BlobStore(index=head["index"],
                                             source=_FileSource(path, base)))
                    if not lazy:
                        for h in ar.blobs:  # fetch + verify everything up front
                            ar.blobs[h]
                    return ar
                f.seek(0)
                return cls.from_bytes(f.read(), lazy=lazy)

    # -- debugging / storage accounting --------------------------------
    def blob_bytes(self) -> int:
        return self.blobs.raw_bytes()

    def manifest_json(self) -> str:
        return json.dumps(self.manifest, indent=1, default=str)
