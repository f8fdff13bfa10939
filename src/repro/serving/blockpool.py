"""Block-table paged KV pool + radix prefix cache for the serving engine.

Replaces the slot pool's per-request contiguous KV rows (kvcache.py) with
fixed-size KV *blocks* shared across requests:

  * ``BlockAllocator`` — ref-counted free list over ``n_blocks`` blocks.
    Block 0 is a reserved scratch block: padded/inactive batch rows point
    every block-table entry at it, so their in-graph writes and gathers are
    harmless (decode masks positions past ``lengths`` before softmax).
  * ``RadixPrefixCache`` — a radix tree over *block-sized token chunks*.
    Each node owns exactly one block (one tree reference in the allocator);
    a request whose prompt prefixes a cached chain reuses those blocks
    instead of re-prefilling, diverging tails fork copy-on-write, and
    unreferenced nodes evict LRU when the allocator runs dry.
  * ``PagedKVCachePool`` — the engine-facing pool. Device state is the
    donated decode-cache pytree ``{"k","v","block_tables","lengths"}``: the
    k/v pools are batch-invariant ``[L, NB, bs, Hkv, Dh]`` buffers (every
    bucket's captured program takes the *same* pools; only block_tables and
    lengths carry the batch dim), so templates group across buckets exactly
    as the slot layout's did. Host-side metadata (per-slot block tables and
    lengths) is the source of truth; scheduling events mark it dirty and
    ``sync`` rebuilds the small device tables wholesale before dispatch.

Slot compaction becomes pure host bookkeeping — releasing a request moves
its *table*, never its KV bytes (the slot pool's O(cache) device row move
disappears). Construction registers the pool's deterministic extents with
the MemoryPlan exactly like the slot pool (paper §5.4), and
``export_rows``/``import_rows`` speak the same dense RowBundle interchange
format as ``KVCachePool`` so live reshard (§8) migrates KV across layouts
and meshes.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.memory_plan import MemoryPlan
from repro.obs import metrics as obs_metrics
from repro.serving.rowbundle import (RowBundle, check_export_slots,
                                     check_import, reshard_rows)

# Mirrors RadixPrefixCache.stats — both fed at the same code points so the
# exposition and the dict can never disagree (docs/architecture.md §13).
_M_RADIX = obs_metrics.counter(
    "kv_radix_events_total",
    "Radix prefix-cache events (hit/miss/eviction/dedup/cow_fork).",
    labelnames=("event",))


class BlockAllocator:
    """Ref-counted allocator over ``n_blocks`` fixed-size KV blocks.

    Block 0 is the reserved scratch block: its refcount is pinned and it is
    never handed out, so zeroed block-table entries always alias a block no
    live request reads through its length mask."""

    SCRATCH = 0

    def __init__(self, n_blocks: int):
        if n_blocks < 2:
            raise ValueError(f"need >= 2 blocks (scratch + 1), got {n_blocks}")
        self.n_blocks = n_blocks
        self.refs = [0] * n_blocks
        self.refs[self.SCRATCH] = 1
        # pop() yields ascending block ids — deterministic layouts for tests
        self._free = list(range(n_blocks - 1, 0, -1))

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def n_usable(self) -> int:
        """Blocks a single request could ever hold (everything but scratch)."""
        return self.n_blocks - 1

    def alloc(self) -> int:
        if not self._free:
            raise RuntimeError("kv block pool exhausted")
        b = self._free.pop()
        self.refs[b] = 1
        return b

    def ref(self, block: int) -> int:
        return self.refs[block]

    def incref(self, block: int):
        if self.refs[block] <= 0:
            raise ValueError(f"incref of free block {block}")
        self.refs[block] += 1

    def decref(self, block: int):
        if block == self.SCRATCH:
            return
        if self.refs[block] <= 0:
            raise ValueError(f"decref of free block {block}")
        self.refs[block] -= 1
        if self.refs[block] == 0:
            self._free.append(block)


class _RadixNode:
    __slots__ = ("chunk", "block", "children", "parent", "tick")

    def __init__(self, chunk, block, parent):
        self.chunk = chunk          # tuple of block_size token ids
        self.block = block          # allocator block backing this chunk's KV
        self.children: Dict[tuple, "_RadixNode"] = {}
        self.parent = parent
        self.tick = 0


class RadixPrefixCache:
    """Radix tree over block-sized token chunks; one block per node.

    The tree holds one allocator reference per node, so a cached block
    outlives the request that produced it and is reclaimed only by LRU
    eviction (``evict_lru``) once no live request references it."""

    def __init__(self, allocator: BlockAllocator, block_size: int):
        self.allocator = allocator
        self.block_size = block_size
        self.root = _RadixNode(None, None, None)
        self._tick = 0
        self.stats = {"hits": 0, "misses": 0, "evictions": 0, "dedup": 0}

    # ------------------------------------------------------------------
    def _chunks(self, tokens):
        bs = self.block_size
        for i in range(len(tokens) // bs):
            yield tuple(tokens[i * bs:(i + 1) * bs])

    def _touch(self, node: _RadixNode):
        self._tick += 1
        node.tick = self._tick

    def match(self, tokens) -> List[_RadixNode]:
        """Longest chain of cached full-block nodes prefixing ``tokens``.
        Read-only on the allocator: callers take their own references."""
        node, out = self.root, []
        for chunk in self._chunks(tokens):
            child = node.children.get(chunk)
            if child is None:
                break
            self._touch(child)
            out.append(child)
            node = child
        return out

    def partial_child(self, node: _RadixNode,
                      rest) -> Tuple[Optional[_RadixNode], int]:
        """Child of ``node`` sharing the longest strict token prefix with
        ``rest``: the copy-on-write fork point (0 < k < block_size slots of
        the child's block are reusable; the caller copies them into a fresh
        private block)."""
        best, best_k = None, 0
        for chunk, child in node.children.items():
            k = 0
            for a, b in zip(chunk, rest):
                if a != b:
                    break
                k += 1
            if k > best_k:
                best, best_k = child, k
        return best, best_k

    def insert(self, tokens, table: List[int]) -> List[Tuple[int, int]]:
        """Record ``tokens``' full blocks in the tree, backed by ``table``.

        New chunks take a tree reference on the slot's block. Chunks already
        cached under a *different* block dedupe: the return value lists
        ``(table_index, cached_block)`` swaps for the caller to apply
        (retarget its table at the cached block and drop its private copy —
        KV content at a position is a pure function of the token prefix, so
        the blocks are interchangeable)."""
        node, swaps = self.root, []
        for i, chunk in enumerate(self._chunks(tokens)):
            child = node.children.get(chunk)
            if child is None:
                child = _RadixNode(chunk, table[i], node)
                node.children[chunk] = child
                self.allocator.incref(table[i])
            elif child.block != table[i]:
                swaps.append((i, child.block))
                self.stats["dedup"] += 1
                _M_RADIX.inc(event="dedup")
            self._touch(child)
            node = child
        return swaps

    # ------------------------------------------------------------------
    def evictable(self) -> List[_RadixNode]:
        """Leaf nodes whose block only the tree still references — the only
        nodes eviction may free. An interior node's block stays pinned while
        descendants exist (a child's KV attends into it), and a block a live
        request's table references has allocator refcount > 1."""
        out, stack = [], list(self.root.children.values())
        while stack:
            n = stack.pop()
            if n.children:
                stack.extend(n.children.values())
            elif self.allocator.ref(n.block) == 1:
                out.append(n)
        return out

    def reclaimable_count(self, exclude=frozenset()) -> int:
        """Blocks iterated LRU eviction could eventually return to the
        allocator. A node is reclaimable iff only the tree references its
        block AND its whole subtree is reclaimable (eviction is leaf-first:
        a pinned descendant keeps every ancestor interior forever). Counting
        only current leaves would under-report chains and wedge admission.
        ``exclude``: blocks to treat as pinned — an admission probe passes
        the chain the candidate itself would adopt, since those blocks stop
        being evictable the moment it is admitted."""
        def walk(n):
            total, clean = 0, True
            for c in n.children.values():
                t, ok = walk(c)
                total += t
                clean = clean and ok
            if (clean and n.block not in exclude
                    and self.allocator.ref(n.block) == 1):
                return total + 1, True
            return total, False

        return sum(walk(c)[0] for c in self.root.children.values())

    def evict_lru(self) -> bool:
        """Drop the least-recently-hit evictable leaf, freeing its block
        back to the allocator. Returns False when nothing can be evicted."""
        cands = self.evictable()
        if not cands:
            return False
        victim = min(cands, key=lambda n: n.tick)
        del victim.parent.children[victim.chunk]
        self.allocator.decref(victim.block)
        self.stats["evictions"] += 1
        _M_RADIX.inc(event="eviction")
        return True

    @property
    def n_nodes(self) -> int:
        n, stack = 0, list(self.root.children.values())
        while stack:
            node = stack.pop()
            n += 1
            stack.extend(node.children.values())
        return n


class PagedKVCachePool:
    """Engine-facing paged pool; interface-compatible with ``KVCachePool``
    (slots/acquire/release/export/import and the same guard errors) plus the
    paged lifecycle hooks the decode-fill engine loop drives:

        begin_sequence   radix-match the prompt, adopt cached blocks (+COW)
        ensure_step_capacity   allocate this step's write block per slot
        sync             rebuild device block_tables/lengths when dirty
        note_step        mirror the in-graph ``lengths + 1`` on the host
        commit_prefix    insert a finished fill's full blocks into the tree
    """

    def __init__(self, model, max_batch: int, max_seq: int, bucket_of,
                 memory_plan: Optional[MemoryPlan] = None,
                 block_size: int = 16, n_blocks: Optional[int] = None):
        self.model = model
        self.max_batch = max_batch
        self.max_seq = max_seq
        self.bucket_of = bucket_of
        self.block_size = block_size
        self.blocks_per_seq = -(-max_seq // block_size)
        # default: every request can hold a full table, plus scratch
        self.n_blocks = n_blocks or max_batch * self.blocks_per_seq + 1
        self.allocator = BlockAllocator(self.n_blocks)
        self.prefix = RadixPrefixCache(self.allocator, block_size)
        self.cur_bucket = bucket_of(1)
        self.slots: List[Optional[int]] = [None] * self.cur_bucket
        self.tables: List[List[int]] = [[] for _ in range(self.cur_bucket)]
        self.host_len: List[int] = [0] * self.cur_bucket
        self.dirty = True
        self.cache = self._init_device_state(self.cur_bucket)
        if memory_plan is not None:
            # paged extents are bucket-invariant (pools carry no batch dim);
            # registered rank-relative like the slot pool so stamped LOADs
            # re-derive per-rank buffer sizes from a 1-rank capture (§4.3)
            specs = model.paged_cache_specs(max_batch, max_seq,
                                            self.n_blocks, block_size)
            for path, sd in jax.tree_util.tree_flatten_with_path(specs)[0]:
                memory_plan.alloc(
                    "kv_paged" + jax.tree_util.keystr(path),
                    int(np.prod(sd.shape)) * jnp.dtype(sd.dtype).itemsize,
                    scope="per_rank")

    # ------------------------------------------------------------------
    def _specs(self, bucket: int):
        return self.model.paged_cache_specs(bucket, self.max_seq,
                                            self.n_blocks, self.block_size)

    def _init_device_state(self, bucket: int):
        def mk(sd):
            z = jnp.zeros(sd.shape, sd.dtype)
            return jax.device_put(z, sd.sharding) if sd.sharding is not None else z
        return jax.tree.map(mk, self._specs(bucket))

    def _apply_shardings(self):
        if self.model.ctx.mesh is None:
            return
        specs = self._specs(self.cur_bucket)
        self.cache = jax.tree.map(
            lambda x, sd: (jax.device_put(x, sd.sharding)
                           if sd.sharding is not None else x),
            self.cache, specs)

    @property
    def n_active(self) -> int:
        return sum(s is not None for s in self.slots)

    # ------------------------------------------------------------------
    # slot lifecycle (KVCachePool-compatible)
    # ------------------------------------------------------------------
    def acquire(self, req_id: int) -> int:
        for i, s in enumerate(self.slots):
            if s is None:
                self.slots[i] = req_id
                self.tables[i] = []
                self.host_len[i] = 0
                self.dirty = True
                return i
        n = self.n_active + 1
        if n > self.max_batch:
            raise RuntimeError("pool exhausted")
        self._resize(self.bucket_of(n))
        return self.acquire(req_id)

    def release(self, slot: int):
        """Free a slot: drop its table's block references (radix-cached
        blocks survive on the tree's reference) and compact by moving the
        last active slot's *metadata* into the hole — no device KV moves."""
        if not (0 <= slot < len(self.slots)):
            raise ValueError(
                f"release of slot {slot}: out of range for bucket "
                f"{self.cur_bucket} (valid slots 0..{len(self.slots) - 1})")
        if self.slots[slot] is None:
            raise ValueError(
                f"release of slot {slot}: not an active slot "
                f"({'pool is empty' if self.n_active == 0 else 'double release'}"
                f") — compacting would corrupt a live row")
        for b in self.tables[slot]:
            self.allocator.decref(b)
        self.tables[slot] = []
        self.host_len[slot] = 0
        last = max(i for i, s in enumerate(self.slots) if s is not None)
        if last != slot:
            self.slots[slot] = self.slots[last]
            self.tables[slot] = self.tables[last]
            self.host_len[slot] = self.host_len[last]
            self.tables[last] = []
            self.host_len[last] = 0
        self.slots[last] = None
        self.dirty = True
        want = self.bucket_of(max(1, self.n_active))
        if want < self.cur_bucket and self.bucket_of(self.n_active + 1) < self.cur_bucket:
            self._resize(want)

    def moved_request(self, slot: int) -> Optional[int]:
        return self.slots[slot]

    def reset_slot(self, slot: int):
        """Drop a slot's blocks so a fresh fill can repopulate it."""
        for b in self.tables[slot]:
            self.allocator.decref(b)
        self.tables[slot] = []
        self.host_len[slot] = 0
        self.dirty = True

    def _resize(self, new_bucket: int):
        """Pad/slice the batch-dim device leaves (block_tables, lengths) and
        the host metadata; the k/v pools are bucket-invariant."""
        old = self.cur_bucket
        for name in ("block_tables", "lengths"):
            x = self.cache[name]
            if new_bucket > old:
                pad = [(0, new_bucket - old)] + [(0, 0)] * (x.ndim - 1)
                self.cache[name] = jnp.pad(x, pad)
            elif new_bucket < old:
                self.cache[name] = x[:new_bucket]
        self.slots = (self.slots + [None] * new_bucket)[:new_bucket]
        self.tables = (self.tables + [[] for _ in range(new_bucket)])[:new_bucket]
        self.host_len = (self.host_len + [0] * new_bucket)[:new_bucket]
        self.cur_bucket = new_bucket
        self._apply_shardings()

    # ------------------------------------------------------------------
    # block budget + prefix lifecycle
    # ------------------------------------------------------------------
    def _alloc_block(self) -> int:
        """Allocate a block, evicting LRU radix leaves when the free list is
        dry. Raises RuntimeError when nothing is evictable either."""
        while True:
            try:
                return self.allocator.alloc()
            except RuntimeError:
                if not self.prefix.evict_lru():
                    raise

    def match_blocks(self, tokens) -> int:
        """Full cached blocks a fill of ``tokens`` would reuse (peek, no
        references taken). Capped so the last token is always re-processed —
        the fill step that feeds it produces the first sampled token, and
        serving it from cache would change the sampling computation."""
        cap = max(0, len(tokens) - 1)
        return len(self.prefix.match(list(tokens)[:cap]))

    def blocks_needed(self, plen: int, max_new: int) -> int:
        """Table size a request needs end-of-life: prompt + generation
        budget, clamped to the engine's max_seq position capacity."""
        return -(-min(plen + max_new, self.max_seq) // self.block_size)

    def free_and_evictable(self) -> int:
        return self.allocator.n_free + self.prefix.reclaimable_count()

    def begin_sequence(self, slot: int, tokens) -> int:
        """Attach the radix-cached prefix of ``tokens`` to ``slot``: adopt
        matched full blocks by reference, then fork the best partially
        matching child copy-on-write (device-copy its first k positions into
        a fresh private block). Returns the number of cached positions —
        the fill loop starts there instead of at 0."""
        toks = list(tokens)
        bs = self.block_size
        cap = max(0, len(toks) - 1)  # always re-process the last token
        matched = self.prefix.match(toks[:cap])
        table = self.tables[slot]
        for node in matched:
            self.allocator.incref(node.block)
            table.append(node.block)
        cached = len(matched) * bs
        parent = matched[-1] if matched else self.prefix.root
        child, k = self.prefix.partial_child(parent, toks[cached:cap])
        if child is not None and k > 0:
            fresh = self._alloc_block()
            for leaf in ("k", "v"):
                src = self.cache[leaf][:, child.block, :k]
                self.cache[leaf] = self.cache[leaf].at[:, fresh, :k].set(src)
            self.prefix._touch(child)
            table.append(fresh)
            cached += k
            self._apply_shardings()
            _M_RADIX.inc(event="cow_fork")
        self.host_len[slot] = cached
        self.dirty = True
        self.prefix.stats["hits" if cached else "misses"] += 1
        _M_RADIX.inc(event="hit" if cached else "miss")
        return cached

    def ensure_step_capacity(self) -> Optional[int]:
        """Make every active slot's table cover its next write position
        (``host_len``). Returns None on success, or the first slot whose
        block allocation failed (the engine preempts it and retries)."""
        for i, s in enumerate(self.slots):
            if s is None:
                continue
            need_idx = self.host_len[i] // self.block_size
            while len(self.tables[i]) <= need_idx:
                try:
                    self.tables[i].append(self._alloc_block())
                except RuntimeError:
                    return i
                self.dirty = True
        return None

    def sync(self) -> int:
        """Rebuild the device block_tables/lengths from host metadata when
        dirty. Returns bytes moved host->device (0 on the clean fast path —
        steady-state decode advances lengths in-graph and never syncs)."""
        if not self.dirty:
            return 0
        B, MB = self.cur_bucket, self.blocks_per_seq
        bt = np.zeros((B, MB), np.int32)
        ln = np.zeros((B,), np.int32)
        for i, s in enumerate(self.slots):
            if s is None:
                continue
            t = self.tables[i]
            bt[i, :len(t)] = t
            ln[i] = self.host_len[i]
        self.cache["block_tables"] = jnp.asarray(bt)
        self.cache["lengths"] = jnp.asarray(ln)
        self._apply_shardings()
        self.dirty = False
        return bt.nbytes + ln.nbytes

    def attended_blocks(self) -> int:
        """Blocks the captured step's attention reads for the active rows:
        each row's table up to its write position, ``min(blocks_per_seq,
        host_len // block_size + 1)``."""
        bs, mb = self.block_size, self.blocks_per_seq
        return sum(min(mb, n // bs + 1)
                   for s, n in zip(self.slots, self.host_len) if s is not None)

    def note_step(self):
        """Mirror the captured step's ``lengths + 1`` on the host."""
        for i, s in enumerate(self.slots):
            if s is not None:
                self.host_len[i] += 1

    def commit_prefix(self, slot: int, tokens):
        """Insert a completed fill's full blocks into the radix tree. Chunks
        another request cached first dedupe: this slot's table retargets at
        the cached block and the private duplicate is freed."""
        swaps = self.prefix.insert(list(tokens), self.tables[slot])
        for idx, shared in swaps:
            self.allocator.incref(shared)
            self.allocator.decref(self.tables[slot][idx])
            self.tables[slot][idx] = shared
        if swaps:
            self.dirty = True

    # ------------------------------------------------------------------
    # uniform row accessors (layout-neutral seams for tests/tools)
    # ------------------------------------------------------------------
    def row_length(self, slot: int) -> int:
        return self.host_len[slot]

    def seed_length(self, slot: int, n: int):
        """Force a slot's length to ``n``, backing it with blocks."""
        self.reset_slot(slot)
        for _ in range(-(-n // self.block_size)):
            self.tables[slot].append(self._alloc_block())
        self.host_len[slot] = n
        self.dirty = True

    # ------------------------------------------------------------------
    # cross-pool row migration (live reshard, serving/fleet.py)
    # ------------------------------------------------------------------
    def export_rows(self, slots: List[int]) -> RowBundle:
        """Gather the given slots' blocks into dense per-request rows in the
        slot-layout interchange format ([L,n,S,Hkv,Dh] k rows, [n] lengths,
        v rows) so either pool layout can import them."""
        check_export_slots(slots, self.slots)
        MB, bs = self.blocks_per_seq, self.block_size
        tbl = np.zeros((len(slots), MB), np.int32)
        lens = np.zeros((len(slots),), np.int32)
        for j, s in enumerate(slots):
            t = self.tables[s]
            tbl[j, :len(t)] = t
            lens[j] = self.host_len[s]
        idx = jnp.asarray(tbl)

        def dense(pool):  # [L, NB, bs, Hkv, Dh] -> [L, n, S, Hkv, Dh]
            g = pool[:, idx]  # [L, n, MB, bs, Hkv, Dh]
            L, n = g.shape[0], g.shape[1]
            g = g.reshape((L, n, MB * bs) + g.shape[4:])
            return g[:, :, :self.max_seq]

        rows = [dense(self.cache["k"]), jnp.asarray(lens),
                dense(self.cache["v"])]
        return RowBundle(rows, [1, 0, 1], len(slots))

    def import_rows(self, bundle: RowBundle, req_ids: List[int]) -> List[int]:
        """Adopt dense interchange rows: per request, allocate blocks for
        its length, reshard the row onto this pool's mesh, and scatter it
        block-by-block into the pools. Imported rows are private (no radix
        attachment — the migrated request may be mid-stream)."""
        check_import(bundle, req_ids, self.n_active, self.max_batch)
        k_rows, lens, v_rows = bundle.rows
        lens = np.asarray(lens)
        bs = self.block_size
        specs = self._specs(self.cur_bucket)
        mesh = self.model.ctx.mesh
        slots = []
        for j, rid in enumerate(req_ids):
            slot = self.acquire(rid)
            slots.append(slot)
            ln = int(lens[j])
            nb = -(-ln // bs)
            blocks = [self._alloc_block() for _ in range(nb)]
            self.tables[slot] = blocks
            self.host_len[slot] = ln
            if nb == 0:
                continue
            bidx = jnp.asarray(blocks, jnp.int32)
            for name, rows in (("k", k_rows), ("v", v_rows)):
                row = jax.lax.slice_in_dim(rows, j, j + 1, axis=1)[:, 0]
                row = reshard_rows(row, specs[name], mesh)  # [L, S, Hkv, Dh]
                S = row.shape[1]
                if S < nb * bs:
                    pad = [(0, 0), (0, nb * bs - S), (0, 0), (0, 0)]
                    row = jnp.pad(row, pad)
                row = row[:, :nb * bs].reshape(
                    (row.shape[0], nb, bs) + row.shape[2:])
                pool = self.cache[name]
                self.cache[name] = pool.at[:, bidx].set(row.astype(pool.dtype))
        self.dirty = True
        self._apply_shardings()
        return slots
