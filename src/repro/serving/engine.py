"""Serving engine: bucketed decode + continuous batching + Foundry cold start.

Four cold-start paths (the paper's Figure 7/8 comparison, plus §4.3):
  * "vanilla"          — trace+lower+compile every capture bucket up front
                         (vLLM with CUDA graphs: full warmup + stream
                         capture);
  * "foundry"          — LOAD an archive captured on THIS topology: templates
                         restored with zero compile, all buckets pad-served
                         immediately, exact buckets hot-swap in the
                         background;
  * "foundry-stamped"  — LOAD an archive captured on a DIFFERENT but
                         shape-compatible topology (1-rank offline capture,
                         or a TP<->EP re-arrangement): the shared templates
                         are reused byte-identically and only rank-dependent
                         communication state is stamped per deployment rank
                         (core/rank_stamp.py). Still zero compile; reported
                         automatically when the LOAD takes the stamped path;
  * "eager"            — no capture; each bucket compiles lazily on first use
                         (vLLM without CUDA graphs: fast start, degraded
                         serving).

The decode hot loop is identical in all of them — only program provenance
differs — so TPOT preservation (Figure 9) is measured on the same code path.

Decode hot loop (docs/architecture.md "decode hot path"): the captured step
is the fused ``decode_step(params, cache, tokens) -> (cache', token_ids)``
with the KV cache donated (in-place update, the cache never leaves the
device) and greedy sampling folded into the graph, so steady-state decode
moves only O(B) int32 token ids across the host boundary per token — never
the O(B x padded_vocab) logits matrix. Sampled ids feed straight back as the
next step's input from the device side; the host rebuilds the token vector
(O(B) ints, one transfer) only when scheduling events invalidate it
(prefill, completion/compaction, pool resize). ``decode_loop="host"``
preserves the pre-fusion loop — captured programs return full logits and the
host argmaxes in numpy — as the measurable baseline for benchmarks/fig9 and
the token-identity regression tests.
"""
from __future__ import annotations

import bisect
import logging
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import metrics as obs_metrics
from repro.obs.trace import span

from repro.core import (Archive, CaptureSpec, MemoryPlan, ProgramSet,
                        default_bucket_ladder, foundry_load, foundry_save,
                        group_buckets, pad_batch_arg, topology_key)
from repro.core.templates import TopologyGroup
from repro.launch.mesh import ShardCtx
from repro.models.model import Model
from repro.serving.blockpool import PagedKVCachePool
from repro.serving.faults import fault_point
from repro.serving.kvcache import KVCachePool, RowBundle
from repro.serving.scheduler import ReqState, Request, Scheduler

log = logging.getLogger("repro.serving.engine")

# docs/architecture.md §13 has the full metric catalog
_M_TPOT = obs_metrics.histogram(
    "serving_tpot_seconds",
    "Per-decode-step wall time (the steady-state TPOT proxy).",
    buckets=(1e-4, 2.5e-4, 5e-4, 1e-3, 2.5e-3, 5e-3, 1e-2, 2.5e-2, 5e-2,
             0.1, 0.25, 0.5, 1.0))
_M_DECODE_STEPS = obs_metrics.counter(
    "engine_decode_steps_total", "Decode steps that served >= 1 request.")
_M_COLD_STARTS = obs_metrics.counter(
    "engine_cold_starts_total", "Engine cold starts by mode.", ("mode",))


#: The supported-convention matrix: every ``CaptureSpec.tags`` key this
#: engine can serve, with its legal value domain (a tuple enumerates the
#: values; ``"int+"`` means a positive int). Tags version the captured
#: calling convention — the archived programs bake in the decode loop and
#: KV layout, so a key or value outside this matrix means the archive
#: speaks a convention this engine does not, and serving it anyway risks
#: silent token corruption rather than a graceful fallback.
#: ``repro.analysis.checker`` validates archives against this matrix
#: statically (the ``tags-schema`` pass).
TAG_CONVENTIONS: Dict[str, Any] = {
    "decode_loop": ("host", "device"),
    "fused_sampling": (False, True),
    "kv_layout": ("slot", "paged"),
    "kv_block_size": "int+",
    "kv_blocks": "int+",
}


def validate_tags(tags: Dict[str, Any]) -> List[str]:
    """Problems (empty = clean) with a tag dict vs ``TAG_CONVENTIONS``."""
    problems = []
    for k, v in tags.items():
        domain = TAG_CONVENTIONS.get(k)
        if domain is None:
            problems.append(f"unknown tag key {k!r} (engine speaks: "
                            f"{sorted(TAG_CONVENTIONS)})")
        elif domain == "int+":
            if not isinstance(v, int) or isinstance(v, bool) or v < 1:
                problems.append(f"tag {k}={v!r} must be a positive int")
        elif v not in domain or isinstance(v, bool) != any(
                isinstance(d, bool) for d in domain):
            problems.append(f"tag {k}={v!r} not in supported domain {domain}")
    return problems


@dataclass
class ColdStartReport:
    """How this engine became servable and what it cost.

    Fields:
        mode              cold-start path actually taken: "vanilla" |
                          "foundry" | "foundry-stamped" | "eager" (module
                          docstring). "foundry-stamped" means the archive was
                          captured on a different, shape-compatible topology
                          and was rank-stamped rather than recompiled.
        phases            phase name -> seconds; for foundry modes these are
                          the LoadReport phases (core/restore.py).
        n_buckets         capture buckets this engine dispatches over.
        n_templates       topology-group templates backing those buckets.
        rank_stamped      (template x rank) stampings performed by the LOAD;
                          0 for non-stamped modes.
        fallback_compiles critical-path compiles the LOAD could not avoid;
                          0 on exact and shape-compatible stamped loads.
    """
    mode: str
    phases: Dict[str, float] = field(default_factory=dict)
    n_buckets: int = 0
    n_templates: int = 0
    rank_stamped: int = 0
    fallback_compiles: int = 0

    @property
    def total_s(self) -> float:
        return sum(self.phases.values())


class ServingEngine:
    def __init__(self, model: Model, *, max_batch: int = 16,
                 max_seq: int = 128, bucket_mode: str = "all",
                 eos_token: Optional[int] = None,
                 memory_plan: Optional[MemoryPlan] = None,
                 decode_loop: str = "device",
                 kv_layout: str = "auto", kv_block_size: int = 16,
                 kv_blocks: Optional[int] = None):
        if decode_loop not in ("device", "host"):
            raise ValueError(f"decode_loop must be 'device' or 'host', "
                             f"got {decode_loop!r}")
        if kv_layout not in ("auto", "paged", "slot"):
            raise ValueError(f"kv_layout must be 'auto', 'paged' or 'slot', "
                             f"got {kv_layout!r}")
        self.model = model
        self.cfg = model.cfg
        self.ctx = model.ctx
        self.max_batch = max_batch
        self.max_seq = max_seq
        self.buckets = default_bucket_ladder(max_batch, bucket_mode)
        self.eos_token = eos_token
        self.memory_plan = memory_plan or MemoryPlan()
        self.params = None
        self.programs: Optional[ProgramSet] = None
        self.scheduler = Scheduler()
        self.pool = None  # KVCachePool or PagedKVCachePool per kv_layout
        self._prefill_cache: Dict[int, Any] = {}
        self._eager_mode = False
        self.decode_steps = 0
        self.decode_loop = decode_loop
        # KV layout: block-table paged pool with radix prefix cache for the
        # attention families; slot compaction for SSM/hybrid/seqpar layouts
        # (their decode state has no block structure to page).
        self.kv_layout = (self._auto_kv_layout() if kv_layout == "auto"
                          else kv_layout)
        if self.kv_layout == "paged" and self._auto_kv_layout() == "slot":
            raise ValueError(
                f"kv_layout='paged' unsupported for family "
                f"'{self.cfg.family}' / seqpar sharding; use 'slot'")
        self.kv_block_size = kv_block_size
        self.kv_blocks = (kv_blocks or
                          max_batch * (-(-max_seq // kv_block_size)) + 1)
        # paged decode-fill bookkeeping: req_id -> prompt+prefix length the
        # fill must reach before sampled ids become recordable
        self._fill_target: Dict[int, int] = {}
        self.prefill_stats = {"prefilled_tokens": 0, "cached_tokens": 0,
                              "prefix_hits": 0, "prefix_misses": 0}
        # device-resident token state (decode_loop="device"): the sampled ids
        # of step k ARE step k+1's input, device-to-device; dirty marks the
        # scheduling events that force an O(B) host rebuild.
        self._tokens_dev: Optional[Any] = None
        self._tokens_bucket: int = 0
        self._tokens_dirty: bool = True
        # host<->device traffic of the decode loop, in bytes (the fig9
        # transfer accounting; tests cross-check it with patched transports)
        self.transfer_stats = {"h2d_bytes": 0, "d2h_bytes": 0,
                               "token_rebuilds": 0}
        # fault-injection identity (serving/faults.py): the owning fleet
        # stamps this with the replica id so chaos plans can target one
        # replica's decode steps / KV imports; None outside a fleet
        self.fault_tag: Optional[str] = None

    def _auto_kv_layout(self) -> str:
        if (self.cfg.family in ("dense", "vlm", "moe")
                and not self.model._seqpar_axes()):
            return "paged"
        return "slot"

    # ------------------------------------------------------------------
    def _decode_fn(self, loop: Optional[str] = None):
        """The captured step for this engine's decode loop.

        device: fused ``(params, cache, tokens) -> (cache', token_ids)`` —
                greedy sampling over the real (unpadded) vocab happens inside
                the graph; only B int32 ids ever cross to the host.
        host:   pre-fusion ``(params, cache, tokens) -> (cache', logits)``.
        """
        m = self.model
        vocab = self.cfg.vocab_size
        step_fn = (m.decode_step_paged if self.kv_layout == "paged"
                   else m.decode_step)
        if (loop or self.decode_loop) == "device":
            def decode_step(params, cache, tokens):
                new_cache, logits = step_fn(params, cache, tokens)
                live = logits[:, :vocab]
                # first-max argmax as two vectorizable reduces (max, then min
                # over the tied-index iota). XLA:CPU lowers jnp.argmax to a
                # scalar-looped variadic reduce ~3.5x slower than the logits
                # readback it is meant to replace; tie-breaking (lowest
                # index) matches np.argmax, which the host loop uses — the
                # token-identity tests pin that equivalence.
                mx = jnp.max(live, axis=-1, keepdims=True)
                iota = jax.lax.broadcasted_iota(jnp.int32, live.shape, 1)
                ids = jnp.min(jnp.where(live == mx, iota, jnp.int32(vocab)),
                              axis=-1)
                return new_cache, ids
        else:
            def decode_step(params, cache, tokens):
                return step_fn(params, cache, tokens)
        return decode_step

    def _decode_args(self, bucket: int):
        m, ctx = self.model, self.ctx
        tok_sh = (ctx.sharding(("batch",), (bucket,))
                  if ctx.mesh is not None else None)
        if self.kv_layout == "paged":
            cache = m.paged_cache_specs(bucket, self.max_seq,
                                        self.kv_blocks, self.kv_block_size)
        else:
            cache = m.cache_specs(bucket, self.max_seq)
        return (m.param_specs(), cache,
                jax.ShapeDtypeStruct((bucket,), jnp.int32, sharding=tok_sh))

    def capture_spec(self) -> CaptureSpec:
        # kv_* tags version the captured calling convention: a paged archive
        # must be served through the paged pool (and vice versa); archives
        # without the tag predate paging and load via the slot path.
        return CaptureSpec("decode", self._decode_fn(), self._decode_args,
                           self.buckets, donate_argnums=(1,),
                           tags={"decode_loop": self.decode_loop,
                                 "fused_sampling":
                                     self.decode_loop == "device",
                                 "kv_layout": self.kv_layout,
                                 "kv_block_size": self.kv_block_size,
                                 "kv_blocks": self.kv_blocks})

    # ---- weights -------------------------------------------------------
    def load_weights(self, params=None, rng=None):
        """Weight loading is assumed solved (RDMA, 1-2 s; paper §2); here we
        either take provided params or init. Registers with the memory plan."""
        with span("engine.load_weights", cat="engine") as sp:
            self.params = params if params is not None else self.model.init(
                rng if rng is not None else jax.random.PRNGKey(0))
            for path, leaf in jax.tree_util.tree_flatten_with_path(
                    self.params)[0]:
                self.memory_plan.alloc(
                    "params" + jax.tree_util.keystr(path),
                    leaf.size * leaf.dtype.itemsize)
        return sp.seconds

    def _init_pool(self):
        with span("engine.init_pool", cat="engine"):
            if self.kv_layout == "paged":
                self.pool = PagedKVCachePool(
                    self.model, self.max_batch, self.max_seq,
                    bucket_of=self._bucket_of, memory_plan=self.memory_plan,
                    block_size=self.kv_block_size, n_blocks=self.kv_blocks)
            else:
                self.pool = KVCachePool(
                    self.model, self.max_batch, self.max_seq,
                    bucket_of=self._bucket_of, memory_plan=self.memory_plan)
        self._fill_target.clear()
        self._tokens_dev = None
        self._tokens_dirty = True

    def _bucket_of(self, n: int) -> int:
        i = bisect.bisect_left(self.buckets, n)
        return self.buckets[min(i, len(self.buckets) - 1)]

    # ---- cold start paths ------------------------------------------------
    def cold_start_vanilla(self, verbose: bool = False) -> ColdStartReport:
        """Full capture: per-bucket trace+lower+compile (stream capture)."""
        rep = ColdStartReport("vanilla", n_buckets=len(self.buckets))
        step = self._decode_fn()
        keys = {}
        t0 = time.perf_counter()
        extra = {"mesh": str(None if self.ctx.mesh is None
                             else self.ctx.mesh.shape)}
        for b in self.buckets:
            keys[b] = topology_key(step, *self._decode_args(b), extra=extra)
        rep.phases["trace_key_s"] = time.perf_counter() - t0
        groups = group_buckets(keys)
        rep.n_templates = len(groups)
        ps = ProgramSet(groups)
        t0 = time.perf_counter()
        jitted = jax.jit(step, donate_argnums=(1,))
        for b in self.buckets:
            exe = jitted.lower(*self._decode_args(b)).compile()
            ps.set_exact(b, exe)
            g = next(g for g in groups if b in g.buckets)
            if b == g.template_bucket:
                ps.set_template(g.key, exe)
        rep.phases["capture_compile_s"] = time.perf_counter() - t0
        self.programs = ps
        self._init_pool()
        _M_COLD_STARTS.inc(mode="vanilla")
        if verbose:
            log.info("[cold-start vanilla] %.2fs (%d buckets)",
                     rep.total_s, len(self.buckets))
        return rep

    def cold_start_foundry(self, archive: Archive,
                           background_exact: bool = True,
                           allow_stamping: bool = True,
                           warm: bool = False,
                           strict: bool = True,
                           verbose: bool = False) -> ColdStartReport:
        """LOAD ``archive`` and become servable. The report's mode is
        "foundry" when the archive was captured on this engine's topology
        and "foundry-stamped" when LOAD rank-stamped a shape-compatible
        capture onto it (``allow_stamping=False`` forces mesh mismatches
        down the compile-from-StableHLO fallback instead).

        The engine adopts the archive's decode loop: the archived programs
        either fuse sampling (device loop) or return logits (host loop), and
        the serving loop must match what SAVE captured. Archives without the
        tag (pre-fusion) are served with the host loop.

        ``warm=True`` marks this a LOAD into an already-warm serving process
        (live reshard: the old topology's replicas are still serving when
        the new ones come up): the memory-plan preallocation is skipped —
        the extent is already mapped in this process — and templates
        deserialized by an earlier LOAD of the same archive are reused."""
        spec_m = archive.manifest.get("specs", {}).get("decode", {})
        tags = spec_m.get("tags") or {}
        if strict:
            # validate BEFORE adopting: a tag outside the convention matrix
            # would otherwise mutate engine state (loop/pool selection) into
            # a convention SAVE never captured — token corruption, not a
            # fallback. foundry_load(strict=True) re-checks the full
            # manifest; this guards the two fields adopted pre-LOAD.
            problems = validate_tags(tags)
            if problems:
                raise ValueError(
                    f"archive capture tags fail the engine convention "
                    f"matrix: {'; '.join(problems)} (run `python -m "
                    f"repro.analysis.check` on the archive)")
        archived_loop = tags.get("decode_loop", "host")
        if archived_loop != self.decode_loop and verbose:
            log.info("[LOAD] archive captured for decode_loop='%s'; "
                     "adopting it", archived_loop)
        self.decode_loop = archived_loop
        # adopt the archived KV calling convention: the restored programs
        # bake in the cache layout, so the pool must match it. Untagged
        # (pre-paged) archives default to the slot path.
        self.kv_layout = tags.get("kv_layout", "slot")
        self.kv_block_size = tags.get("kv_block_size", self.kv_block_size)
        self.kv_blocks = tags.get("kv_blocks", self.kv_blocks)
        with span("engine.cold_start", cat="engine", mode="foundry"):
            progs, load_rep, plan = foundry_load(
                archive, self.ctx.mesh,
                background_exact=background_exact,
                allow_stamping=allow_stamping, warm=warm, strict=strict,
                verbose=verbose)
        mode = ("foundry-stamped" if load_rep.restore_path == "stamped"
                else "foundry")
        _M_COLD_STARTS.inc(mode=mode)
        rep = ColdStartReport(mode, n_buckets=len(self.buckets),
                              rank_stamped=load_rep.rank_stamped,
                              fallback_compiles=load_rep.fallback_compiles)
        self.programs = progs["decode"]
        rep.phases.update(load_rep.phases)
        rep.n_templates = load_rep.n_templates
        self._load_report = load_rep
        self._init_pool()
        return rep

    def cold_start_eager(self, verbose: bool = False) -> ColdStartReport:
        """No capture: programs compile lazily on first use."""
        rep = ColdStartReport("eager", n_buckets=len(self.buckets))
        step = self._decode_fn()
        keys = {b: f"eager-{b}" for b in self.buckets}  # no grouping
        ps = ProgramSet(group_buckets(keys))
        self.programs = ps
        self._eager_mode = True
        self._eager_jit = jax.jit(step, donate_argnums=(1,))
        rep.phases["noop_s"] = 0.0
        self._init_pool()
        return rep

    def save_archive(self, path: Optional[str] = None, **kw):
        """Offline SAVE for this engine's capture set."""
        if self.pool is None:
            # register the KV pool's (rank-relative) extents in the memory
            # plan so the archive's RankDelta section records them (§4.3)
            self._init_pool()
        ar, rep = foundry_save([self.capture_spec()], self.ctx.mesh,
                               memory_plan=self.memory_plan,
                               meta={"arch": self.cfg.name,
                                     "max_seq": self.max_seq,
                                     "decode_loop": self.decode_loop}, **kw)
        if path:
            ar.save(path)
        return ar, rep

    # ---- serving ---------------------------------------------------------
    def submit(self, prompt: Sequence[int], max_new_tokens: int) -> Request:
        return self.scheduler.submit(list(prompt), max_new_tokens)

    def _prefill(self, req: Request):
        """Prefill one request into its slot (pads prompt to pow2 bucket)."""
        m = self.model
        plen = len(req.prompt) + len(req.generated)
        toks = list(req.prompt) + list(req.generated)
        pb = 1 << (plen - 1).bit_length()
        pb = min(max(pb, 8), self.max_seq)
        padded = np.zeros((1, pb), np.int32)
        padded[0, :plen] = toks
        key = pb
        if key not in self._prefill_cache:
            self._prefill_cache[key] = jax.jit(
                lambda p, b: m.prefill(p, b, cache_len=self.max_seq))
        logits, cache1 = self._prefill_cache[key](
            self.params, {"tokens": jnp.asarray(padded)})
        # fix lengths: prefill padded to pb, true length is plen
        cache1 = {**cache1, "lengths": jnp.asarray([plen], jnp.int32)}
        slot = self.pool.acquire(req.req_id)
        req.slot = slot
        self.pool.write_prefill(slot, cache1)
        # the prefill handoff writes device-to-device into the persistent
        # pool rows; only the token vector needs a host rebuild next step
        self._tokens_dirty = True
        # note: prefill over right-padded prompts is exact for causal attn
        # (pad positions sit after plen and are never attended by pos<plen),
        # and for SSM archs we re-run prefill at exact length buckets.
        return slot

    def _begin_fill(self, req: Request) -> int:
        """Paged admission: attach the radix-cached prefix of the request's
        tokens to a fresh slot and schedule the rest for decode-fill — the
        uncached positions run token-by-token through the *captured* decode
        graph (no separate prefill program, no extra compile). Sampled ids
        become recordable once the fill reaches the last prompt token; a
        prefix hit skips straight there, which is the TTFT win."""
        toks = list(req.prompt) + list(req.generated)
        slot = self.pool.acquire(req.req_id)
        req.slot = slot
        cached = self.pool.begin_sequence(slot, toks)
        self._fill_target[req.req_id] = len(toks)
        self.prefill_stats["prefilled_tokens"] += len(toks) - cached
        self.prefill_stats["cached_tokens"] += cached
        self.prefill_stats["prefix_hits" if cached else
                           "prefix_misses"] += 1
        self._tokens_dirty = True
        return slot

    def _put_tokens(self, t):
        t = jnp.asarray(t)
        if self.ctx.mesh is not None:
            sh = self.ctx.sharding(("batch",), t.shape)
            if sh is not None:
                t = jax.device_put(t, sh)
        return t

    def _rebuild_tokens(self, exec_bucket: int, by_slot):
        """O(B) host rebuild of the token vector (the only host->device
        transfer the decode loop ever makes, and only on dirty steps)."""
        arr = np.zeros((exec_bucket,), np.int32)
        if self.kv_layout == "paged":
            # unified decode-fill rule: every step feeds the token at the
            # row's next write position. Steady state this is the last
            # sampled token (host_len == len(toks) - 1); during a fill it
            # walks the uncached prompt suffix.
            for slot, req in by_slot.items():
                toks = req.prompt + req.generated
                arr[slot] = toks[min(self.pool.host_len[slot],
                                     len(toks) - 1)]
        else:
            for slot, req in by_slot.items():
                arr[slot] = (req.generated or req.prompt)[-1]
        self.transfer_stats["h2d_bytes"] += arr.nbytes
        self.transfer_stats["token_rebuilds"] += 1
        return self._put_tokens(arr)

    def _device_tokens(self, exec_bucket: int, by_slot):
        """Token input for the fused step: previous step's on-device sampled
        ids when clean; bucket growth pads the device view in place (no host
        round-trip); anything dirty rebuilds from host state."""
        t = self._tokens_dev
        if not self._tokens_dirty and t is not None:
            if self._tokens_bucket == exec_bucket:
                return t
            if self._tokens_bucket < exec_bucket:
                # pre-padded device view for the bucket transition
                t = pad_batch_arg(t, self._tokens_bucket, exec_bucket)
            else:
                t = t[:exec_bucket]
            return self._put_tokens(t)
        return self._rebuild_tokens(exec_bucket, by_slot)

    def _program(self, bucket: int):
        """The bucket the step executes at and its program."""
        if self._eager_mode:
            return bucket, self._eager_jit
        exec_bucket, exe, _path = self.programs.lookup(bucket)
        return exec_bucket, exe

    def _read_ids(self, out) -> np.ndarray:
        """Block on the step's output and bring the sampled ids to the host:
        the fused step's O(B) ids (the device loop's only device->host
        readback), or the pre-fusion loop's full padded-vocab logits,
        argmaxed in numpy (kept as the measurable baseline for fig9 and the
        identity tests)."""
        if self.decode_loop == "device":
            ids = np.asarray(out)
            self.transfer_stats["d2h_bytes"] += ids.nbytes
            return ids
        logits_np = np.asarray(out[:, :self.cfg.vocab_size])
        self.transfer_stats["d2h_bytes"] += logits_np.nbytes
        return logits_np.argmax(axis=-1)

    def _admit(self, free: int):
        """Pull admissions from the scheduler and give each a slot.

        Paged admission accounting charges a request only for its *uncached*
        KV blocks: the radix-matched prefix is served from shared cached
        blocks, so a request whose full prompt would blow the block budget
        is still admitted when the cached suffix fits (ISSUE 6 satellite).
        A genuine shortfall defers (queue front, no retry penalty); only a
        request that could never fit — uncached need beyond every usable
        block — fails terminally."""
        sched, pool = self.scheduler, self.pool
        admitted = sched.admissions(free)
        to_defer: List[Request] = []
        for req in admitted:
            plen = len(req.prompt) + len(req.generated)
            if plen >= self.max_seq:
                # position capacity, not block budget: even a fully cached
                # prompt occupies plen positions + one generated token
                sched.reject(
                    req, f"prompt+prefix length {plen} exceeds engine "
                         f"capacity (max_seq={self.max_seq} incl. one "
                         f"generated token)")
                continue
            if to_defer:
                to_defer.append(req)  # keep FIFO order behind the blocker
                continue
            if self.kv_layout != "paged":
                self._prefill(req)
                continue
            # end-of-life table size; generated-prefix retries fold into
            # max_new (finished counts generated against the same budget)
            total = pool.blocks_needed(len(req.prompt), req.max_new_tokens)
            if total > pool.allocator.n_usable:
                sched.reject(
                    req, f"request needs {total} KV blocks end-to-end, "
                         f"beyond pool capacity ({pool.allocator.n_usable} "
                         f"usable blocks of {pool.block_size} tokens)")
                continue
            toks = list(req.prompt) + list(req.generated)
            matched = pool.prefix.match(toks[:max(0, len(toks) - 1)])
            need = total - len(matched)
            headroom = (pool.allocator.n_free
                        + pool.prefix.reclaimable_count(
                            frozenset(n.block for n in matched))
                        - self._outstanding_blocks())
            if need > headroom:
                to_defer.append(req)
                continue
            self._begin_fill(req)
        for req in reversed(to_defer):
            sched.defer(req)

    def _outstanding_blocks(self) -> int:
        """Blocks already-admitted running requests will still allocate on
        their way to their generation budget — reserved, not yet drawn from
        the free list. Admission headroom subtracts this so two admissions
        cannot jointly over-commit the pool and thrash via preemption."""
        pool, out = self.pool, 0
        for r in self.scheduler.running.values():
            if r.slot is None:
                continue
            total = pool.blocks_needed(len(r.prompt), r.max_new_tokens)
            out += max(0, total - len(pool.tables[r.slot]))
        return out

    def _preempt_until_feasible(self):
        """Paged mid-decode block exhaustion: running requests' tables grow
        every block_size steps, and the admission budget can be overtaken by
        later admissions' growth. Preempt (defer + release) the slot that
        failed to get its write block until the rest of the batch fits."""
        sched, pool = self.scheduler, self.pool
        while True:
            stuck = pool.ensure_step_capacity()
            if stuck is None:
                return
            victim = sched.running[pool.slots[stuck]]
            self._fill_target.pop(victim.req_id, None)
            sched.defer(victim)
            pool.release(stuck)
            moved_id = (pool.slots[stuck]
                        if stuck < len(pool.slots) else None)
            if moved_id is not None and moved_id in sched.running:
                sched.running[moved_id].slot = stuck
            self._tokens_dirty = True

    def step(self) -> int:
        """One engine iteration: admit + decode one token for all running.
        Returns number of active requests served.

        A step that has work is one ``engine.step`` span (args ``rows``,
        ``bucket``, ``fill_rows``) over six children in the order they run:
        ``engine.admit``, ``engine.pool_sync``, ``engine.tokens``,
        ``engine.dispatch`` (an asynchronous enqueue; on the paged pool
        with args ``kv_live_blocks``, the blocks attention reads for the
        active rows, and ``kv_table_blocks``, bucket x blocks per row),
        ``engine.readback`` (the wait for the device) and
        ``engine.finish``. The span's ``seconds`` feed
        ``serving_tpot_seconds`` and
        ``engine_decode_steps_total``. A tick with nothing queued or
        running records no span; one that admits nothing from its queue
        records an ``engine.step`` with ``rows=0`` and only its
        ``engine.admit``, which feeds neither metric."""
        # injected BEFORE any scheduler/pool mutation: a crash here leaves
        # the engine coherent, so the fleet's salvage path (export_inflight)
        # can migrate the in-flight KV rows instead of re-prefilling
        fault_point("engine.decode_step", tag=self.fault_tag)
        if not self.scheduler.queue and self.pool.n_active == 0:
            return 0
        with span("engine.step", cat="engine") as sp:
            n = self._step(sp)
        if n:  # ticks that admitted nothing would skew TPOT
            _M_TPOT.observe(sp.seconds)
            _M_DECODE_STEPS.inc()
        return n

    def _step(self, sp: span) -> int:
        sched, pool = self.scheduler, self.pool
        paged = self.kv_layout == "paged"
        with span("engine.admit", cat="engine"):
            self._admit(self.max_batch - pool.n_active)
            if paged:
                self._preempt_until_feasible()
        n = pool.n_active
        if n == 0:
            sp.set(rows=0)
            return 0
        bucket = pool.cur_bucket
        with span("engine.pool_sync", cat="engine"):
            if paged:
                # rebuild the (small) device block tables if scheduling
                # dirtied them; steady-state decode takes the free fast path
                self.transfer_stats["h2d_bytes"] += pool.sync()
                if self._fill_target:
                    # fill steps feed prompt tokens, not the sampled ids
                    self._tokens_dirty = True
            # the lookup names the bucket the pool must be resized to
            exec_bucket, exe = self._program(bucket)
            if exec_bucket != bucket:
                pool._resize(exec_bucket)
        sp.set(rows=n, bucket=exec_bucket, fill_rows=len(self._fill_target))
        by_slot = {r.slot: r for r in sched.running.values()}
        if paged:
            # recordability is decided on PRE-step lengths: the step feeding
            # the last prompt token produces the first real sample
            eligible = {
                slot: (self._fill_target.get(req.req_id) is None
                       or pool.host_len[slot]
                       >= self._fill_target[req.req_id] - 1)
                for slot, req in by_slot.items()}
        with span("engine.tokens", cat="engine"):
            if self.decode_loop == "device":
                toks = self._device_tokens(exec_bucket, by_slot)
            else:
                # the pre-fusion loop re-packs the tokens every step
                toks = self._rebuild_tokens(exec_bucket, by_slot)
        with span("engine.dispatch", cat="engine") as dsp:
            if paged:
                # the share of the whole table that attention reads
                dsp.set(kv_live_blocks=pool.attended_blocks(),
                        kv_table_blocks=exec_bucket * pool.blocks_per_seq)
            # donated cache; the fused program samples on the device
            pool.cache, out = exe(self.params, pool.cache, toks)
        if self.decode_loop == "device":
            self._tokens_dev = out
            self._tokens_bucket = exec_bucket
            self._tokens_dirty = False
        with span("engine.readback", cat="engine"):
            next_tokens = self._read_ids(out)
        self.decode_steps += 1
        with span("engine.finish", cat="engine"):
            if paged:
                pool.note_step()  # host mirror of the in-graph lengths + 1
                for slot, req in by_slot.items():
                    tgt = self._fill_target.get(req.req_id)
                    if tgt is not None and pool.host_len[slot] >= tgt:
                        # fill finished: publish the prompt's full blocks to
                        # the radix tree for later requests to hit
                        pool.commit_prefix(slot, req.prompt)
                        del self._fill_target[req.req_id]
                pairs = [(req, int(next_tokens[slot]))
                         for slot, req in by_slot.items() if eligible[slot]]
            else:
                pairs = [(req, int(next_tokens[slot]))
                         for slot, req in by_slot.items()]
            self._finish_step(pairs)
        return n

    def _finish_step(self, pairs):
        """Batched host readback bookkeeping: record the sampled ids,
        complete/compact finished requests, invalidate device token state
        when slots moved."""
        sched = self.scheduler
        finished = sched.record_step(
            pairs, eos_token=self.eos_token, max_total_len=self.max_seq - 1)
        for req in finished:
            sched.complete(req)
            self.pool.release(req.slot)
            # compaction may have moved another request into this slot
            moved_id = self.pool.slots[req.slot] if req.slot < len(self.pool.slots) else None
            if moved_id is not None and moved_id in sched.running:
                sched.running[moved_id].slot = req.slot
            req.slot = None
        if finished:
            # release/compaction/shrink reshuffled rows under the sampled ids
            self._tokens_dirty = True

    def run_until_drained(self, max_steps: int = 10000) -> int:
        steps = 0
        while self.scheduler.pending and steps < max_steps:
            self.step()
            steps += 1
        return steps

    # ---- live migration (Fleet.reshard cutover, prefill->decode handoff) ---
    def export_requests(self, reqs: List[Request], *,
                        release: bool = False) -> RowBundle:
        """Detach specific RUNNING requests with their KV rows for migration
        to another engine. The requests leave WAITING with no slot — in
        flight between engines; fill progress travels as the exported row's
        length (the adopter re-derives its own fill target from it).

        ``release=False`` leaves the pool slots occupied — for callers that
        strip a replica about to be retired (reshard cutover, salvage), where
        releasing would only churn the doomed pool. ``release=True`` is the
        per-request handoff path (docs/architecture.md §14): this engine
        keeps serving, so the slots must go back to the pool. Slots are
        released highest-first — ``release`` compacts the max active row
        into the hole, and under that order the moved row always belongs to
        a still-running request, so its slot fixup can land."""
        sched = self.scheduler
        for r in reqs:
            if r.slot is None or sched.running.get(r.req_id) is not r:
                raise ValueError(f"export of request {r.req_id}: not running "
                                 f"with a slot on this engine")
        bundle = self.pool.export_rows([r.slot for r in reqs])
        slots = []
        for r in reqs:
            sched.running.pop(r.req_id, None)
            self._fill_target.pop(r.req_id, None)
            slots.append(r.slot)
            r.slot = None
            r.state = ReqState.WAITING
        if release:
            for s in sorted(slots, reverse=True):
                self.pool.release(s)
                moved_id = (self.pool.slots[s]
                            if s < len(self.pool.slots) else None)
                if moved_id is not None and moved_id in sched.running:
                    sched.running[moved_id].slot = s
        self._tokens_dirty = True
        return bundle

    def export_inflight(self):
        """Detach this engine's whole in-flight population for migration to
        another engine (possibly on a different mesh): every RUNNING request
        with its KV rows, plus the queued-but-not-admitted requests. Returns
        ``(running, bundle, queued)`` where ``bundle`` is a ``RowBundle``
        aligned with ``running`` (None when nothing was running). Slots stay
        occupied — every caller retires this engine afterwards."""
        running = [r for r in self.scheduler.running.values()
                   if r.slot is not None]
        bundle = self.export_requests(running) if running else None
        # anything admitted but slotless (mid-failure) rides with the queue
        stragglers = list(self.scheduler.running.values())
        for r in stragglers:
            self.scheduler.running.pop(r.req_id, None)
            r.state = ReqState.WAITING
        queued = stragglers + list(self.scheduler.queue)
        self.scheduler.queue.clear()
        self._tokens_dirty = True
        return running, bundle, queued

    def adopt_inflight(self, reqs: List[Request],
                       bundle: Optional[RowBundle]) -> int:
        """Adopt migrated requests together with their exported KV rows from
        a foreign pool: rows are resharded onto this pool's cache specs
        (``KVCachePool.import_rows``) and decode continues from the migrated
        state — token streams stay byte-identical across the move. Adopts as
        many requests as this engine has free capacity for and returns the
        count; the caller re-routes the remainder (with
        ``bundle.select(range(n, bundle.n))``)."""
        if not reqs:
            return 0
        if bundle is None or bundle.n != len(reqs):
            raise ValueError("adopt_inflight needs one bundle row per request")
        n_fit = min(len(reqs), self.max_batch - self.pool.n_active)
        if n_fit <= 0:
            return 0
        # before the pool import touches anything: a poisoned import raises
        # with the target pool unmutated, so the caller (cutover/salvage)
        # can exclude this engine and route the requests elsewhere
        fault_point("kv.import_rows", tag=self.fault_tag)
        take = reqs[:n_fit]
        slots = self.pool.import_rows(bundle.select(range(n_fit)),
                                      [r.req_id for r in take])
        for r, s in zip(take, slots):
            r.slot = s
            r.state = ReqState.RUNNING
            self.scheduler.running[r.req_id] = r
            if self.kv_layout == "paged":
                # re-derive fill state from the migrated row length: a row
                # short of prompt+prefix resumes its decode-fill here (a
                # steady row degenerates to a one-step-left fill, which is
                # exactly the steady-state feeding rule)
                tot = len(r.prompt) + len(r.generated)
                if self.pool.host_len[s] < tot:
                    self._fill_target[r.req_id] = tot
        self._tokens_dirty = True
        return n_fit

    # ---- fault tolerance ---------------------------------------------------
    def simulate_worker_failure(self):
        """Drop all running requests (worker died): re-queue with prefix kept,
        reset the pool (fresh replacement worker)."""
        for req in list(self.scheduler.running.values()):
            self.scheduler.requeue_on_failure(req)
        self._init_pool()
