"""Pallas TPU kernel: GQA flash-decode over a long KV cache.

The decode hot spot: one query token per sequence attending to a cache of
S_ctx positions. Memory-bound — the whole KV cache streams HBM->VMEM once;
the kernel's job is to keep scores/softmax state resident in VMEM (the XLA
path materializes every score block to HBM; see EXPERIMENTS.md §Roofline).

TPU adaptation (vs. the CUDA flash-decode it mirrors):
  * the query group (G = H/Hkv heads sharing one KV head) forms the MXU
    row-block: scores[G, blk] = q[G, Dh] @ K[blk, Dh]^T — Dh=64..128 aligns
    the contraction with the 128-wide systolic array;
  * grid = (B, S/blk) with the KV-block dim innermost: one step streams a
    KV block of every head ([blk, Hkv*Dh], the full trailing dims, so the
    block satisfies the (8, 128) tiling rule for any head count) and the
    online-softmax carry (m, l, acc) of every head lives in VMEM scratch
    across grid steps — the TPU-idiomatic replacement for CUDA's split-K +
    shared-memory reduction;
  * per-sequence lengths are scalar-prefetch operands in SMEM;
    out-of-range positions are masked (the compiler still streams their
    blocks).

The paged variant, the one the server runs, instead leaves the block pool
in HBM and copies only each row's live blocks (its docstring).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

NEG_INF = -1e30


def _flash_block(q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref, *, s: int,
                 length, blk: int, n_kv: int, dh: int, scale: float):
    """Online-softmax update of every KV head's carry with one KV block.

    k_ref/v_ref hold ``blk`` positions with the heads flattened into the
    lane dim ([blk, Hkv*Dh]): a block that spans the full trailing dims
    meets the TPU's (8, 128) tiling rule for any head count. Heads are a
    static loop over lane slices; the query group of head h is the MXU
    row-block ``q[h]: [G, Dh]``."""
    k = k_ref[0].astype(jnp.float32)  # [blk, Hkv*Dh]
    v = v_ref[0].astype(jnp.float32)
    for h in range(n_kv):
        q = q_ref[0, h].astype(jnp.float32)   # [G, Dh]
        kh = k[:, h * dh:(h + 1) * dh]        # [blk, Dh]
        vh = v[:, h * dh:(h + 1) * dh]
        scores = jax.lax.dot_general(
            q, kh, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [G, blk]
        pos = s * blk + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
        scores = jnp.where(pos <= length, scores, NEG_INF)
        m_prev = m_ref[h]                    # [G, 1]
        m_new = jnp.maximum(m_prev, scores.max(axis=1, keepdims=True))
        p = jnp.exp(scores - m_new)          # [G, blk]
        corr = jnp.exp(m_prev - m_new)       # [G, 1]
        l_ref[h] = l_ref[h] * corr + p.sum(axis=1, keepdims=True)
        acc_ref[h] = acc_ref[h] * corr + jax.lax.dot_general(
            p, vh, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[h] = m_new


def _init_carry(m_ref, l_ref, acc_ref):
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def _finalize(o_ref, l_ref, acc_ref):
    o_ref[0] = (acc_ref[...] /
                jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def _carry_scratch(n_kv: int, G: int, Dh: int):
    return [pltpu.VMEM((n_kv, G, 1), jnp.float32),   # m
            pltpu.VMEM((n_kv, G, 1), jnp.float32),   # l
            pltpu.VMEM((n_kv, G, Dh), jnp.float32)]  # acc


def _decode_kernel(lengths_ref, q_ref, k_ref, v_ref, o_ref,
                   m_ref, l_ref, acc_ref, **kw):
    b = pl.program_id(0)
    s = pl.program_id(1)

    @pl.when(s == 0)
    def _init():
        _init_carry(m_ref, l_ref, acc_ref)

    _flash_block(q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref, s=s,
                 length=lengths_ref[b], **kw)

    @pl.when(s == pl.num_programs(1) - 1)
    def _out():
        _finalize(o_ref, l_ref, acc_ref)


def decode_attention_kernel(q, k_cache, v_cache, lengths, *, blk: int = 512,
                            interpret: Optional[bool] = None):
    """q: [B, H, Dh]; caches: [B, S, Hkv, Dh]; lengths: [B] (new-token pos;
    the new token's K/V must already be written at lengths[b]).
    Returns [B, H, Dh].

    Grid (B, S/blk) with the KV-block dim innermost; ``lengths`` rides in
    as a scalar-prefetch operand (SMEM), and each grid step streams one
    [blk, Hkv*Dh] slab of K and of V (a free reshape of the cache)."""
    B, H, Dh = q.shape
    _, S, Hkv, _ = k_cache.shape
    G = H // Hkv
    blk = min(blk, S)
    assert S % blk == 0, (S, blk)
    qg = q.reshape(B, Hkv, G, Dh)
    kf = k_cache.reshape(B, S, Hkv * Dh)
    vf = v_cache.reshape(B, S, Hkv * Dh)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,  # lengths
        grid=(B, S // blk),
        in_specs=[
            pl.BlockSpec((1, Hkv, G, Dh), lambda b, s, ln: (b, 0, 0, 0)),
            pl.BlockSpec((1, blk, Hkv * Dh), lambda b, s, ln: (b, s, 0)),
            pl.BlockSpec((1, blk, Hkv * Dh), lambda b, s, ln: (b, s, 0)),
        ],
        out_specs=pl.BlockSpec((1, Hkv, G, Dh),
                               lambda b, s, ln: (b, 0, 0, 0)),
        scratch_shapes=_carry_scratch(Hkv, G, Dh),
    )
    out = pl.pallas_call(
        functools.partial(_decode_kernel, blk=blk, n_kv=Hkv, dh=Dh,
                          scale=1.0 / math.sqrt(Dh)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, Dh), q.dtype),
        interpret=resolve_interpret(interpret),
    )(lengths.astype(jnp.int32), qg, kf, vf)
    return out.reshape(B, H, Dh)


# ---------------------------------------------------------------------------
# paged variant: KV lives in a shared block pool, indirected by block tables
# ---------------------------------------------------------------------------
LANES = 128            # the vector unit's lane width
CHUNK_POSITIONS = 128  # positions a chunk covers, at the least
F32 = jax.lax.Precision.HIGHEST  # float32 products, not bfloat16 passes


class _PagedGeometry:
    """Static layout of a pool block as the kernel reads it.

    A block ``[bs, Hkv, Dh]`` is copied as ``R`` rows of ``lane`` values
    (its bytes in order; ``lane`` is 128 wherever ``bs*Hkv*Dh`` allows), so
    every copy moves whole tiles for any head count or width. In that view
    position ``p`` and head ``h`` start at flat offset ``p*W + h*Dh``
    (``W = Hkv*Dh``): the row pattern repeats every ``P`` positions, i.e.
    every ``RP = P*W/lane`` rows. A *piece* is the part of one head at one
    phase ``p % P`` that lies in one row: rows ``row, row+RP, ...`` of a
    chunk, lanes ``[l0, l0+w)``, holding dims ``[d0, d0+w)`` of that head at
    positions ``phase, phase+P, ...``. Scores sum over a head's pieces at a
    phase; each piece's share of the output lands in its own lanes."""

    def __init__(self, bs: int, n_kv: int, dh: int, mb: int):
        W = n_kv * dh
        self.bs, self.n_kv, self.dh, self.W = bs, n_kv, dh, W
        self.lane = math.gcd(bs * W, LANES)
        self.R = bs * W // self.lane
        self.P = self.lane // math.gcd(W, self.lane)
        self.RP = self.P * W // self.lane
        # blocks per chunk: CHUNK_POSITIONS positions, fewer only for a
        # table that is shorter
        self.C = max(1, min(mb, -(-CHUNK_POSITIONS // bs)))
        self.T = self.C * bs                 # positions a chunk covers
        self.n = self.T // self.P            # positions a piece covers
        self.pieces = []                     # (phase, head, row, l0, w, d0)
        for phase in range(self.P):
            for h in range(n_kv):
                f, d = phase * W + h * dh, 0
                while d < dh:
                    row, l0 = divmod(f + d, self.lane)
                    w = min(dh - d, self.lane - l0)
                    self.pieces.append((phase, h, row, l0, w, d))
                    d += w


def _paged_decode_kernel(len_ref, bt_ref, q_ref, k_hbm, v_hbm, o_ref,
                         kbuf, vbuf, kx, vx, sems, slot_ref, *,
                         geo: _PagedGeometry, mb: int):
    """Grid step b attends row b over its live blocks only.

    Row b's live blocks are ``n_b = min(mb, lengths[b] // bs + 1)``; they
    are streamed ``C`` at a time from the pools (left in HBM) by one async
    copy per block into a double buffer, the next chunk's copies in flight
    while this one computes. A row's last chunk starts the next row's
    first, so the copies run on across grid steps (the slot in use rides in
    SMEM). No copy is made of a block past ``n_b`` and no chunk past it is
    visited. Within the last chunk the slots past ``n_b`` hold stale VMEM:
    their V rows are zeroed, and every score past ``lengths[b]`` is
    masked. A NaN in a block past ``n_b`` cannot reach the output, since
    the block is never copied; one in V past ``lengths[b]`` inside a live
    block would (probability 0 times NaN), as in the dense path. The
    online-softmax state (per head m, l; per piece acc) is the chunk
    loop's carry."""
    b = pl.program_id(0)
    n_rows = pl.num_programs(0)
    bs, C, P, G = geo.bs, geo.C, geo.P, q_ref.shape[2]

    def live_blocks(r):
        return jnp.clip(len_ref[r] // bs + 1, 1, mb)

    def each_live_block(r, c, slot, op):
        def body(j, carry):
            page = bt_ref[r * mb + c * C + j]
            op(pltpu.make_async_copy(k_hbm.at[page], kbuf.at[slot, j],
                                     sems.at[0, slot]))
            op(pltpu.make_async_copy(v_hbm.at[page], vbuf.at[slot, j],
                                     sems.at[1, slot]))
            return carry
        jax.lax.fori_loop(0, jnp.minimum(C, live_blocks(r) - c * C), body, 0)

    def start(r, c, slot):
        each_live_block(r, c, slot, lambda cp: cp.start())

    @pl.when(b == 0)
    def _first():
        slot_ref[0] = 0
        start(0, 0, 0)

    n_b = live_blocks(b)
    n_chunks = (n_b + C - 1) // C
    end = jnp.minimum(len_ref[b] + 1, n_b * bs)  # live positions: [0, end)

    def chunk(c, carry):
        m, l, acc = carry
        slot = slot_ref[0]
        nxt = 1 - slot
        # the next chunk of this row, else the next row's first
        last = c + 1 == n_chunks
        next_row = jnp.where(last, b + 1, b)

        @pl.when(next_row < n_rows)
        def _prefetch():
            start(next_row, jnp.where(last, 0, c + 1), nxt)

        each_live_block(b, c, slot, lambda cp: cp.wait())
        slot_ref[0] = nxt
        # f32 copies in which a piece is a strided row load
        kx[...] = kbuf[slot].astype(jnp.float32).reshape(kx.shape)
        vx[...] = vbuf[slot].astype(jnp.float32).reshape(vx.shape)

        def positions(phase, shape, dim):
            return (c * geo.T + phase
                    + P * jax.lax.broadcasted_iota(jnp.int32, shape, dim))
        # V rows of the chunk's unused slots hold stale VMEM: zero them;
        # scores past lengths[b] are masked
        row_copied = [positions(ph, (geo.n, 1), 0) < n_b * bs
                      for ph in range(P)]
        col_live = [positions(ph, (1, geo.n), 1) < end for ph in range(P)]
        scores, vals = {}, []
        for i, (phase, h, row, *_rest) in enumerate(geo.pieces):
            rows = pl.ds(row, geo.n, stride=geo.RP)
            vals.append(jnp.where(row_copied[phase], vx[rows, :], 0.0))
            s = jax.lax.dot_general(
                q_ref[0, i], kx[rows, :], (((1,), (1,)), ((), ())),
                precision=F32, preferred_element_type=jnp.float32)  # [G, n]
            key = (h, phase)
            scores[key] = scores[key] + s if key in scores else s
        m_new, l_new, corr, ps = [], [], [], {}
        for h in range(geo.n_kv):
            ss = [jnp.where(col_live[ph], scores[h, ph], NEG_INF)
                  for ph in range(P)]
            mh = m[h]
            for s in ss:
                mh = jnp.maximum(mh, s.max(axis=1, keepdims=True))
            corr.append(jnp.exp(m[h] - mh))
            lh = l[h] * corr[h]
            for ph, s in enumerate(ss):
                ps[h, ph] = jnp.exp(s - mh)
                lh = lh + ps[h, ph].sum(axis=1, keepdims=True)
            m_new.append(mh)
            l_new.append(lh)
        acc = tuple(
            acc[i] * corr[h] + jax.lax.dot_general(
                ps[h, phase], vals[i], (((1,), (0,)), ((), ())),
                precision=F32, preferred_element_type=jnp.float32)
            for i, (phase, h, *_rest) in enumerate(geo.pieces))
        return tuple(m_new), tuple(l_new), acc

    col = jnp.zeros((G, 1), jnp.float32)
    m, l, acc = jax.lax.fori_loop(
        0, n_chunks, chunk,
        ((col + NEG_INF,) * geo.n_kv, (col,) * geo.n_kv,
         (jnp.zeros((G, geo.lane), jnp.float32),) * len(geo.pieces)))
    for i, (_phase, h, *_rest) in enumerate(geo.pieces):
        o_ref[0, i] = acc[i] / jnp.maximum(l[h], 1e-30)


def decode_attention_paged_kernel(q, k_pool, v_pool, block_tables, lengths,
                                  *, interpret: Optional[bool] = None):
    """Flash-decode over the paged pool layout (serving/blockpool.py),
    reading only each row's live blocks.

    q: [B, H, Dh]; pools: [NB, bs, Hkv, Dh] (no batch dim — blocks are
    shared across sequences via ref-counted prefix caching); block_tables:
    [B, MB] int32 mapping each sequence's logical block s to a physical
    pool block (unused tail entries point at the scratch block 0);
    lengths: [B], the new token's position (its K/V already written).
    Row b attends positions ``0..lengths[b]`` of its first
    ``min(MB, lengths[b] // bs + 1)`` table blocks. Returns [B, H, Dh].

    Grid (B,): the tables and lengths are scalar-prefetch operands (SMEM),
    the pools stay in HBM and the kernel copies the blocks the table names,
    ``C`` blocks (at least 128 positions) a chunk, double-buffered; work and
    traffic grow with the live lengths, not with MB. Scores, softmax and
    accumulation are float32."""
    B, H, Dh = q.shape
    NB, bs, Hkv, _ = k_pool.shape
    MB = block_tables.shape[1]
    G = H // Hkv
    geo = _PagedGeometry(bs, Hkv, Dh, MB)
    lane, NP = geo.lane, len(geo.pieces)
    # the query as one [G, lane] operand per piece: its dims at the piece's
    # lanes, zero elsewhere, the softmax scale folded in
    qs = q.reshape(B, Hkv, G, Dh).astype(jnp.float32) / math.sqrt(Dh)
    qp = jnp.stack([
        jnp.pad(qs[:, h, :, d0:d0 + w], ((0, 0), (0, 0),
                                         (l0, lane - l0 - w)))
        for _phase, h, _row, l0, w, d0 in geo.pieces], axis=1)
    kf = k_pool.reshape(NB, geo.R, lane)
    vf = v_pool.reshape(NB, geo.R, lane)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # lengths, block tables (flat)
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, NP, G, lane), lambda b, ln, bt: (b, 0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, NP, G, lane),
                               lambda b, ln, bt: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, geo.C, geo.R, lane), k_pool.dtype),  # K chunks
            pltpu.VMEM((2, geo.C, geo.R, lane), v_pool.dtype),  # V chunks
            pltpu.VMEM((geo.C * geo.R, lane), jnp.float32),     # K, f32
            pltpu.VMEM((geo.C * geo.R, lane), jnp.float32),     # V, f32
            pltpu.SemaphoreType.DMA((2, 2)),                    # [k|v, slot]
            pltpu.SMEM((1,), jnp.int32),                        # slot
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_decode_kernel, geo=geo, mb=MB),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, NP, G, lane), jnp.float32),
        # sequential rows: a row's last chunk prefetches the next row's
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=resolve_interpret(interpret),
    )(lengths.astype(jnp.int32), block_tables.reshape(-1).astype(jnp.int32),
      qp, kf, vf)
    # each head's dims from its pieces' lanes, summed over its phases
    parts = {}
    for i, (phase, h, _row, l0, w, _d0) in enumerate(geo.pieces):
        parts.setdefault((h, phase), []).append(out[:, i, :, l0:l0 + w])
    heads = [sum(jnp.concatenate(parts[h, phase], axis=-1)
                 for phase in range(geo.P)) for h in range(Hkv)]
    return jnp.stack(heads, axis=1).reshape(B, H, Dh).astype(q.dtype)
