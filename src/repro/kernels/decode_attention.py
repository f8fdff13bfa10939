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
  * per-sequence lengths (and the paged block tables) are scalar-prefetch
    operands in SMEM; out-of-range positions are masked (the compiler
    still streams their blocks).
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
def _paged_decode_kernel(bt_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                         m_ref, l_ref, acc_ref, **kw):
    b = pl.program_id(0)
    s = pl.program_id(1)

    @pl.when(s == 0)
    def _init():
        _init_carry(m_ref, l_ref, acc_ref)

    # logical position of pool slot j within THIS sequence is table-relative
    # (block s of the table holds positions s*bs..s*bs+bs-1), independent of
    # which physical block the table entry points at
    _flash_block(q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref, s=s,
                 length=len_ref[b], **kw)

    @pl.when(s == pl.num_programs(1) - 1)
    def _out():
        _finalize(o_ref, l_ref, acc_ref)


def decode_attention_paged_kernel(q, k_pool, v_pool, block_tables, lengths,
                                  *, interpret: Optional[bool] = None):
    """Flash-decode over the paged pool layout (serving/blockpool.py).

    q: [B, H, Dh]; pools: [NB, bs, Hkv, Dh] (no batch dim — blocks are
    shared across sequences via ref-counted prefix caching); block_tables:
    [B, MB] int32 mapping each sequence's logical block s to a physical
    pool block (unused tail entries point at the scratch block 0 and are
    masked by ``lengths``); lengths: [B]. Returns [B, H, Dh].

    The indirection is the TPU analogue of PagedAttention's gather: the
    block table and lengths ride in as scalar-prefetch operands
    (``PrefetchScalarGridSpec``), so the k/v BlockSpec index_map can pick
    the physical block ``bt[b, s]`` for grid step (b, s) and the DMA
    engine streams exactly one pool block (all heads) per step — no
    [B, S] contiguous materialization of the cache ever exists.
    """
    B, H, Dh = q.shape
    NB, bs, Hkv, _ = k_pool.shape
    MB = block_tables.shape[1]
    G = H // Hkv
    qg = q.reshape(B, Hkv, G, Dh)
    kf = k_pool.reshape(NB, bs, Hkv * Dh)
    vf = v_pool.reshape(NB, bs, Hkv * Dh)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # block_tables, lengths
        grid=(B, MB),
        in_specs=[
            pl.BlockSpec((1, Hkv, G, Dh), lambda b, s, bt, ln: (b, 0, 0, 0)),
            pl.BlockSpec((1, bs, Hkv * Dh),
                         lambda b, s, bt, ln: (bt[b, s], 0, 0)),
            pl.BlockSpec((1, bs, Hkv * Dh),
                         lambda b, s, bt, ln: (bt[b, s], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, Hkv, G, Dh),
                               lambda b, s, bt, ln: (b, 0, 0, 0)),
        scratch_shapes=_carry_scratch(Hkv, G, Dh),
    )
    out = pl.pallas_call(
        functools.partial(_paged_decode_kernel, blk=bs, n_kv=Hkv, dh=Dh,
                          scale=1.0 / math.sqrt(Dh)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, Dh), q.dtype),
        interpret=resolve_interpret(interpret),
    )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32),
      qg, kf, vf)
    return out.reshape(B, H, Dh)
