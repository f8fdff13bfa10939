"""Layer primitives shared by all model families.

Pure-JAX implementations (dry-run / roofline / CPU path). Perf-critical hot
spots have Pallas TPU twins in ``repro.kernels`` that swap in via
``use_pallas`` on real hardware.

All functions take a ``ShardCtx`` for logical-axis sharding constraints and
degrade to no-ops off-mesh.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.launch.mesh import ShardCtx
from repro.models.tuning import FLAGS


def _dot_f32(spec, a, b):
    """Einsum with f32 accumulation. Baseline materializes f32 copies of the
    operands (the naive-but-faithful XLA path); with mixed_precision_attn the
    operands stay bf16 and only the MXU accumulator is f32."""
    if FLAGS.mixed_precision_attn:
        return jnp.einsum(spec, a, b, preferred_element_type=jnp.float32)
    return jnp.einsum(spec, a.astype(jnp.float32), b.astype(jnp.float32))


def _cast_for_pv(p, v):
    """Probability operand for the PV dot: bf16 under mixed precision."""
    if FLAGS.mixed_precision_attn:
        return p.astype(v.dtype)
    return p


def rms_norm(x, scale, eps: float = 1e-5):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    y = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def rope(x, positions, theta: float):
    """Rotary embedding. x: [..., S, H, Dh]; positions: broadcastable [..., S]."""
    dh = x.shape[-1]
    half = dh // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs  # [..., S, half]
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]  # [..., S, 1, half]
    x1, x2 = x[..., :half], x[..., half:]
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    return jnp.concatenate([y1, y2], axis=-1).astype(x.dtype)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

def _blk(x, n, b):
    """[B, n*b, H, D] -> [n, B, b, H, D] scan layout."""
    B, _, H, D = x.shape
    return jnp.moveaxis(x.reshape(B, n, b, H, D), 1, 0)


def _unblk(x):
    """[n, B, H, b, D] -> [B, n*b, H, D]."""
    n, B, H, b, D = x.shape
    return jnp.moveaxis(x, 0, 1).transpose(0, 1, 3, 2, 4).reshape(B, n * b, H, D)


def _flash_fwd_core(q, k, v, causal, qb, kb, skv_real):
    """Padded core. q: [B,Sq,H,Dh]; k,v: [B,Skv,H,Dh] (already GQA-repeated).
    Returns (out [B,Sq,H,Dh], lse [B,H,Sq])."""
    B, Sq, H, Dh = q.shape
    Skv = k.shape[1]
    nq, nk = Sq // qb, Skv // kb
    scale = 1.0 / math.sqrt(Dh)
    qs, ks, vs = _blk(q, nq, qb), _blk(k, nk, kb), _blk(v, nk, kb)

    def q_step(_, qi_blk):
        qi, q_blk_ = qi_blk
        q_pos = qi * qb + jnp.arange(qb)

        def kv_step(carry, kj_blk):
            m, l, acc = carry
            kj, k_blk, v_blk = kj_blk
            kpos = kj * kb + jnp.arange(kb)
            s = _dot_f32("bqhd,bkhd->bhqk", q_blk_, k_blk) * scale
            mask = kpos[None, None, None, :] < skv_real
            if causal:
                mask = mask & (q_pos[None, None, :, None]
                               >= kpos[None, None, None, :])
            s = jnp.where(mask, s, -1e30)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            acc_new = acc * corr[..., None] + _dot_f32(
                "bhqk,bkhd->bhqd", _cast_for_pv(p, v_blk), v_blk)
            return (m_new, l_new, acc_new), None

        init = (jnp.full((B, H, qb), -1e30, jnp.float32),
                jnp.zeros((B, H, qb), jnp.float32),
                jnp.zeros((B, H, qb, Dh), jnp.float32))
        (m, l, acc), _ = jax.lax.scan(kv_step, init,
                                      (jnp.arange(nk), ks, vs))
        out = (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)
        lse = jnp.where(l > 0, m + jnp.log(jnp.maximum(l, 1e-30)), 0.0)
        return None, (out, lse)

    _, (outs, lses) = jax.lax.scan(q_step, None, (jnp.arange(nq), qs))
    out = _unblk(outs)                      # [B, Sq, H, Dh]
    lse = jnp.moveaxis(lses, 0, 2)          # [nq,B,H,qb] -> [B,H,nq,qb]
    lse = lse.reshape(B, H, Sq)
    return out, lse


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, causal, qb, kb, skv_real):
    out, _ = _flash_fwd_core(q, k, v, causal, qb, kb, skv_real)
    return out


def _flash_vjp_fwd(q, k, v, causal, qb, kb, skv_real):
    out, lse = _flash_fwd_core(q, k, v, causal, qb, kb, skv_real)
    return out, (q, k, v, out, lse)


def _flash_vjp_bwd(causal, qb, kb, skv_real, res, dout):
    """FlashAttention backward: blockwise recompute from (out, lse).
    Peak temp O(qb*kb) instead of O(Sq*Skv) saved probabilities."""
    q, k, v, out, lse = res
    B, Sq, H, Dh = q.shape
    Skv = k.shape[1]
    nq, nk = Sq // qb, Skv // kb
    scale = 1.0 / math.sqrt(Dh)
    delta = jnp.einsum("bqhd,bqhd->bhq", dout.astype(jnp.float32),
                       out.astype(jnp.float32))  # [B, H, Sq]
    qs, ks, vs = _blk(q, nq, qb), _blk(k, nk, kb), _blk(v, nk, kb)
    dos = _blk(dout, nq, qb)
    lses = jnp.moveaxis(lse.reshape(B, H, nq, qb), 2, 0)    # [nq,B,H,qb]
    deltas = jnp.moveaxis(delta.reshape(B, H, nq, qb), 2, 0)

    def block_dS(qi, kj, q_blk, k_blk, lse_blk):
        """Recompute P and return (P, positions mask) for block (qi, kj)."""
        q_pos = qi * qb + jnp.arange(qb)
        kpos = kj * kb + jnp.arange(kb)
        s = _dot_f32("bqhd,bkhd->bhqk", q_blk, k_blk) * scale
        mask = kpos[None, None, None, :] < skv_real
        if causal:
            mask = mask & (q_pos[None, None, :, None]
                           >= kpos[None, None, None, :])
        p = jnp.where(mask, jnp.exp(s - lse_blk[..., None]), 0.0)
        return p

    # pass A: dq (outer over q blocks, inner over kv blocks)
    def dq_step(_, xs):
        qi, q_blk, do_blk, lse_blk, delta_blk = xs

        def inner(dq_acc, ys):
            kj, k_blk, v_blk = ys
            p = block_dS(qi, kj, q_blk, k_blk, lse_blk)
            dp = _dot_f32("bqhd,bkhd->bhqk", do_blk, v_blk)
            ds = p * (dp - delta_blk[..., None]) * scale
            dq_acc = dq_acc + _dot_f32("bhqk,bkhd->bqhd",
                                       _cast_for_pv(ds, k_blk), k_blk)
            return dq_acc, None

        dq0 = jnp.zeros((B, qb, H, Dh), jnp.float32)
        dq_blk, _ = jax.lax.scan(inner, dq0, (jnp.arange(nk), ks, vs))
        return None, dq_blk

    _, dqs = jax.lax.scan(dq_step, None, (jnp.arange(nq), qs, dos, lses, deltas))
    dq = jnp.moveaxis(dqs, 0, 1).reshape(B, Sq, H, Dh).astype(q.dtype)

    # pass B: dk, dv (outer over kv blocks, inner over q blocks)
    def dkv_step(_, xs):
        kj, k_blk, v_blk = xs

        def inner(carry, ys):
            dk_acc, dv_acc = carry
            qi, q_blk, do_blk, lse_blk, delta_blk = ys
            p = block_dS(qi, kj, q_blk, k_blk, lse_blk)
            dv_acc = dv_acc + _dot_f32("bhqk,bqhd->bkhd",
                                       _cast_for_pv(p, do_blk), do_blk)
            dp = _dot_f32("bqhd,bkhd->bhqk", do_blk, v_blk)
            ds = p * (dp - delta_blk[..., None]) * scale
            dk_acc = dk_acc + _dot_f32("bhqk,bqhd->bkhd",
                                       _cast_for_pv(ds, q_blk), q_blk)
            return (dk_acc, dv_acc), None

        z = jnp.zeros((B, kb, H, Dh), jnp.float32)
        (dk_blk, dv_blk), _ = jax.lax.scan(
            inner, (z, z), (jnp.arange(nq), qs, dos, lses, deltas))
        return None, (dk_blk, dv_blk)

    _, (dks, dvs) = jax.lax.scan(dkv_step, None, (jnp.arange(nk), ks, vs))
    dk = jnp.moveaxis(dks, 0, 1).reshape(B, Skv, H, Dh).astype(k.dtype)
    dv = jnp.moveaxis(dvs, 0, 1).reshape(B, Skv, H, Dh).astype(v.dtype)
    return dq, dk, dv


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_attention(q, k, v, *, causal: bool, q_block: int = 512,
                    kv_block: int = 512, ctx: Optional[ShardCtx] = None):
    """Blocked (FlashAttention-style) attention, pure XLA, custom VJP.

    q: [B, Sq, H, Dh]; k, v: [B, Skv, Hkv, Dh] with H % Hkv == 0.
    Online-softmax over KV blocks inside a scan over Q blocks: peak temp is
    O(q_block * kv_block) instead of O(Sq * Skv), forward AND backward (the
    backward recomputes P blockwise from the saved logsumexp).
    """
    B, Sq, H, Dh = q.shape
    _, Skv, Hkv, _ = k.shape
    G = H // Hkv
    if H != Hkv:  # GQA: broadcast KV across the query group (diff'able)
        k = jnp.repeat(k, G, axis=2)
        v = jnp.repeat(v, G, axis=2)

    qb = min(q_block, Sq)
    kb = min(kv_block, Skv)
    Sq_p, Skv_p = -(-Sq // qb) * qb, -(-Skv // kb) * kb
    q = jnp.pad(q, ((0, 0), (0, Sq_p - Sq), (0, 0), (0, 0)))
    k = jnp.pad(k, ((0, 0), (0, Skv_p - Skv), (0, 0), (0, 0)))
    v = jnp.pad(v, ((0, 0), (0, Skv_p - Skv), (0, 0), (0, 0)))
    out = _flash(q, k, v, causal, qb, kb, Skv)
    return out[:, :Sq]


def decode_attention_dense(q, k_cache, v_cache, lengths, layout: str = "bshd"):
    """Single-token attention against a full cache (head-sharded / replicated).

    q: [B, 1, H, Dh]; caches: [B, S, Hkv, Dh] ("bshd") or the head-major
    [B, Hkv, S, Dh] ("bhsd", transpose-free dots); lengths: [B] — the new
    token sits at position lengths[b] and must already be in the cache.
    """
    B, _, H, Dh = q.shape
    if layout == "bhsd":
        Hkv, S = k_cache.shape[1], k_cache.shape[2]
        qk, pv = "bkgd,bksd->bkgs", "bkgs,bksd->bkgd"
    else:
        S, Hkv = k_cache.shape[1], k_cache.shape[2]
        qk, pv = "bkgd,bskd->bkgs", "bkgs,bskd->bkgd"
    G = H // Hkv
    scale = 1.0 / math.sqrt(Dh)
    qg = q.reshape(B, Hkv, G, Dh)
    s = _dot_f32(qk, qg, k_cache) * scale
    mask = jnp.arange(S)[None, :] <= lengths[:, None]  # [B, S]
    s = jnp.where(mask[:, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = _dot_f32(pv, _cast_for_pv(p, v_cache), v_cache)
    return out.reshape(B, 1, H, Dh).astype(q.dtype)


def _combined_axis_index(axes: tuple[str, ...]):
    idx = jax.lax.axis_index(axes[0])
    for a in axes[1:]:
        idx = idx * jax.lax.psum(1, a) + jax.lax.axis_index(a)
    return idx


def decode_attention_seqpar(q, k_cache, v_cache, k_new, v_new, lengths, *,
                            mesh, batch_axes: tuple[str, ...],
                            seq_axes: tuple[str, ...], layout: str = "bshd"):
    """Sequence-parallel flash-decode via shard_map (TPU adaptation for GQA
    archs whose KV heads don't divide the model axis).

    The KV cache is sharded along sequence over ``seq_axes``; each shard
    computes partial online-softmax statistics which are combined with a tiny
    psum (the flash-decode split-k trick, mapped onto ICI).

    Also performs the cache write: the owner shard inserts (k_new, v_new) at
    lengths[b]. Returns (out [B,1,H,Dh], k_cache', v_cache').
    Cache layout "bshd" [B,S,Hkv,Dh] or head-major "bhsd" [B,Hkv,S,Dh].
    """
    head_major = layout == "bhsd"
    if head_major:
        B, Hkv, S, Dh = k_cache.shape
        seq_axis_in_cache = 2
        qk, pv = "bkgd,bksd->bkgs", "bkgs,bksd->bkgd"
        cspec = lambda b, s: P(b, None, s, None)
    else:
        B, S, Hkv, Dh = k_cache.shape
        seq_axis_in_cache = 1
        qk, pv = "bkgd,bskd->bkgs", "bkgs,bskd->bkgd"
        cspec = lambda b, s: P(b, s, None, None)
    H = q.shape[2]
    G = H // Hkv
    scale = 1.0 / math.sqrt(Dh)
    n_seq = math.prod(mesh.shape[a] for a in seq_axes)
    S_loc = S // n_seq
    bspec = batch_axes if batch_axes else None
    sspec = seq_axes if len(seq_axes) > 1 else (seq_axes[0] if seq_axes else None)
    waxis = seq_axis_in_cache - 1  # per-batch-row write axis

    def kernel(q_, kc, vc, kn, vn, lens):
        sid = _combined_axis_index(seq_axes)
        offset = sid * S_loc
        # --- owner-shard cache write at local position ---
        loc = lens - offset  # [B]
        own = (loc >= 0) & (loc < S_loc)
        locc = jnp.clip(loc, 0, S_loc - 1)

        def write_one(c, new, l, o):
            # c: per-row cache [S_loc, Hkv, Dh] or [Hkv, S_loc, Dh]
            nw = new if not head_major else new  # [Hkv, Dh] new row
            cur = jax.lax.dynamic_slice_in_dim(c, l, 1, axis=waxis)
            upd_new = (nw[None] if waxis == 0 else nw[:, None])
            upd = jnp.where(o, upd_new.astype(c.dtype), cur)
            return jax.lax.dynamic_update_slice_in_dim(c, upd, l, axis=waxis)

        kc = jax.vmap(write_one)(kc, kn, locc, own)
        vc = jax.vmap(write_one)(vc, vn, locc, own)
        # --- partial attention over the local KV slice ---
        qg = q_.reshape(-1, Hkv, G, Dh)
        s = _dot_f32(qk, qg, kc) * scale
        pos = offset + jnp.arange(S_loc)
        mask = pos[None, :] <= lens[:, None]
        s = jnp.where(mask[:, None, None, :], s, -1e30)
        m = s.max(axis=-1)  # [B,Hkv,G]
        m_g = jax.lax.pmax(m, seq_axes)
        p = jnp.exp(s - m_g[..., None])
        l_part = p.sum(axis=-1)
        acc = _dot_f32(pv, _cast_for_pv(p, vc), vc)
        l_g = jax.lax.psum(l_part, seq_axes)
        acc_g = jax.lax.psum(acc, seq_axes)
        out = (acc_g / jnp.maximum(l_g, 1e-30)[..., None])
        return out.reshape(-1, 1, H, Dh).astype(q_.dtype), kc, vc

    in_specs = (P(bspec, None, None, None), cspec(bspec, sspec),
                cspec(bspec, sspec), P(bspec, None, None),
                P(bspec, None, None), P(bspec))
    out_specs = (P(bspec, None, None, None), cspec(bspec, sspec),
                 cspec(bspec, sspec))
    mapped = jax.shard_map(kernel, mesh=mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=False)
    out, kc, vc = mapped(q, k_cache, v_cache, k_new, v_new, lengths)
    return out, kc, vc


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def _mlp_axes_for(h):
    return ("batch",) + (None,) * (h.ndim - 2) + ("mlp",)


def swiglu(x, w_gate, w_up, w_down, ctx: ShardCtx):
    h = jax.nn.silu(x @ w_gate) * (x @ w_up)
    h = ctx.constrain(h, *_mlp_axes_for(h))
    return h @ w_down


def gelu_mlp(x, w_up, b_up, w_down, b_down, ctx: ShardCtx):
    h = jax.nn.gelu(x @ w_up + b_up)
    h = ctx.constrain(h, *_mlp_axes_for(h))
    return h @ w_down + b_down


# ---------------------------------------------------------------------------
# Mixture of Experts (sort/gather-based capacity dispatch; EP over "experts")
# ---------------------------------------------------------------------------

def _moe_row(x, w_router, w_gate, w_up, w_down, *, top_k: int, capacity: int):
    """Route one sequence row. x: [T, D] -> (out [T, D], aux scalar).

    Capacity-based dispatch with gather/scatter (no O(T*E*C) one-hots):
    tokens are ranked within their expert via a stable sort; ranks >= capacity
    are dropped (standard capacity-factor semantics; pass capacity=T for
    lossless decode).
    """
    T, D = x.shape
    E, _, F = w_gate.shape
    C = capacity

    gate_logits = (x @ w_router).astype(jnp.float32)  # [T, E]
    probs = jax.nn.softmax(gate_logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, top_k)  # [T, k]
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    # aux loss (Switch-style load balancing)
    me = probs.mean(axis=0)
    ce = jnp.zeros((E,), jnp.float32).at[top_i.reshape(-1)].add(1.0) / (T * top_k)
    aux = E * jnp.sum(me * ce)

    flat_e = top_i.reshape(-1)  # [T*k]
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    # rank within expert group
    first = jnp.searchsorted(sorted_e, sorted_e, side="left")
    rank = jnp.arange(T * top_k) - first
    valid = rank < C
    slot = jnp.where(valid, sorted_e * C + rank, E * C)  # E*C = drop bin
    tok = order // top_k
    wgt = top_p.reshape(-1)[order]

    slot_tok = jnp.full((E * C + 1,), T, jnp.int32).at[slot].set(
        jnp.where(valid, tok, T))[:-1]
    slot_wgt = jnp.zeros((E * C + 1,), jnp.float32).at[slot].set(
        jnp.where(valid, wgt, 0.0))[:-1]

    x_pad = jnp.concatenate([x, jnp.zeros((1, D), x.dtype)], axis=0)
    xe = x_pad[slot_tok].reshape(E, C, D)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, w_gate))
    h = h * jnp.einsum("ecd,edf->ecf", xe, w_up)
    ye = jnp.einsum("ecf,efd->ecd", h, w_down).reshape(E * C, D)
    ye = ye * slot_wgt[:, None].astype(ye.dtype)

    out = jnp.zeros((T + 1, D), ye.dtype).at[slot_tok].add(ye)[:T]
    return out.astype(x.dtype), aux


def _moe_routing_row(x, w_router, *, top_k: int, capacity: int):
    """Routing for one row: returns (slot_tok [E*C], slot_wgt [E*C], aux)."""
    T = x.shape[0]
    E = w_router.shape[-1]
    C = capacity
    gate_logits = (x @ w_router).astype(jnp.float32)
    probs = jax.nn.softmax(gate_logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, top_k)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    me = probs.mean(axis=0)
    ce = jnp.zeros((E,), jnp.float32).at[top_i.reshape(-1)].add(1.0) / (T * top_k)
    aux = E * jnp.sum(me * ce)

    flat_e = top_i.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    first = jnp.searchsorted(sorted_e, sorted_e, side="left")
    rank = jnp.arange(T * top_k) - first
    valid = rank < C
    slot = jnp.where(valid, sorted_e * C + rank, E * C)
    tok = order // top_k
    wgt = top_p.reshape(-1)[order]
    slot_tok = jnp.full((E * C + 1,), T, jnp.int32).at[slot].set(
        jnp.where(valid, tok, T))[:-1]
    slot_wgt = jnp.zeros((E * C + 1,), jnp.float32).at[slot].set(
        jnp.where(valid, wgt, 0.0))[:-1]
    return slot_tok, slot_wgt, aux


def _moe_batched(x, w_router, w_gate, w_up, w_down, *, top_k: int,
                 capacity: int, ctx: ShardCtx):
    """Batched dispatch: only the (cheap, index-valued) routing is vmapped;
    the gather / expert GEMMs / combine carry explicit batch dims with
    sharding constraints, so dispatch buffers stay (batch x experts)-sharded
    instead of being all-gathered across the model axis (baseline failure
    mode; see EXPERIMENTS.md §Perf B1)."""
    B, S, D = x.shape
    E, _, F = w_gate.shape
    C = capacity
    slot_tok, slot_wgt, aux = jax.vmap(
        partial(_moe_routing_row, top_k=top_k, capacity=capacity),
        in_axes=(0, None))(x, w_router)          # [B, E*C] each

    x_pad = jnp.concatenate([x, jnp.zeros((B, 1, D), x.dtype)], axis=1)
    xe = jnp.take_along_axis(x_pad, slot_tok[..., None], axis=1)  # [B, E*C, D]
    xe = xe.reshape(B, E, C, D)
    xe = ctx.constrain(xe, "batch", "experts", None, None)
    h = jax.nn.silu(jnp.einsum("becd,edf->becf", xe, w_gate))
    h = h * jnp.einsum("becd,edf->becf", xe, w_up)
    h = ctx.constrain(h, "batch", "experts", None, None)
    ye = jnp.einsum("becf,efd->becd", h, w_down)
    ye = ctx.constrain(ye, "batch", "experts", None, None)
    ye = ye.reshape(B, E * C, D)  # dim1 stays expert-sharded (E | E*C)
    ye = ye * slot_wgt[..., None].astype(ye.dtype)

    out = ctx.constrain(jnp.zeros((B, S + 1, D), ye.dtype),
                        "batch", None, None)
    bidx = jnp.broadcast_to(jnp.arange(B)[:, None], slot_tok.shape)
    out = out.at[bidx, slot_tok].add(ye)
    out = ctx.constrain(out, "batch", None, None)[:, :S]
    return out, aux.mean()


def moe_ffn(x, w_router, w_gate, w_up, w_down, *, top_k: int, capacity: int,
            ctx: ShardCtx):
    """Top-k routed expert FFN over [B, S, D] activations.

    Baseline: routing AND dispatch vmapped over batch rows (gathers stay
    local to a data shard; capacity is per-row). Optimized
    (FLAGS.moe_batched_dispatch): batched dispatch with explicit sharding
    constraints — same math, far fewer collectives.
    """
    if FLAGS.moe_batched_dispatch:
        return _moe_batched(x, w_router, w_gate, w_up, w_down, top_k=top_k,
                            capacity=capacity, ctx=ctx)
    B, S, D = x.shape
    row = partial(_moe_row, top_k=top_k, capacity=capacity)
    out, aux = jax.vmap(row, in_axes=(0, None, None, None, None))(
        x, w_router, w_gate, w_up, w_down)
    out = ctx.constrain(out, "batch", None, None)
    return out, aux.mean()


def moe_capacity(cfg, tokens_per_shard: int, *, lossless: bool) -> int:
    if lossless:
        return tokens_per_shard
    c = int(math.ceil(tokens_per_shard * cfg.top_k / cfg.num_experts
                      * cfg.capacity_factor))
    return max(8, min(tokens_per_shard, -(-c // 8) * 8))
