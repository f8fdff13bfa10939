"""Pallas TPU kernel: Mamba-1 selective scan (prefill).

The recurrence h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t is sequential in t
but embarrassingly parallel over channels: the TPU mapping blocks channels
into VPU-width tiles kept in VMEM and walks time in chunks, carrying the
state h [N, Cblk] in VMEM scratch across grid steps (grid iterates time
innermost). This replaces the CUDA kernel's warp-parallel scan with a
lane-parallel scan — no cross-lane communication is needed because B_t/C_t
are shared across channels (broadcast along sublanes).

Grid: (B, C/Cblk, T/Tc); carry h in VMEM persists over the T dimension.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret


def _scan_kernel(dt_ref, x_ref, B_ref, C_ref, A_ref, o_ref, h_ref,
                 *, t_chunk: int):
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    # Whole-chunk loads, then static row slices: a dynamic index into the
    # sublane dim of a block is refused by the TPU compiler.
    dt = dt_ref[0].astype(jnp.float32)              # [Tc, Cblk]
    dtx = dt * x_ref[0].astype(jnp.float32)         # [Tc, Cblk]
    Bt = B_ref[0].astype(jnp.float32).T             # [N, Tc]
    Ct = C_ref[0].astype(jnp.float32).T             # [N, Tc]
    A = A_ref[...]                                  # [N, Cblk]
    h = h_ref[...]                                  # [N, Cblk]
    ys = []
    for i in range(t_chunk):
        decay = jnp.exp(dt[i:i + 1] * A)            # [N, Cblk]
        h = decay * h + dtx[i:i + 1] * Bt[:, i:i + 1]
        ys.append(jnp.sum(h * Ct[:, i:i + 1], axis=0, keepdims=True))
    h_ref[...] = h
    o_ref[0] = jnp.concatenate(ys, axis=0).astype(o_ref.dtype)


def mamba1_scan_kernel(dt, x, Bm, Cm, A, *, c_blk: int = 128,
                       t_chunk: int = 16, interpret: Optional[bool] = None):
    """dt, x: [B, T, C]; Bm, Cm: [B, T, N]; A: [C, N] (negative).
    Returns y: [B, T, C] with y_t = C_t . h_t (caller adds D*x and gating).

    The state is kept channel-minor ([N, Cblk]: channels on the 128 lanes),
    so A enters transposed."""
    B, T, C = x.shape
    N = Bm.shape[-1]
    c_blk = min(c_blk, C)
    t_chunk = min(t_chunk, T)
    assert C % c_blk == 0 and T % t_chunk == 0

    grid = (B, C // c_blk, T // t_chunk)
    return pl.pallas_call(
        functools.partial(_scan_kernel, t_chunk=t_chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, t_chunk, c_blk), lambda b, c, t: (b, t, c)),
            pl.BlockSpec((1, t_chunk, c_blk), lambda b, c, t: (b, t, c)),
            pl.BlockSpec((1, t_chunk, N), lambda b, c, t: (b, t, 0)),
            pl.BlockSpec((1, t_chunk, N), lambda b, c, t: (b, t, 0)),
            pl.BlockSpec((N, c_blk), lambda b, c, t: (0, c)),
        ],
        out_specs=pl.BlockSpec((1, t_chunk, c_blk), lambda b, c, t: (b, t, c)),
        out_shape=jax.ShapeDtypeStruct((B, T, C), x.dtype),
        scratch_shapes=[pltpu.VMEM((N, c_blk), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(dt, x, Bm, Cm, A.T)
