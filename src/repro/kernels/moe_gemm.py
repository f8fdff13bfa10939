"""Pallas TPU kernel: grouped expert GEMM (MoE FFN).

Computes out[e] = act(x[e] @ w_in[e]) for capacity-dispatched expert inputs
xe [E, C, D] against per-expert weights [E, D, F]. Grid iterates experts
outermost and the contraction innermost; a VMEM fp32 accumulator carries
partial products across D-blocks, so each [bc, bf] output tile is written to
HBM exactly once (the XLA path materializes per-expert intermediates).
Tiles are 128-aligned for the MXU; expert tokens-per-capacity C is padded by
the caller (ops.py) to a sublane multiple.

Grid: (E, C/bc, F/bf, D/bd).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret


def _gemm_kernel(x_ref, w_ref, o_ref, acc_ref, *, activation: str):
    d = pl.program_id(3)

    @pl.when(d == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[0].astype(jnp.float32), w_ref[0].astype(jnp.float32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(d == pl.num_programs(3) - 1)
    def _finalize():
        acc = acc_ref[...]
        if activation == "silu":
            acc = acc * jax.nn.sigmoid(acc)
        o_ref[0] = acc.astype(o_ref.dtype)


def moe_grouped_gemm_kernel(xe, w, *, activation: str = "none",
                            bc: int = 128, bf: int = 128, bd: int = 128,
                            interpret: Optional[bool] = None):
    """xe: [E, C, D]; w: [E, D, F] -> [E, C, F] (optionally silu-activated)."""
    E, C, D = xe.shape
    _, _, F = w.shape
    bc, bf, bd = min(bc, C), min(bf, F), min(bd, D)
    assert C % bc == 0 and F % bf == 0 and D % bd == 0

    grid = (E, C // bc, F // bf, D // bd)
    return pl.pallas_call(
        functools.partial(_gemm_kernel, activation=activation),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bc, bd), lambda e, c, f, d: (e, c, d)),
            pl.BlockSpec((1, bd, bf), lambda e, c, f, d: (e, d, f)),
        ],
        out_specs=pl.BlockSpec((1, bc, bf), lambda e, c, f, d: (e, c, f)),
        out_shape=jax.ShapeDtypeStruct((E, C, F), xe.dtype),
        scratch_shapes=[pltpu.VMEM((bc, bf), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(xe, w)
