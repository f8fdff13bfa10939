"""Work a decode step needs, counted from the shapes and the live lengths.

The counts follow the algorithm, not the implementation: a row reads the
weights once per step, attends over its live positions only and writes one
new K/V position. Padded rows and positions past a row's length are not
work. So a program that reads every ``max_seq`` position of every row reads
low here by construction, and one that stops doing so reads higher.

``rows`` is the number of live rows of the step and ``ctx`` the sum over
them of the positions each attends to, the new one included.
"""
from __future__ import annotations

from typing import Dict, Tuple

from harness.spec import Dims


def matmul_params(d: Dims) -> int:
    """Weights one row multiplies by: every layer's projections and the
    output head (the embedding lookup is a gather, not a product)."""
    D, F = d.d_model, d.d_ff
    q, kv = d.heads * d.head_dim, d.kv_heads * d.head_dim
    per_layer = D * q + 2 * D * kv + q * D + 3 * D * F
    return d.layers * per_layer + D * d.vocab


def param_bytes(d: Dims) -> int:
    """Bytes of weights one step reads: every layer, the final norm and the
    output head. With an untied head the embedding table is read only at
    the rows' tokens, which ``step_bytes`` adds."""
    D = d.d_model
    per_layer = matmul_params(d) - D * d.vocab
    norms = d.layers * 2 * D + D
    return (per_layer + norms + D * d.vocab) * d.dtype_bytes


def kv_bytes_per_position(d: Dims) -> int:
    return d.layers * 2 * d.kv_heads * d.head_dim * d.dtype_bytes


def step_flops(d: Dims, rows: int, ctx: int) -> int:
    """2 x weights per row, plus QK^T and PV over the live positions."""
    attn = 4 * d.layers * d.heads * d.head_dim * ctx
    return 2 * rows * matmul_params(d) + attn


def step_bytes(d: Dims, rows: int, ctx: int) -> int:
    """Weights once, the K/V of the live positions (the new position is
    written and read), and the embedding rows of an untied table."""
    gather = 0 if d.tied else rows * d.d_model * d.dtype_bytes
    return param_bytes(d) + kv_bytes_per_position(d) * ctx + gather


def least_time(d: Dims, peak: Dict, rows: int, ctx: int) -> Tuple[float, str]:
    """Seconds the chip needs at least, and which bound sets it."""
    t_flops = step_flops(d, rows, ctx) / peak["bf16_flops_per_s"]
    t_bytes = step_bytes(d, rows, ctx) / peak["hbm_bytes_per_s"]
    return (t_bytes, "memory") if t_bytes >= t_flops else (t_flops, "compute")
