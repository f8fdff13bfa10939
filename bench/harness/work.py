"""Work a decode step needs, counted from the shapes and the live lengths.

The counts follow the algorithm, not the implementation: a row reads the
weights once per step, attends over its live positions only and writes one
new K/V position. Padded rows and positions past a row's length are not
work. So a program that reads every ``max_seq`` position of every row reads
low here by construction, and one that stops doing so reads higher.

``rows`` is the number of live rows of the step and ``ctx`` the sum over
them of the positions each attends to, the new one included.

The counts belong to the configuration's family module (``step_flops``,
``step_bytes``), kept with the benchmark; this module reaches them through
the sizes' ``family``. A family with routed experts counts, in bytes, the
weights of each expert held on this chip and reached by some row of the
step once, and, in FLOPs, ``top_k`` experts for every row; a family whose
cache holds a latent counts the latent's values a position, not per-head
K and V.
"""
from __future__ import annotations

from typing import Dict, Tuple


def step_flops(d, rows: int, ctx: int) -> int:
    return d.family.step_flops(d, rows, ctx)


def step_bytes(d, rows: int, ctx: int) -> int:
    return d.family.step_bytes(d, rows, ctx)


# The dense family's helpers, kept importable here only because
# test_bench_units.py calls them. They are not part of the family contract
# (spec.py): a family that does not define them raises AttributeError here,
# and nothing in the harness calls them.
def matmul_params(d) -> int:
    return d.family.matmul_params(d)


def param_bytes(d) -> int:
    return d.family.param_bytes(d)


def kv_bytes_per_position(d) -> int:
    return d.family.kv_bytes_per_position(d)


def least_time(d, peak: Dict, rows: int, ctx: int) -> Tuple[float, str]:
    """Seconds the chip needs at least, and which bound sets it."""
    t_flops = step_flops(d, rows, ctx) / peak["bf16_flops_per_s"]
    t_bytes = step_bytes(d, rows, ctx) / peak["hbm_bytes_per_s"]
    return (t_bytes, "memory") if t_bytes >= t_flops else (t_flops, "compute")
