"""[Fig 11] Unique templates vs captured graphs per model.

Paper: 512 captured graphs collapse to 12-25 unique topologies (95-98%
served via on-demand update). Here topology keys are computed over jaxprs
traced against the production (16,16) mesh shape (AbstractMesh: no devices
needed for tracing) for buckets 1..512 — topology transitions come from
sharding-divisibility classes of the batch axis, the JAX counterpart of the
paper's "nearby batch sizes share a topology".
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.registry import get_arch
from repro.core import group_buckets, topology_key
from repro.core.templates import default_bucket_ladder
from repro.launch.mesh import ShardCtx
from repro.models.model import Model

ARCHS = ["qwen3-14b", "smollm-360m", "yi-9b", "moonshot-v1-16b-a3b"]


def _abstract_production_mesh():
    return jax.sharding.AbstractMesh((16, 16), ("data", "model"))


def template_count(arch: str, n_buckets: int = 512, max_seq: int = 64):
    mesh = _abstract_production_mesh()
    ctx = ShardCtx(mesh=mesh)
    cfg = get_arch(arch).reduced()
    m = Model(cfg, ctx)

    def step(p, c, t):
        return m.decode_step(p, c, t)

    keys = {}
    for b in default_bucket_ladder(n_buckets, "all"):
        cache = m.cache_specs(b, max_seq)
        tok = jax.ShapeDtypeStruct((b,), jnp.int32)
        keys[b] = topology_key(step, m.param_shapes(), cache, tok,
                               extra=("(16,16)",))
    groups = group_buckets(keys)
    return len(groups), len(keys)


def run():
    rows = []
    for arch in ARCHS:
        n_templates, n_buckets = template_count(arch, n_buckets=512)
        pct = 100.0 * (n_buckets - n_templates) / n_buckets
        rows.append((f"fig11.{arch}.templates", n_templates,
                     f"of_{n_buckets}_graphs,{pct:.1f}%_via_update"))
    return rows


if __name__ == "__main__":
    from benchmarks.common import emit
    emit(run(), figure="fig11_templates")
