"""Compile-only checks for a described TPU v5e chip (no chip attached).

The TPU compiler is installed with JAX and compiles for a topology that is
described rather than attached: it refuses what the chip would refuse
(block shapes off the (8, 128) tiling, too much fast memory, a program that
does not fit), which interpret mode on the CPU cannot show. Nothing runs, so
these tests say nothing about results or times.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and under several test workers only the worker given this file loads it.
The persistent compilation cache is off around the compiles (a compile for
a described chip is written to it but cannot be read back without one).
"""
import dataclasses
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, SingleDeviceSharding

from repro.configs.registry import get_arch
from repro.kernels import decode_attention
from repro.kernels.decode_attention import (decode_attention_kernel,
                                            decode_attention_paged_kernel)
from repro.kernels.moe_gemm import moe_grouped_gemm_kernel
from repro.kernels.ssm_scan import mamba1_scan_kernel
from repro.launch.mesh import ShardCtx
from repro.models.model import Model
from repro.serving.engine import ServingEngine


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no compiler logs
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, *shapes):
    """ShapeDtypeStructs placed on the described chip."""
    return [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]


def _compile(fn, args):
    return jax.jit(fn).lower(*args).compile()


@pytest.fixture
def mosaic(monkeypatch):
    """Kernels inside a traced program lower for the chip: this process's
    backend is the CPU, which would pick the interpreter."""
    monkeypatch.setattr(decode_attention, "resolve_interpret",
                        lambda interpret: False)


def _compile_served_step(sharding, cfg, max_batch, max_seq):
    """The captured decode step the server runs, at its largest bucket,
    with the paged pool of ``max_batch`` full rows plus scratch."""
    eng = ServingEngine(Model(cfg), max_batch=max_batch, max_seq=max_seq,
                        bucket_mode="pow2")
    assert eng.kv_layout == "paged"
    args = jax.tree.map(
        lambda sd: jax.ShapeDtypeStruct(sd.shape, sd.dtype, sharding=sharding),
        eng._decode_args(max_batch))
    assert args[1]["k"].shape[1] == max_batch * max_seq // 16 + 1
    compiled = jax.jit(eng._decode_fn(), donate_argnums=(1,)).lower(
        *args).compile()
    ma = compiled.memory_analysis()
    assert ma.argument_size_in_bytes + ma.temp_size_in_bytes < 16e9
    # the paged attention kernel, lowered by Mosaic inside the layer scan
    assert "tpu_custom_call" in compiled.as_text()


def test_served_paged_decode_step_smollm_360m(one_chip, mosaic):
    """smollm-360m at full depth and published widths, bf16, the chat
    replica's 32 rows x 2048 positions (4097 pool blocks)."""
    _compile_served_step(one_chip, get_arch("smollm-360m"), 32, 2048)


def test_served_paged_decode_step_yi_9b_l24(one_chip, mosaic):
    """yi-9b's widths at 24 of its 48 layers, bf16, 16 rows x 4096
    positions (4097 pool blocks)."""
    cfg = dataclasses.replace(get_arch("yi-9b"), num_layers=24)
    _compile_served_step(one_chip, cfg, 16, 4096)


def test_served_paged_decode_step_kv_sharded(topo, mosaic):
    """On TP=4 over the described 2x2 host, yi-9b's 4 kv heads shard the
    pool: the kernel runs per shard under shard_map (4 of its layers)."""
    mesh = Mesh(np.asarray(topo.devices).reshape(1, 4), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    cfg = dataclasses.replace(get_arch("yi-9b"), num_layers=4)
    model = Model(cfg, ShardCtx(mesh=mesh))
    assert model.kv_shard
    eng = ServingEngine(model, max_batch=16, max_seq=4096, bucket_mode="pow2")
    assert eng.kv_layout == "paged"
    compiled = jax.jit(eng._decode_fn(), donate_argnums=(1,)).lower(
        *eng._decode_args(16)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("arch", ["smollm-360m", "qwen3-14b"])
def test_paged_decode_kernel(one_chip, arch):
    c = get_arch(arch)
    B, max_seq, bs = 8, 1024, 16
    MB = max_seq // bs
    NB = B * MB + 1
    H, Hkv, Dh = c.num_heads, c.num_kv_heads, c.head_dim
    args = _on(one_chip, ((B, H, Dh), jnp.bfloat16),
               ((NB, bs, Hkv, Dh), jnp.bfloat16),
               ((NB, bs, Hkv, Dh), jnp.bfloat16),
               ((B, MB), jnp.int32), ((B,), jnp.int32))
    compiled = _compile(
        functools.partial(decode_attention_paged_kernel, interpret=False),
        args)
    assert "tpu_custom_call" in compiled.as_text()


def test_contiguous_decode_kernel(one_chip):
    c = get_arch("smollm-360m")
    B, S = 8, 1024
    H, Hkv, Dh = c.num_heads, c.num_kv_heads, c.head_dim
    args = _on(one_chip, ((B, H, Dh), jnp.bfloat16),
               ((B, S, Hkv, Dh), jnp.bfloat16),
               ((B, S, Hkv, Dh), jnp.bfloat16), ((B,), jnp.int32))
    compiled = _compile(
        functools.partial(decode_attention_kernel, blk=512, interpret=False),
        args)
    assert "tpu_custom_call" in compiled.as_text()


def test_moe_grouped_gemm_kernel(one_chip):
    c = get_arch("moonshot-v1-16b-a3b")
    E, C, D, F = c.num_experts, 128, c.d_model, c.d_ff
    args = _on(one_chip, ((E, C, D), jnp.bfloat16), ((E, D, F), jnp.bfloat16))
    compiled = _compile(
        functools.partial(moe_grouped_gemm_kernel, activation="silu",
                          bc=128, bf=128, bd=128, interpret=False), args)
    assert "tpu_custom_call" in compiled.as_text()


def test_mamba1_scan_kernel(one_chip):
    c = get_arch("falcon-mamba-7b")
    B, T, C, N = 1, 128, c.d_inner, c.ssm_state
    args = _on(one_chip, ((B, T, C), jnp.bfloat16), ((B, T, C), jnp.bfloat16),
               ((B, T, N), jnp.bfloat16), ((B, T, N), jnp.bfloat16),
               ((C, N), jnp.float32))
    compiled = _compile(
        functools.partial(mamba1_scan_kernel, c_blk=128, t_chunk=16,
                          interpret=False), args)
    assert "tpu_custom_call" in compiled.as_text()
