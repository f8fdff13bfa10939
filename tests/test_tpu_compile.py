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
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.registry import get_arch
from repro.kernels.decode_attention import (decode_attention_kernel,
                                            decode_attention_paged_kernel)
from repro.kernels.moe_gemm import moe_grouped_gemm_kernel
from repro.kernels.ssm_scan import mamba1_scan_kernel
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


def test_served_paged_decode_step_smollm_360m(one_chip):
    """The captured decode step the server runs: smollm-360m at full depth
    and published widths, bf16, max_batch 8, max_seq 1024, paged pool."""
    eng = ServingEngine(Model(get_arch("smollm-360m")), max_batch=8,
                        max_seq=1024, bucket_mode="pow2")
    assert eng.kv_layout == "paged"
    args = jax.tree.map(
        lambda sd: jax.ShapeDtypeStruct(sd.shape, sd.dtype, sharding=one_chip),
        eng._decode_args(8))
    compiled = jax.jit(eng._decode_fn(), donate_argnums=(1,)).lower(
        *args).compile()
    ma = compiled.memory_analysis()
    assert ma.argument_size_in_bytes + ma.temp_size_in_bytes < 16e9


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
