"""The system under test, as the benchmark drives it.

This is the only module that imports the program (``src/repro``). It builds
the program's ``ArchConfig`` from a configuration file's sizes, hands the
benchmark's weights to a ``ServingEngine``, SAVEs the decode capture set
and cold-starts engines from it by foundry LOAD.
"""
from __future__ import annotations

import gc
import os
import sys
from pathlib import Path

from harness.spec import Dims


def use_program(root: Path):
    """Put the checkout's ``src`` on the import path; the program is then
    imported lazily by the functions below."""
    src = str(root / "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def configure_cache(root: Path) -> tuple:
    """JAX's persistent compilation cache in ``<checkout>/.jax_cache``:
    one fixed path inside the checkout, handed to the program's own
    ``configure_compile_cache`` through the variable it reads. Every
    program is written to it, however quickly it compiled, so that only a
    checkout's first run compiles."""
    import jax
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from repro.launch.serve import configure_compile_cache
    return configure_compile_cache()


def arch_config(name: str, d: Dims):
    from repro.configs.base import ArchConfig
    return ArchConfig(
        name=name, family="dense", num_layers=d.layers, d_model=d.d_model,
        num_heads=d.heads, num_kv_heads=d.kv_heads, head_dim=d.head_dim,
        d_ff=d.d_ff, vocab_size=d.vocab, tie_embeddings=d.tied,
        rope_theta=d.rope_theta, norm_eps=d.norm_eps, param_dtype=d.dtype)


def engine(name: str, d: Dims, params):
    """A fresh engine of the configuration's shape over ``params``."""
    import jax
    from repro.models.model import Model
    from repro.serving.engine import ServingEngine
    model = Model(arch_config(name, d))
    want = jax.tree.map(lambda s: (s.shape, str(s.dtype)),
                        model.param_shapes())
    got = jax.tree.map(lambda a: (a.shape, str(a.dtype)), params)
    if want != got:
        raise ValueError(f"the benchmark's weights do not match the "
                         f"program's parameter tree: {got} vs {want}")
    eng = ServingEngine(model, max_batch=d.max_batch, max_seq=d.max_seq,
                        bucket_mode="pow2", kv_block_size=d.kv_block_size)
    eng.load_weights(params)
    return eng


def save(name: str, d: Dims, params, path: Path) -> dict:
    """SAVE the decode capture set of the configuration's engine. The
    engine's KV pool is freed before this returns."""
    eng = engine(name, d, params)
    _, rep = eng.save_archive(str(path))
    del eng
    gc.collect()  # the engine and its pool refer to each other
    return rep


def cold_start(name: str, d: Dims, params, path: Path):
    """Open the archive as a new object, build a new engine and LOAD it.
    Returns the engine and its ``LoadReport``."""
    from repro.core import Archive
    archive = Archive.load(str(path))
    eng = engine(name, d, params)
    eng.cold_start_foundry(archive)
    return eng, eng._load_report


def join_background(report) -> None:
    from repro.core import wait_for_background
    wait_for_background(report)
