"""The system under test, as the benchmark drives it.

This is the only module that imports the program (``src/repro``). It builds
the program's ``ArchConfig`` from the keyword arguments that the
configuration's family module gives, hands the benchmark's weights to a
``ServingEngine`` after checking them against the program's parameter tree,
SAVEs the decode capture set and cold-starts engines from it by foundry
LOAD.

A family module (``d.family``; ``harness.spec`` states the contract)
provides ``dims``, ``program_config``, ``top_shapes``, ``layer_groups``,
``step_flops``, ``step_bytes`` and the reference ``logits``. This module
uses ``program_config(name, d)`` alone, and of the sizes ``d`` only
``max_batch``, ``max_seq`` and ``kv_block_size``.
"""
from __future__ import annotations

import gc
import os
import sys
import time
from pathlib import Path


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


def arch_config(name: str, d):
    from repro.configs.base import ArchConfig
    return ArchConfig(**d.family.program_config(name, d))


def engine(name: str, d, params):
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


def save(name: str, d, params, path: Path) -> dict:
    """SAVE the decode capture set of the configuration's engine. The
    engine's KV pool is freed before this returns."""
    eng = engine(name, d, params)
    _, rep = eng.save_archive(str(path))
    del eng
    gc.collect()  # the engine and its pool refer to each other
    return rep


def cold_start(name: str, d, params, path: Path, times=None):
    """Open the archive as a new object, build a new engine and LOAD it.
    Returns the engine and its ``LoadReport``; appends the host seconds
    of the three parts to ``times`` when it is given."""
    from repro.core import Archive
    t0 = time.perf_counter()
    archive = Archive.load(str(path))
    t1 = time.perf_counter()
    eng = engine(name, d, params)
    t2 = time.perf_counter()
    eng.cold_start_foundry(archive)
    if times is not None:
        times.extend((t1 - t0, t2 - t1, time.perf_counter() - t2))
    return eng, eng._load_report


def join_background(report) -> None:
    from repro.core import wait_for_background
    wait_for_background(report)
