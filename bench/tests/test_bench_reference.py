"""The plain float32 reference against the program, at a small size on the
CPU: LOAD -> decode-fill -> cached decode through the paged pool agree with
the reference's full forward pass, and the float8 control does not."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import benchtree
from harness import check, spec, system, weights

D0 = spec.dims(benchtree.TINY)
REF = spec.load_module(benchtree.BENCH / "configs" / "dense_gqa.py",
                       "dense_gqa")
# bf16 weights are exact in both; the program rounds activations to bf16
# (relative 2^-8) at each projection input and residual add, which puts
# its logits about 0.01 from float32 ones whose spread is about 0.33 here.
# 0.03 leaves room for that and still fails float8 (about 0.12 off).
LOGIT_TOL = 0.03


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return benchtree.make(tmp_path_factory.mktemp("benchtree"))


def _paged_logits(d, params, toks):
    """Logits of the program's paged decode step, fed one token at a time
    from an empty pool: decode-fill and then cached decode."""
    eng = system.engine("tiny", d, params)
    m = eng.model
    cache = m.init_cache_paged(1, d.max_seq, 9, d.kv_block_size)
    step = jax.jit(m.decode_step_paged)
    out = []
    for t in toks:
        cache, lg = step(params, cache, jnp.asarray([t], jnp.int32))
        out.append(np.asarray(lg[0, :d.vocab]))
    return np.stack(out)


@pytest.mark.parametrize("tied", [False, True])
def test_weights_remade_layer_by_layer_are_the_same_bits(tied):
    d = dataclasses.replace(D0, tied=tied)
    seed = 2**40 + 3
    params = weights.make(d, seed)
    layer = weights.layer_fn(d, seed)
    for i in range(d.layers):
        for k, v in layer(i).items():
            assert jnp.array_equal(params["layers"][k][i], v), (i, k)
    top = weights.top_of(d, seed)
    for k, v in top.items():
        assert jnp.array_equal(params[k], v), k


@pytest.mark.parametrize("tied", [False, True])
def test_paged_decode_matches_reference(tied):
    d = dataclasses.replace(D0, tied=tied)
    seed = 11
    params = weights.make(d, seed)
    toks = np.random.default_rng(seed).integers(1, d.vocab, 40).tolist()
    got = _paged_logits(d, params, toks)
    want = np.asarray(REF.logits(d, weights.layer_fn(d, seed),
                                 weights.top_of(d, seed), [toks])[0])[:40]
    assert np.abs(got - want).max() < LOGIT_TOL
    low = np.asarray(REF.logits(d, weights.layer_fn(d, seed),
                                weights.top_of(d, seed), [toks],
                                quant="fp8")[0])[:40]
    assert np.abs(low - want).max() > LOGIT_TOL


@pytest.mark.parametrize("seed", [5, 6, 7])
def test_loaded_program_passes_and_float8_control_fails(tree, seed):
    """A run of the tiny batch cell: the LOADed program's served tokens sit
    within the cell's limit of the reference's best; the tokens that the
    reference computed in float8 puts first, on the same sample, do not."""
    from harness.main import measure
    result, cell, reqs = measure("tiny.batch", seed, 2.0, False,
                                 bench=tree, require_tpu=False, cache=False)
    limit = benchtree.LIMITS["max_logit_gap"]
    assert result["correct"]
    assert result["checks"]["max_logit_gap"]["value"] <= limit
    control, n = check.gaps(cell, seed, reqs, "fp8")
    assert n > 20
    assert control > limit
