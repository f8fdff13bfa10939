"""A run whose timed path is broken underneath comes out not correct.

Each fault a serving cell on one chip can have is planted in the LOADed
decode program as the engine dispatches it, and a tiny cell is run on the
CPU with everything else as a benchmark run does it: a step that returns
its state unchanged, half of the batch left out, and a token altered where
it is produced. (A one-chip cell has no exchange between chips to leave
out.)"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

import benchtree


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return benchtree.make(tmp_path_factory.mktemp("benchtree"))


def _broken(exe, fault: str, vocab: int):
    def call(params, cache, tokens):
        if fault == "state_unchanged":
            kept = jax.tree.map(jnp.copy, cache)
        new_cache, ids = exe(params, cache, tokens)
        if fault == "state_unchanged":
            return kept, ids
        if fault == "half_batch":  # rows past the first half never computed
            return new_cache, ids.at[(ids.shape[0] + 1) // 2:].set(0)
        return new_cache, (ids + 1) % vocab  # token altered where produced
    return call


FAULTS = ["state_unchanged", "half_batch", "token_altered"]


# a coldstart probe is one row, so it has no half of a batch to leave out
@pytest.mark.parametrize("workload,fault", [
    *(("tiny.batch", f) for f in FAULTS),
    ("tiny.coldstart", "state_unchanged"),
    ("tiny.coldstart", "token_altered"),
])
def test_broken_step_is_not_correct(tree, monkeypatch, workload, fault):
    from repro.core.templates import ProgramSet
    vocab = benchtree.TINY["vocab_size"]
    lookup = ProgramSet.lookup

    def broken_lookup(self, n_active):
        bucket, exe, path = lookup(self, n_active)
        return bucket, _broken(exe, fault, vocab), path

    monkeypatch.setattr(ProgramSet, "lookup", broken_lookup)
    r = benchtree.run(tree, workload, seed=13)
    assert not r["correct"]
    assert r["checks"]["max_logit_gap"]["value"] > \
        r["checks"]["max_logit_gap"]["limit"]
