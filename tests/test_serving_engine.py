"""Serving engine end-to-end on CPU: vanilla vs foundry vs eager cold starts
produce identical tokens; continuous batching; failure re-queue."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_arch
from repro.core import Archive, wait_for_background
from repro.obs import trace
from repro.models.model import Model
from repro.serving.engine import ServingEngine


def make_engine(**kw):
    cfg = get_arch("smollm-360m").reduced()
    m = Model(cfg)
    eng = ServingEngine(m, max_batch=8, max_seq=64, bucket_mode="pow2", **kw)
    eng.load_weights(rng=jax.random.PRNGKey(7))
    return eng


def serve_tokens(eng, prompts, n_new=6):
    reqs = [eng.submit(p, n_new) for p in prompts]
    eng.run_until_drained()
    assert all(r.state.value == "done" for r in reqs)
    return [tuple(r.generated) for r in reqs]


PROMPTS = [[5, 9, 2], [11, 3], [7, 7, 7, 1], [2], [13, 4, 9, 9, 1, 2]]


def test_vanilla_serving_and_batching():
    eng = make_engine()
    rep = eng.cold_start_vanilla()
    assert rep.n_templates >= 1
    outs = serve_tokens(eng, PROMPTS)
    assert all(len(o) == 6 for o in outs)
    assert eng.scheduler.pending == 0


def test_foundry_cold_start_token_identity(tmp_path):
    # SAVE with one engine, LOAD with a fresh one; tokens must be identical
    eng1 = make_engine()
    archive, save_rep = eng1.save_archive()
    assert save_rep["specs"]["decode"]["n_templates"] < len(eng1.buckets)
    eng1.cold_start_vanilla()
    ref = serve_tokens(eng1, PROMPTS)

    eng2 = make_engine()
    rep = eng2.cold_start_foundry(archive)
    assert rep.n_templates == save_rep["specs"]["decode"]["n_templates"]
    out = serve_tokens(eng2, PROMPTS)
    assert out == ref, "foundry-restored engine diverged from vanilla"

    # foundry cold start must be much cheaper than vanilla capture
    assert rep.phases["templates_s"] >= 0


def test_eager_matches_vanilla():
    eng1 = make_engine()
    eng1.cold_start_vanilla()
    ref = serve_tokens(eng1, PROMPTS[:3])
    eng2 = make_engine()
    eng2.cold_start_eager()
    out = serve_tokens(eng2, PROMPTS[:3])
    assert out == ref


def test_failure_requeue_completes():
    eng = make_engine()
    eng.cold_start_vanilla()
    reqs = [eng.submit(p, 6) for p in PROMPTS]
    eng.step()
    eng.step()
    eng.simulate_worker_failure()  # drops running work, keeps prefixes
    eng.run_until_drained()
    assert all(r.state.value == "done" for r in reqs)
    assert all(len(r.generated) >= 6 for r in reqs)
    assert any(r.retries > 0 for r in reqs)


def test_background_exact_swap(tmp_path):
    eng = make_engine()
    archive, _ = eng.save_archive()
    eng2 = make_engine()
    eng2.cold_start_foundry(archive, background_exact=True)
    wait_for_background(eng2._load_report)
    cov = eng2.programs.coverage()
    assert cov["exact_loaded"] > 0
    # a systematically failing background compile must be visible, not
    # swallowed: the happy path reports zero errors
    assert eng2._load_report.background_errors == 0
    assert eng2._load_report.background_first_error is None
    serve_tokens(eng2, PROMPTS[:2])


def test_oversized_prompt_rejected_cleanly():
    """A prompt that cannot fit max_seq used to raise a broadcast ValueError
    inside step() and wedge the request in `running` forever; it must fail
    cleanly through the scheduler while other traffic proceeds."""
    eng = make_engine()
    eng.cold_start_vanilla()
    ok = eng.submit([1, 2, 3], 4)
    too_long = eng.submit(list(range(1, 80)), 4)       # 79 tokens > max_seq=64
    exactly_max = eng.submit(list(range(1, 65)), 4)    # 64 == max_seq: no room
    eng.run_until_drained()
    assert too_long.state.value == "failed"
    assert "max_seq" in too_long.fail_reason
    assert exactly_max.state.value == "failed"
    assert too_long.req_id not in eng.scheduler.running
    assert too_long in eng.scheduler.failed
    assert ok.state.value == "done" and len(ok.generated) == 4
    assert eng.scheduler.pending == 0


def test_boundary_prompt_still_served():
    """max_seq - 1 prompt tokens leaves room for exactly one generated token
    and must be admitted, not rejected."""
    eng = make_engine()
    eng.cold_start_vanilla()
    edge = eng.submit(list(range(1, 64)), 4)  # 63 == max_seq - 1
    eng.run_until_drained()
    assert edge.state.value == "done"
    assert len(edge.generated) >= 1


def test_multi_completion_slot_compaction():
    """Two+ requests finishing in the same step(): after release+compaction
    every surviving request's slot must still point at its own KV row (the
    moved_id repair in ServingEngine.step). Pinned to the slot layout whose
    device row-compaction it exercises (and whose one-shot prefill the step
    counts assume); the paged layout's compaction is covered by
    tests/test_prefix_cache.py and the blockpool property suite."""
    eng = make_engine(kv_layout="slot")
    eng.cold_start_vanilla()
    short = [eng.submit(p, 3) for p in ([5, 9, 2], [11, 3], [7, 7, 7, 1])]
    long = [eng.submit(p, 8) for p in ([2, 4], [13, 4, 9])]
    for _ in range(3):  # all 5 admitted at once; short ones finish together
        eng.step()
    assert all(r.state.value == "done" for r in short)
    for r in long:
        assert r.state.value == "running"
        assert eng.pool.slots[r.slot] == r.req_id, \
            f"request {r.req_id} slot {r.slot} points at someone else's row"
    eng.run_until_drained()
    assert all(r.state.value == "done" and len(r.generated) == 8 for r in long)


def test_pool_shrink_during_release_keeps_slots_valid():
    """A mass completion shrinks the pool bucket (hysteresis) while a
    survivor is still decoding; its slot must survive the shrink. Slot
    layout pinned — the step counts assume one-shot prefill."""
    eng = make_engine(kv_layout="slot")
    eng.cold_start_vanilla()
    many = [eng.submit([3, 1, 4], 2) for _ in range(5)]
    survivor = eng.submit([2, 7], 9)
    for _ in range(2):
        eng.step()
    assert all(r.state.value == "done" for r in many)
    assert eng.pool.cur_bucket < 8  # pool shrank under the survivor
    assert survivor.state.value == "running"
    assert eng.pool.slots[survivor.slot] == survivor.req_id
    eng.run_until_drained()
    assert survivor.state.value == "done" and len(survivor.generated) == 9


def test_paged_kernel_exact_load_matches_vanilla(monkeypatch):
    """The paged step with the attention kernel inside it is SAVEd and
    exact-LOADed with no compile and serves the vanilla engine's tokens;
    ``engine.dispatch`` reports the blocks attention reads, counted here
    from the device tables and lengths each dispatch hands the program."""
    eng1 = make_engine()
    archive, _ = eng1.save_archive()
    eng1.cold_start_vanilla()
    ref = serve_tokens(eng1, PROMPTS)

    eng2 = make_engine()
    rep = eng2.cold_start_foundry(Archive.from_bytes(archive.to_bytes()),
                                  background_exact=False)
    assert rep.mode == "foundry" and rep.fallback_compiles == 0
    assert eng2.kv_layout == "paged"
    pool, seen, program = eng2.pool, [], eng2._program

    def recording(bucket):
        exec_bucket, exe = program(bucket)

        def run(params, cache, toks):
            bt = np.asarray(cache["block_tables"])
            ln = np.asarray(cache["lengths"])
            rows = [i for i, s in enumerate(pool.slots) if s is not None]
            n = [min(bt.shape[1], ln[i] // pool.block_size + 1) for i in rows]
            # an active row's attended blocks are allocated, not scratch
            assert all(bt[i, :k].all() for i, k in zip(rows, n))
            seen.append((sum(n), bt.size))
            return exe(params, cache, toks)
        return exec_bucket, run

    monkeypatch.setattr(eng2, "_program", recording)
    trace.start()
    try:
        out = serve_tokens(eng2, PROMPTS)
    finally:
        doc = trace.stop().to_dict()
    assert out == ref, "exact-LOADed paged program diverged from vanilla"
    got = [(ev["args"]["kv_live_blocks"], ev["args"]["kv_table_blocks"])
           for ev in trace.spans_named(doc, "engine.dispatch")]
    assert got == seen and len(got) == eng2.decode_steps
    assert all(live <= table for live, table in got)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_paged_decode_logits_match_slot_path(dtype):
    """Cached decode over the paged pool (the kernel, two chunks a row)
    against the slot layout's dense attention, step by step from rows at
    different lengths. Tolerance: float32 scores and softmax on both
    paths, summed in another order (online over chunks vs one softmax),
    so float32 agrees to 1e-4; bfloat16 rounds K, V and every activation
    on both paths, and the two round differently once the sums differ."""
    cfg = dataclasses.replace(get_arch("smollm-360m").reduced(),
                              param_dtype=dtype)
    m = Model(cfg)
    params = m.init(jax.random.PRNGKey(3))
    B, S, bs = 3, 256, 16
    start = jnp.asarray([0, 100, 120], jnp.int32)
    slot = {**m.init_cache(B, S), "lengths": start}
    paged = {**m.init_cache_paged(B, S, B * S // bs + 1, bs),
             "lengths": start}
    slot_step, paged_step = jax.jit(m.decode_step), jax.jit(m.decode_step_paged)
    tol = 1e-4 if dtype == "float32" else 5e-2
    toks = jax.random.randint(jax.random.PRNGKey(4), (24, B), 0,
                              cfg.vocab_size)
    for t in range(toks.shape[0]):  # rows 1 and 2 cross position 128
        slot, want = slot_step(params, slot, toks[t])
        paged, got = paged_step(params, paged, toks[t])
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=tol, atol=tol)
