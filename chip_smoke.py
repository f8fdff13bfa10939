"""Smoke run of Foundry's serving main path on a TPU: SAVE -> LOAD -> serve.

    python3 chip_smoke.py             # one chip
    python3 chip_smoke.py --chips 4   # single-chip capture stamped onto TP=4

One chip: smollm-360m at its published widths (32 layers, d_model 960,
15/5 heads, d_ff 2560, vocab 49152, bf16; random weights from
``PRNGKey(--seed)``), built through ``repro.launch.serve.build`` with the
paged KV pool. The decode capture set is SAVEd to ``bench_out/``, a vanilla
engine compiles it and serves 8 seeded requests, a fresh engine cold-starts
by foundry LOAD of the archive and serves the same requests. The run fails
unless LOAD took the exact path with no fallback compile and no background
error, every request got its 32 tokens, every id is in the vocab, and the
foundry token streams equal the vanilla ones byte for byte.

``--chips 4``: SAVE on the one-chip capture mesh, then LOAD onto a TP=4 mesh
twice, once rank-stamped and once with stamping off (the compile-from-
StableHLO fallback); both serve the same requests and must agree. It prints
the devices that params, the KV pool and the decode step's outputs live on.

Runs in one process and starts none. It asks JAX for the backend first and
exits nonzero, printing no result, when that is not a TPU. The last line of
a passing run is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import random
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
ARCH = "smollm-360m"
MAX_BATCH, MAX_SEQ = 8, 1024
N_REQUESTS, PROMPT_LENS, NEW_TOKENS = 8, (64, 512), 32


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str):
    if not ok:
        raise SmokeFailure(what)


def log(msg: str):
    print(msg, flush=True)


def seeded_requests(seed: int, vocab: int, n: int = N_REQUESTS,
                    prompt_lens=PROMPT_LENS, new_tokens: int = NEW_TOKENS):
    rng = random.Random(seed)
    return [([rng.randrange(1, vocab)
              for _ in range(rng.randint(*prompt_lens))], new_tokens)
            for _ in range(n)]


def serve(eng, requests, label: str, after_first_step=None):
    """Submit ``requests`` and drain; returns the sorted token streams after
    checking that each request got its tokens and every id is in vocab.
    ``after_first_step(eng)`` runs once the first engine step is done."""
    for prompt, new in requests:
        eng.submit(prompt, new)
    t0 = time.perf_counter()
    steps = 0
    if after_first_step is not None:
        eng.step()
        steps = 1
        after_first_step(eng)
    steps += eng.run_until_drained()
    wall = time.perf_counter() - t0
    done, failed = eng.scheduler.done, eng.scheduler.failed
    log(f"[{label}] served {len(done)} requests in {wall:.3f}s over "
        f"{steps} engine steps ({eng.decode_steps} decode steps)")
    check(not failed, f"{label}: {len(failed)} requests failed")
    check(len(done) == len(requests),
          f"{label}: {len(done)} of {len(requests)} requests finished")
    vocab = eng.cfg.vocab_size
    for r in done:
        check(len(r.generated) == r.max_new_tokens,
              f"{label}: request {r.req_id} got {len(r.generated)} of "
              f"{r.max_new_tokens} tokens")
        check(all(0 <= t < vocab for t in r.generated),
              f"{label}: request {r.req_id} has ids outside [0, {vocab})")
    return sorted((r.req_id, tuple(r.generated)) for r in done)


def _phases(rep) -> str:
    return " ".join(f"{k}={v:.3f}s" for k, v in rep.phases.items())


def one_chip(arch: str, seed: int, archive_path: Path, *,
             max_seq: int = MAX_SEQ, requests=None) -> dict:
    """SAVE -> vanilla serve -> foundry LOAD serve on the default device."""
    from repro.core import Archive, wait_for_background
    from repro.launch.serve import build

    t0 = time.perf_counter()
    eng = build(arch, MAX_BATCH, max_seq, seed=seed)
    check(eng.kv_layout == "paged", f"kv_layout is {eng.kv_layout}")
    log(f"[build] {arch}: {eng.cfg.num_layers} layers, d_model "
        f"{eng.cfg.d_model}, vocab {eng.cfg.vocab_size}, "
        f"{eng.cfg.param_dtype}; max_batch {MAX_BATCH}, max_seq {max_seq}, "
        f"paged KV (block {eng.kv_block_size}, {eng.kv_blocks} blocks) "
        f"in {time.perf_counter() - t0:.3f}s")
    requests = requests or seeded_requests(seed, eng.cfg.vocab_size)

    t0 = time.perf_counter()
    _, srep = eng.save_archive(str(archive_path))
    log(f"[save] {archive_path} ({archive_path.stat().st_size} bytes, "
        f"{srep['specs']['decode']['n_templates']} templates over "
        f"{srep['specs']['decode']['n_buckets']} buckets) in "
        f"{time.perf_counter() - t0:.3f}s")
    del eng

    van = build(arch, MAX_BATCH, max_seq, seed=seed)
    crep = van.cold_start_vanilla()
    log(f"[vanilla] cold start {crep.total_s:.3f}s ({_phases(crep)}); "
        f"compiles may hit the persistent cache that SAVE just filled")
    want = serve(van, requests, "vanilla")
    del van

    eng = build(arch, MAX_BATCH, max_seq, seed=seed)
    t0 = time.perf_counter()
    archive = Archive.load(str(archive_path))
    crep = eng.cold_start_foundry(archive)
    log(f"[foundry] cold start {time.perf_counter() - t0:.3f}s incl. "
        f"archive open; LOAD phases: {_phases(crep)}")
    got = serve(eng, requests, "foundry")
    lrep = eng._load_report
    t0 = time.perf_counter()
    wait_for_background(lrep)
    log(f"[foundry] restore_path={lrep.restore_path} "
        f"fallback_compiles={lrep.fallback_compiles} "
        f"background_exact={lrep.background_exact} "
        f"background_errors={lrep.background_errors} "
        f"(background join {time.perf_counter() - t0:.3f}s)")
    check(lrep.restore_path == "exact",
          f"restore_path {lrep.restore_path}, want exact")
    check(lrep.fallback_compiles == 0,
          f"{lrep.fallback_compiles} fallback compiles")
    check(lrep.background_errors == 0,
          f"background errors: {lrep.background_first_error}")
    check(got == want, "foundry token streams differ from vanilla")
    log(f"[identity] foundry == vanilla over {len(got)} streams")
    return {"streams": got}


def _device_ids(tree) -> list:
    import jax
    return sorted({d.id for leaf in jax.tree.leaves(tree)
                   for d in leaf.devices()})


def four_chips(arch: str, seed: int, archive_path: Path, *,
               max_seq: int = MAX_SEQ, requests=None) -> dict:
    """One-chip capture, LOADed onto TP=4 stamped and by fallback compile."""
    import jax
    from repro.core import Archive, wait_for_background
    from repro.launch.mesh import make_capture_mesh, make_tp_mesh
    from repro.launch.serve import build

    check(len(jax.devices()) >= 4, f"{len(jax.devices())} devices, want 4")
    t0 = time.perf_counter()
    cap = build(arch, MAX_BATCH, max_seq, mesh=make_capture_mesh(), seed=seed)
    cap.save_archive(str(archive_path))
    log(f"[save] on capture mesh (device "
        f"{_device_ids(cap.params)}) in {time.perf_counter() - t0:.3f}s")
    requests = requests or seeded_requests(seed, cap.cfg.vocab_size)
    del cap

    streams = {}
    for label, stamping in (("stamped", True), ("fallback", False)):
        eng = build(arch, MAX_BATCH, max_seq, mesh=make_tp_mesh(4), seed=seed)
        t0 = time.perf_counter()
        crep = eng.cold_start_foundry(Archive.load(str(archive_path)),
                                      allow_stamping=stamping)
        log(f"[{label}] mode={crep.mode} cold start "
            f"{time.perf_counter() - t0:.3f}s rank_stamped={crep.rank_stamped}"
            f" fallback_compiles={crep.fallback_compiles}")
        def placement(eng, label=label):
            exe = eng.programs.lookup(eng.pool.cur_bucket)[1]
            recorded = exe._in_shardings  # ReshardingExecutable device_puts
            program_devs = sorted({d.id for s in jax.tree.leaves(recorded)
                                   for d in s.device_set})
            log(f"[{label}] after step 1: params on "
                f"{_device_ids(eng.params)}, KV pool on "
                f"{_device_ids(eng.pool.cache)}, decode outputs on "
                f"{_device_ids(eng._tokens_dev)}; the decode program's "
                f"input shardings name devices {program_devs}")
            moved = [p for p, s in zip(jax.tree.leaves(eng.params),
                                       jax.tree.leaves(recorded[0]))
                     if not p.sharding.is_equivalent_to(s, p.ndim)]
            log(f"[{label}] {len(moved)} of {len(jax.tree.leaves(eng.params))}"
                f" param arrays ({sum(p.nbytes for p in moved)} bytes) are "
                f"off the program's recorded sharding, so every decode step "
                f"device_puts them onto devices {program_devs}")

        streams[label] = serve(eng, requests, label, placement)
        wait_for_background(eng._load_report)
        if label == "stamped":
            check(crep.mode == "foundry-stamped", f"mode {crep.mode}")
            check(crep.fallback_compiles == 0,
                  f"{crep.fallback_compiles} fallback compiles when stamped")
        del eng
    check(streams["stamped"] == streams["fallback"],
          "stamped token streams differ from the fallback's")
    log(f"[identity] stamped == fallback over {len(streams['stamped'])} "
        f"streams")
    return streams


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: no TPU found (JAX backend is {backend!r})",
              file=sys.stderr)
        return 2
    dev = jax.devices()[0]
    log(f"[device] {dev.platform} {dev.device_kind} x {len(jax.devices())}")

    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.serve import configure_compile_cache
    cache_dir, empty = configure_compile_cache()
    log(f"[cache] {cache_dir} ({'empty' if empty else 'warm'} at start)")

    out = ROOT / "bench_out"
    out.mkdir(exist_ok=True)
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips(ARCH, args.seed, out / "chip_smoke_tp4.fndry")
    else:
        one_chip(ARCH, args.seed, out / "chip_smoke.fndry")
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    log(f"[done] {time.perf_counter() - t0:.3f}s; device 0 peak_bytes_in_use "
        f"{peak}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
