"""Dynamic parallelism hot-switch from ONE single-capture archive
(paper §2.1, §4.2.2, §4.3).

    PYTHONPATH=src python examples/parallelism_switch.py

Parallelism reconfiguration (EP2 -> EP4 style) normally forces a full graph
recapture. With Foundry rank stamping, a SINGLE archive — captured offline on
a 1-device topology — serves *every* shape-compatible deployment: LOAD
reuses the archived template program byte-identically and stamps only
rank-dependent communication state (peer tables, mesh coordinates,
rank-relative buffer offsets) for the deployment mesh.

This example is a CPU tool and runs on 8 CPU placeholder devices (it pins
itself to the CPU backend before JAX loads, so it never takes an
accelerator): one offline SAVE on the single-device capture mesh, then the same engine *process* serves a (2,4)
data x model mesh and hot-switches to a (4,2) mesh — both cold starts are
rank-stamped LOADs of the one archive (``fallback_compiles == 0``), and
in-flight requests keep their generated prefixes across the switch (the
thing process-level checkpoint/restore cannot do, §2.3).
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = " ".join(filter(None, (
    os.environ.get("XLA_FLAGS"), "--xla_force_host_platform_device_count=8")))

import time  # noqa: E402

import jax  # noqa: E402

from repro.configs.registry import get_arch  # noqa: E402
from repro.launch.mesh import ShardCtx, make_capture_mesh, make_mesh  # noqa: E402
from repro.models.model import Model  # noqa: E402
from repro.serving.engine import ServingEngine  # noqa: E402


def build_engine(mesh):
    cfg = get_arch("smollm-360m").reduced()
    model = Model(cfg, ShardCtx(mesh=mesh))
    eng = ServingEngine(model, max_batch=8, max_seq=64, bucket_mode="pow2")
    return eng


def main():
    mesh_cap = make_capture_mesh()                 # 1 device, offline
    mesh_a = make_mesh((2, 4), ("data", "model"))  # deployment A
    mesh_b = make_mesh((4, 2), ("data", "model"))  # deployment B

    # offline: ONE capture on ONE device serves every deployment shape
    print("== offline SAVE on the single-device capture mesh ==")
    with mesh_cap:
        eng = build_engine(mesh_cap)
        eng.load_weights(rng=jax.random.PRNGKey(0))
        archive, rep = eng.save_archive(verbose=True)

    print("\n== serve on 2x4 (rank-stamped LOAD), then hot-switch to 4x2 ==")
    with mesh_a:
        eng = build_engine(mesh_a)
        eng.load_weights(rng=jax.random.PRNGKey(0))
        t0 = time.perf_counter()
        cs = eng.cold_start_foundry(archive, background_exact=False)
        print(f"cold start (2x4): {(time.perf_counter() - t0) * 1e3:.1f} ms "
              f"mode={cs.mode} rank_stamped={cs.rank_stamped} "
              f"fallback_compiles={cs.fallback_compiles}")
        assert cs.mode == "foundry-stamped" and cs.fallback_compiles == 0
        reqs = [eng.submit([3 + i, 5, 7], 10) for i in range(5)]
        for _ in range(4):
            eng.step()
        prefix_lens = {r.req_id: len(r.generated) for r in reqs}
        print(f"in-flight after 4 steps: "
              f"{[(r.req_id, len(r.generated)) for r in reqs]}")

    # ---- the switch: new mesh, SAME archive, SAME request state ----
    t0 = time.perf_counter()
    with mesh_b:
        eng2 = build_engine(mesh_b)
        eng2.load_weights(rng=jax.random.PRNGKey(0))  # reshard (RDMA-class)
        cs2 = eng2.cold_start_foundry(archive, background_exact=False)
        assert cs2.mode == "foundry-stamped" and cs2.fallback_compiles == 0
        # migrate scheduler state: requests keep their generated prefixes
        eng2.scheduler = eng.scheduler
        for r in list(eng2.scheduler.running.values()):
            eng2.scheduler.requeue_on_failure(r)
            r.retries = 0  # a planned switch is not a failure
        t_switch = time.perf_counter() - t0
        print(f"parallelism switch to 4x2: {t_switch * 1e3:.1f} ms "
              f"(rank-stamped LOAD of the same archive, no recapture; "
              f"rank_stamped={cs2.rank_stamped})")
        eng2.run_until_drained()

    done = {r.req_id: r for r in eng2.scheduler.done}
    assert len(done) == 5
    kept = all(len(done[i].generated) >= prefix_lens[i] for i in done)
    print(f"all 5 requests finished on the new mesh; "
          f"prefixes preserved: {kept}")
    for r in sorted(done.values(), key=lambda r: r.req_id):
        print(f"  req {r.req_id}: {len(r.generated)} tokens")


if __name__ == "__main__":
    main()
