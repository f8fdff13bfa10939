"""Per-kernel validation: shape/dtype sweeps vs the pure-jnp oracles in
repro.kernels.ref (interpret mode on a CPU backend, compiled on a TPU), plus
kernel-catalog behaviour."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.kernel_catalog import KernelCatalog
from repro.kernels import ops, ref
from repro.kernels.decode_attention import (CHUNK_POSITIONS,
                                            decode_attention_kernel,
                                            decode_attention_paged_kernel)
from repro.kernels.moe_gemm import moe_grouped_gemm_kernel
from repro.kernels.ssm_scan import mamba1_scan_kernel

RTOL = {jnp.float32: 1e-5, jnp.bfloat16: 2e-2}
ATOL = {jnp.float32: 1e-5, jnp.bfloat16: 2e-2}


def _tols(dtype):
    return dict(rtol=RTOL[dtype], atol=ATOL[dtype])


class TestDecodeAttention:
    @pytest.mark.parametrize("B,S,H,Hkv,Dh,blk", [
        (2, 256, 8, 2, 64, 128),
        (1, 512, 4, 4, 128, 256),   # MHA
        (3, 128, 8, 1, 64, 128),    # MQA
        (2, 256, 16, 4, 128, 256),
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_ref(self, B, S, H, Hkv, Dh, blk, dtype):
        k = jax.random.PRNGKey(0)
        ks = jax.random.split(k, 4)
        q = jax.random.normal(ks[0], (B, H, Dh), dtype)
        kc = jax.random.normal(ks[1], (B, S, Hkv, Dh), dtype)
        vc = jax.random.normal(ks[2], (B, S, Hkv, Dh), dtype)
        lengths = jax.random.randint(ks[3], (B,), 1, S - 1)
        out = decode_attention_kernel(q, kc, vc, lengths, blk=blk)
        want = ref.decode_attention_ref(q, kc, vc, lengths)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(want, np.float32),
            **_tols(dtype))

    def test_mask_respects_length(self):
        """Tokens beyond lengths[b] must not affect the output."""
        B, S, H, Hkv, Dh = 1, 128, 4, 2, 64
        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        q = jax.random.normal(ks[0], (B, H, Dh), jnp.float32)
        kc = jax.random.normal(ks[1], (B, S, Hkv, Dh), jnp.float32)
        vc = jax.random.normal(ks[2], (B, S, Hkv, Dh), jnp.float32)
        lengths = jnp.asarray([40])
        out1 = decode_attention_kernel(q, kc, vc, lengths, blk=64)
        kc2 = kc.at[:, 41:].set(999.0)
        vc2 = vc.at[:, 41:].set(-999.0)
        out2 = decode_attention_kernel(q, kc2, vc2, lengths, blk=64)
        np.testing.assert_allclose(np.asarray(out1), np.asarray(out2),
                                   rtol=1e-6)


class TestPagedDecodeAttention:
    """Block-table indirected flash-decode vs the gather-then-attend oracle
    and the contiguous kernel (the two must agree on identical logical
    content regardless of physical block placement)."""

    @staticmethod
    def _rand_pool(key, B, MB, bs, Hkv, Dh, dtype, n_spare=3):
        """Pool + per-sequence tables of distinct physical blocks, shuffled
        so logical order != physical order; block 0 reserved scratch."""
        NB = 1 + B * MB + n_spare
        ks = jax.random.split(key, 3)
        kp = jax.random.normal(ks[0], (NB, bs, Hkv, Dh), dtype)
        vp = jax.random.normal(ks[1], (NB, bs, Hkv, Dh), dtype)
        perm = np.asarray(jax.random.permutation(ks[2], NB - 1)) + 1
        tables = jnp.asarray(perm[:B * MB].reshape(B, MB), jnp.int32)
        return kp, vp, tables

    @pytest.mark.parametrize("B,MB,bs,H,Hkv,Dh,lengths", [
        pytest.param(*case, "random", id="-".join(map(str, case)))
        for case in [
            (2, 4, 64, 8, 2, 64),
            (1, 2, 256, 4, 4, 128),   # MHA
            (3, 8, 16, 8, 1, 64),     # MQA, small blocks
            (2, 4, 64, 16, 4, 128),
        ]] + [
        (8, 24, 16, 15, 5, 64, "edges"),    # smollm-360m widths, G = 3
        (8, 24, 16, 32, 4, 128, "edges"),   # yi-9b widths, G = 8
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_ref(self, B, MB, bs, H, Hkv, Dh, lengths, dtype):
        ks = jax.random.split(jax.random.PRNGKey(10), 3)
        q = jax.random.normal(ks[0], (B, H, Dh), dtype)
        kp, vp, tables = self._rand_pool(ks[1], B, MB, bs, Hkv, Dh, dtype)
        if lengths == "random":
            lengths = jax.random.randint(ks[2], (B,), 1, MB * bs - 1)
        else:
            # 0, bs-1, bs, both sides of the first chunk edge, MB*bs-1; then
            # two padded rows, whose every entry names the scratch block 0
            # (an inactive row's table) and whose lengths run on in-graph
            lengths = jnp.asarray([0, bs - 1, bs, CHUNK_POSITIONS - 1,
                                   CHUNK_POSITIONS, MB * bs - 1, 0, 2 * bs + 5])
            tables = tables.at[-2:].set(0)
        out = decode_attention_paged_kernel(q, kp, vp, tables, lengths)
        want = ref.decode_attention_paged_ref(q, kp, vp, tables, lengths)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(want, np.float32),
            **_tols(dtype))

    @pytest.mark.parametrize("bs,H,Hkv,Dh", [
        (16, 15, 5, 64),    # smollm-360m widths
        (16, 32, 4, 128),   # yi-9b widths
    ])
    def test_blocks_past_the_live_ones_are_never_read(self, bs, H, Hkv, Dh):
        """Every pool block no row attends (scratch block 0 included, the
        tail entries of each table point there) is NaN. A read of any of
        them would reach the output through V (probability 0 times NaN):
        masking the scores is not enough, the blocks must not be copied."""
        B, MB = 5, 24
        ks = jax.random.split(jax.random.PRNGKey(14), 2)
        q = jax.random.normal(ks[0], (B, H, Dh), jnp.float32)
        kp, vp, tables = self._rand_pool(ks[1], B, MB, bs, Hkv, Dh,
                                         jnp.float32)
        lengths = np.asarray([0, bs - 1, CHUNK_POSITIONS,
                              CHUNK_POSITIONS + 3 * bs, MB * bs - 1])
        n_live = np.minimum(lengths // bs + 1, MB)
        tables = np.array(tables)
        for b in range(B):
            tables[b, n_live[b]:] = 0
        live = {int(x) for b in range(B) for x in tables[b, :n_live[b]]}
        dead = np.asarray([i for i in range(kp.shape[0]) if i not in live])
        assert 0 in dead and len(dead) > B
        tables, lengths = jnp.asarray(tables), jnp.asarray(lengths)
        clean = decode_attention_paged_kernel(q, kp, vp, tables, lengths)
        poisoned = decode_attention_paged_kernel(
            q, kp.at[dead].set(jnp.nan), vp.at[dead].set(jnp.nan), tables,
            lengths)
        assert np.isfinite(np.asarray(poisoned)).all()
        np.testing.assert_array_equal(np.asarray(poisoned), np.asarray(clean))

    def test_matches_contiguous_kernel_on_gathered_cache(self):
        B, MB, bs, H, Hkv, Dh = 2, 4, 64, 8, 2, 64
        ks = jax.random.split(jax.random.PRNGKey(11), 3)
        q = jax.random.normal(ks[0], (B, H, Dh), jnp.float32)
        kp, vp, tables = self._rand_pool(ks[1], B, MB, bs, Hkv, Dh,
                                         jnp.float32)
        lengths = jnp.asarray([100, 255])
        paged = decode_attention_paged_kernel(q, kp, vp, tables, lengths)
        kd = kp[tables].reshape(B, MB * bs, Hkv, Dh)
        vd = vp[tables].reshape(B, MB * bs, Hkv, Dh)
        dense = decode_attention_kernel(q, kd, vd, lengths, blk=bs)
        np.testing.assert_allclose(np.asarray(paged), np.asarray(dense),
                                   rtol=1e-5, atol=1e-5)

    def test_shared_prefix_blocks_attend_identically(self):
        """Two sequences whose tables alias the SAME physical prefix blocks
        (a radix prefix-cache hit) must each see that prefix exactly as if
        they owned a private copy."""
        B, MB, bs, H, Hkv, Dh = 2, 4, 32, 4, 2, 64
        ks = jax.random.split(jax.random.PRNGKey(12), 3)
        q = jax.random.normal(ks[0], (B, H, Dh), jnp.float32)
        kp, vp, _ = self._rand_pool(ks[1], B, MB, bs, Hkv, Dh, jnp.float32,
                                    n_spare=8)
        # seqs share physical blocks 1,2 for their first two logical blocks
        shared = jnp.asarray([[1, 2, 3, 4], [1, 2, 5, 6]], jnp.int32)
        lengths = jnp.asarray([MB * bs - 1, MB * bs - 1])
        aliased = decode_attention_paged_kernel(q, kp, vp, shared, lengths)
        # private copies of the same content at different physical blocks
        kp2 = kp.at[7].set(kp[1]).at[8].set(kp[2])
        vp2 = vp.at[7].set(vp[1]).at[8].set(vp[2])
        private = jnp.asarray([[1, 2, 3, 4], [7, 8, 5, 6]], jnp.int32)
        copied = decode_attention_paged_kernel(q, kp2, vp2, private, lengths)
        np.testing.assert_allclose(np.asarray(aliased), np.asarray(copied),
                                   rtol=1e-6)

    def test_mask_ignores_scratch_tail_blocks(self):
        """Unallocated table tail entries point at the scratch block 0:
        whatever garbage lives there must not leak into the output."""
        B, MB, bs, H, Hkv, Dh = 1, 4, 32, 4, 2, 64
        ks = jax.random.split(jax.random.PRNGKey(13), 2)
        q = jax.random.normal(ks[0], (B, H, Dh), jnp.float32)
        kp, vp, _ = self._rand_pool(ks[1], B, MB, bs, Hkv, Dh, jnp.float32)
        tables = jnp.asarray([[1, 2, 0, 0]], jnp.int32)  # 2 live blocks
        lengths = jnp.asarray([2 * bs - 1])
        out1 = decode_attention_paged_kernel(q, kp, vp, tables, lengths)
        kp2 = kp.at[0].set(999.0)
        vp2 = vp.at[0].set(-999.0)
        out2 = decode_attention_paged_kernel(q, kp2, vp2, tables, lengths)
        np.testing.assert_allclose(np.asarray(out1), np.asarray(out2),
                                   rtol=1e-6)


class TestMamba1Scan:
    @pytest.mark.parametrize("B,T,C,N,cb,tc", [
        (2, 32, 128, 16, 128, 8),
        (1, 64, 256, 16, 128, 16),
        (2, 16, 128, 8, 128, 16),
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_ref(self, B, T, C, N, cb, tc, dtype):
        ks = jax.random.split(jax.random.PRNGKey(2), 4)
        dt = jax.nn.softplus(jax.random.normal(ks[0], (B, T, C))).astype(dtype)
        x = jax.random.normal(ks[1], (B, T, C), dtype)
        Bm = jax.random.normal(ks[2], (B, T, N), dtype)
        Cm = jax.random.normal(ks[3], (B, T, N), dtype)
        A = -jnp.exp(jax.random.normal(jax.random.PRNGKey(5), (C, N))) \
            .astype(jnp.float32)
        out = mamba1_scan_kernel(dt, x, Bm, Cm, A, c_blk=cb, t_chunk=tc)
        want = ref.mamba1_scan_ref(dt, x, Bm, Cm, A)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(want, np.float32),
            rtol=5e-2 if dtype == jnp.bfloat16 else 1e-4,
            atol=5e-2 if dtype == jnp.bfloat16 else 1e-4)

    def test_state_carries_across_chunks(self):
        """Splitting time into chunks must equal one long chunk (carry)."""
        B, T, C, N = 1, 32, 128, 16
        ks = jax.random.split(jax.random.PRNGKey(3), 4)
        dt = jax.nn.softplus(jax.random.normal(ks[0], (B, T, C)))
        x = jax.random.normal(ks[1], (B, T, C))
        Bm = jax.random.normal(ks[2], (B, T, N))
        Cm = jax.random.normal(ks[3], (B, T, N))
        A = -jnp.ones((C, N), jnp.float32)
        a = mamba1_scan_kernel(dt, x, Bm, Cm, A, t_chunk=8)
        b = mamba1_scan_kernel(dt, x, Bm, Cm, A, t_chunk=32)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-5)


class TestMoeGemm:
    @pytest.mark.parametrize("E,C,D,F", [
        (4, 128, 128, 256),
        (2, 256, 256, 128),
        (8, 128, 256, 384),
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("act", ["none", "silu"])
    def test_matches_ref(self, E, C, D, F, dtype, act):
        ks = jax.random.split(jax.random.PRNGKey(4), 2)
        xe = (jax.random.normal(ks[0], (E, C, D)) / np.sqrt(D)).astype(dtype)
        w = (jax.random.normal(ks[1], (E, D, F)) / np.sqrt(D)).astype(dtype)
        out = moe_grouped_gemm_kernel(xe, w, activation=act)
        want = ref.moe_grouped_gemm_ref(xe, w, activation=act)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(want, np.float32),
            **_tols(dtype))


class TestKernelCatalog:
    def test_autotune_skipped_on_catalog_hit(self):
        cat = KernelCatalog()
        B, S, H, Hkv, Dh = 1, 256, 4, 2, 64
        ks = jax.random.split(jax.random.PRNGKey(6), 3)
        q = jax.random.normal(ks[0], (B, H, Dh), jnp.float32)
        kc = jax.random.normal(ks[1], (B, S, Hkv, Dh), jnp.float32)
        vc = jax.random.normal(ks[2], (B, S, Hkv, Dh), jnp.float32)
        lengths = jnp.asarray([100])
        o1 = ops.decode_attention(q, kc, vc, lengths, catalog=cat)
        assert cat.stats["misses"] == 1 and len(cat.entries) == 1
        o2 = ops.decode_attention(q, kc, vc, lengths, catalog=cat)
        assert cat.stats["autotune_skipped"] == 1
        np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))

    def test_autotune_raises_when_no_candidate_compiles(self):
        """No silent ``candidates[0]``: every candidate refused -> raise."""
        q = jnp.zeros((1, 4, 64), jnp.float32)
        kc = jnp.zeros((1, 256, 2, 64), jnp.float32)
        lengths = jnp.asarray([10])
        with pytest.raises(RuntimeError, match="no candidate"):
            ops._autotune("decode_attention", decode_attention_kernel,
                          [{"blk": 96}, {"blk": 160}],  # neither divides S
                          (q, kc, kc, lengths))

    def test_catalog_roundtrip_through_archive(self):
        from repro.core.archive import Archive
        cat = KernelCatalog()
        cat.record("k1(sig)", b"stablehlo-payload", {"blk": 256})
        ar = Archive()
        cat.add_blobs(ar)
        ar.manifest = {"kernel_catalog": cat.to_manifest()}
        ar2 = Archive.from_bytes(ar.to_bytes())
        cat2 = KernelCatalog()
        cat2.prime(ar2.manifest["kernel_catalog"], ar2)
        e = cat2.resolve("k1(sig)")
        assert e is not None and cat2.payload(e) == b"stablehlo-payload"
