"""Pallas kernel validation: interpret-mode vs pure-jnp oracles.

Hypothesis sweeps shapes/dtypes; every kernel asserts allclose against its
ref.py oracle, plus targeted semantic tests (sandbox violations, seal
checks, ring wrap, chunk-boundary states).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip(
    "hypothesis",
    reason="property sweeps need hypothesis, which the pinned container "
           "image does not ship; install it to run this module")
from hypothesis import given, settings, strategies as st

from repro.kernels.flash_prefill.ops import flash_prefill
from repro.kernels.paged_attention.ops import paged_attention
from repro.kernels.scope_copy.ops import gather_pages, scatter_pages
from repro.kernels.ssd.ops import ssd_chunked
from repro.kernels.ssd.ref import ssd_sequential_ref

KEY = jax.random.PRNGKey(42)
HS = settings(max_examples=8, deadline=None)


def _tol(dtype):
    return dict(atol=5e-2, rtol=5e-2) if dtype == jnp.bfloat16 \
        else dict(atol=5e-5, rtol=1e-4)


# ---------------------------------------------------------------------------
# paged attention
# ---------------------------------------------------------------------------
class TestPagedAttention:
    def _inputs(self, B, Hq, Hkv, D, P, T, MAXP, dtype, seed=0):
        ks = jax.random.split(jax.random.fold_in(KEY, seed), 6)
        q = jax.random.normal(ks[0], (B, Hq, D), dtype)
        kp = jax.random.normal(ks[1], (1, P, Hkv, T, D), dtype)
        vp = jax.random.normal(ks[2], (1, P, Hkv, T, D), dtype)
        bt = jax.random.permutation(ks[3], jnp.arange(P))[: B * MAXP] \
            .reshape(B, MAXP).astype(jnp.int32)
        lens = jax.random.randint(ks[4], (B,), 1, MAXP * T + 1)
        perm = jnp.ones((P,), jnp.int32)
        bitmap = jnp.ones((P,), jnp.int32)
        sandbox = jnp.array([0, P, 1], jnp.int32)
        return q, kp, vp, bt, lens, perm, sandbox, bitmap

    @HS
    @given(
        B=st.sampled_from([1, 2, 4]),
        heads=st.sampled_from([(4, 1), (4, 2), (8, 8), (16, 4)]),
        D=st.sampled_from([64, 128]),
        T=st.sampled_from([8, 16]),
        dtype=st.sampled_from([jnp.float32, jnp.bfloat16]),
    )
    def test_matches_ref_sweep(self, B, heads, D, T, dtype):
        Hq, Hkv = heads
        P, MAXP = 32, 6
        args = self._inputs(B, Hq, Hkv, D, P, T, MAXP, dtype)
        o_ref, b_ref = paged_attention(*args, backend="ref")
        o_k, b_k = paged_attention(*args, backend="interpret")
        np.testing.assert_array_equal(np.asarray(b_ref), np.asarray(b_k))
        np.testing.assert_allclose(
            np.asarray(o_k, np.float32), np.asarray(o_ref, np.float32),
            **_tol(dtype))

    def test_wild_pointer_counted_and_masked(self):
        args = list(self._inputs(2, 4, 2, 64, 32, 16, 4, jnp.float32))
        args[3] = args[3].at[0, 0].set(999)  # out of pool bounds
        for backend in ("ref", "interpret"):
            out, oob = paged_attention(*args, backend=backend)
            assert int(oob[0]) >= 1 and int(oob[1]) == 0
            assert np.isfinite(np.asarray(out)).all()

    def test_unsealed_page_rejected(self):
        args = list(self._inputs(2, 4, 2, 64, 32, 16, 4, jnp.float32))
        victim = int(args[3][1, 0])
        args[5] = args[5].at[victim].set(0)  # clear SEALED bit
        _, oob = paged_attention(*args, backend="interpret")
        assert int(oob[1]) >= 1

    def test_sandbox_off_skips_checks(self):
        args = list(self._inputs(2, 4, 2, 64, 32, 16, 4, jnp.float32))
        args[5] = jnp.zeros_like(args[5])              # nothing sealed
        args[6] = jnp.array([0, 32, 0], jnp.int32)     # enforce=0
        _, oob = paged_attention(*args, backend="interpret")
        assert int(oob.sum()) == 0

    def test_foreign_connection_page_blocked_by_bitmap(self):
        """A page inside pool bounds but belonging to another connection
        (bitmap 0) must not be readable — the paper's §4.3 attack."""
        args = list(self._inputs(2, 4, 2, 64, 32, 16, 4, jnp.float32))
        victim = int(args[3][0, 0])
        args[7] = args[7].at[victim].set(0)
        _, oob = paged_attention(*args, backend="interpret")
        assert int(oob[0]) >= 1


# ---------------------------------------------------------------------------
# flash prefill
# ---------------------------------------------------------------------------
class TestFlashPrefill:
    @HS
    @given(
        B=st.sampled_from([1, 2]),
        S=st.sampled_from([64, 100, 256]),
        heads=st.sampled_from([(4, 2), (8, 8), (4, 1)]),
        D=st.sampled_from([64, 128]),
        window=st.sampled_from([0, 32]),
        softcap=st.sampled_from([0.0, 30.0]),
        dtype=st.sampled_from([jnp.float32, jnp.bfloat16]),
    )
    def test_matches_ref_sweep(self, B, S, heads, D, window, softcap, dtype):
        Hq, Hkv = heads
        ks = jax.random.split(KEY, 3)
        q = jax.random.normal(ks[0], (B, S, Hq, D), dtype)
        k = jax.random.normal(ks[1], (B, S, Hkv, D), dtype)
        v = jax.random.normal(ks[2], (B, S, Hkv, D), dtype)
        o_ref = flash_prefill(q, k, v, window=window, softcap=softcap,
                              backend="ref")
        o_k = flash_prefill(q, k, v, window=window, softcap=softcap,
                            bq=64, bk=64, backend="interpret")
        np.testing.assert_allclose(
            np.asarray(o_k, np.float32), np.asarray(o_ref, np.float32),
            **_tol(dtype))

    def test_block_size_invariance(self):
        ks = jax.random.split(KEY, 3)
        q = jax.random.normal(ks[0], (1, 128, 4, 64), jnp.float32)
        k = jax.random.normal(ks[1], (1, 128, 2, 64), jnp.float32)
        v = jax.random.normal(ks[2], (1, 128, 2, 64), jnp.float32)
        outs = [flash_prefill(q, k, v, bq=bq, bk=bk, backend="interpret")
                for bq, bk in [(32, 32), (64, 128), (128, 64)]]
        for o in outs[1:]:
            np.testing.assert_allclose(np.asarray(o), np.asarray(outs[0]),
                                       atol=2e-5, rtol=1e-4)


# ---------------------------------------------------------------------------
# SSD
# ---------------------------------------------------------------------------
class TestSSD:
    def _inputs(self, B, S, H, P, N, dtype, seed=0):
        ks = jax.random.split(jax.random.fold_in(KEY, seed), 5)
        x = jax.random.normal(ks[0], (B, S, H, P), dtype)
        dt = jax.nn.softplus(
            jax.random.normal(ks[1], (B, S, H), jnp.float32))
        A = -jnp.exp(jax.random.normal(ks[2], (H,), jnp.float32) * 0.5)
        Bm = jax.random.normal(ks[3], (B, S, 1, N), dtype)
        Cm = jax.random.normal(ks[4], (B, S, 1, N), dtype)
        return x, dt, A, Bm, Cm

    @HS
    @given(
        B=st.sampled_from([1, 2]),
        S=st.sampled_from([32, 64, 96]),
        H=st.sampled_from([8, 16]),
        P=st.sampled_from([16, 64]),
        N=st.sampled_from([16, 32]),
        Q=st.sampled_from([16, 32]),
    )
    def test_kernel_matches_sequential_scan(self, B, S, H, P, N, Q):
        x, dt, A, Bm, Cm = self._inputs(B, S, H, P, N, jnp.float32)
        y_seq, s_seq = ssd_sequential_ref(x, dt, A, Bm, Cm)
        y_k, s_k = ssd_chunked(x, dt, A, Bm, Cm, chunk=Q,
                               backend="interpret")
        np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_seq),
                                   atol=2e-3, rtol=2e-3)
        np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_seq),
                                   atol=2e-3, rtol=2e-3)

    def test_init_state_continuation(self):
        """Splitting a sequence across two calls with state carry must
        equal one full-sequence call (the serving handoff invariant: the
        RPC'd state page IS the computation)."""
        x, dt, A, Bm, Cm = self._inputs(2, 64, 8, 16, 16, jnp.float32)
        y_full, s_full = ssd_chunked(x, dt, A, Bm, Cm, chunk=16,
                                     backend="ref")
        y1, s1 = ssd_chunked(x[:, :32], dt[:, :32], A, Bm[:, :32],
                             Cm[:, :32], chunk=16, backend="ref")
        y2, s2 = ssd_chunked(x[:, 32:], dt[:, 32:], A, Bm[:, 32:],
                             Cm[:, 32:], chunk=16, backend="ref",
                             init_state=s1)
        np.testing.assert_allclose(np.asarray(y2), np.asarray(y_full[:, 32:]),
                                   atol=1e-4, rtol=1e-4)
        np.testing.assert_allclose(np.asarray(s2), np.asarray(s_full),
                                   atol=1e-4, rtol=1e-4)

    def test_chunk_size_invariance(self):
        x, dt, A, Bm, Cm = self._inputs(1, 96, 8, 16, 16, jnp.float32)
        outs = [ssd_chunked(x, dt, A, Bm, Cm, chunk=q, backend="ref")[0]
                for q in (16, 32, 96)]
        for o in outs[1:]:
            np.testing.assert_allclose(np.asarray(o), np.asarray(outs[0]),
                                       atol=1e-3, rtol=1e-3)


# ---------------------------------------------------------------------------
# scope copy
# ---------------------------------------------------------------------------
class TestScopeCopy:
    @HS
    @given(
        P=st.sampled_from([16, 64]),
        W=st.sampled_from([128, 256]),
        n=st.sampled_from([1, 4, 9]),
        dtype=st.sampled_from([jnp.float32, jnp.bfloat16, jnp.int32]),
    )
    def test_gather_scatter_roundtrip(self, P, W, n, dtype):
        ks = jax.random.split(KEY, 3)
        if dtype == jnp.int32:
            pool = jax.random.randint(ks[0], (P, W), 0, 1000, dtype)
            buf = jax.random.randint(ks[1], (n, W), 0, 1000, dtype)
        else:
            pool = jax.random.normal(ks[0], (P, W), dtype)
            buf = jax.random.normal(ks[1], (n, W), dtype)
        pages = jax.random.permutation(ks[2], jnp.arange(P))[:n] \
            .astype(jnp.int32)
        for backend in ("ref", "interpret"):
            g = gather_pages(pool, pages, backend=backend)
            np.testing.assert_array_equal(
                np.asarray(g), np.asarray(pool)[np.asarray(pages)])
            s = scatter_pages(pool.copy(), pages, buf, backend=backend)
            np.testing.assert_array_equal(
                np.asarray(s)[np.asarray(pages)], np.asarray(buf))
            # untouched rows intact
            untouched = np.setdiff1d(np.arange(P), np.asarray(pages))
            np.testing.assert_array_equal(
                np.asarray(s)[untouched], np.asarray(pool)[untouched])

    def test_wire_roundtrip_between_pools(self):
        """gather → wire → scatter moves a scope between two pools (the
        fallback transport's data plane)."""
        ks = jax.random.split(KEY, 2)
        src = jax.random.normal(ks[0], (32, 128), jnp.float32)
        dst = jnp.zeros((32, 128), jnp.float32)
        pages = jnp.array([3, 7, 11], jnp.int32)
        wire = gather_pages(src, pages, backend="interpret")
        dst2 = scatter_pages(dst, pages, wire, backend="interpret")
        np.testing.assert_array_equal(
            np.asarray(dst2)[np.asarray(pages)],
            np.asarray(src)[np.asarray(pages)])
