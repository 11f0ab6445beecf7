"""Paged attention walks only the live pages of each row.

A block-table slot is live while ``p < ceil(seq_len / T)``. Dead slots may
hold any page id (out of the pool, unsealed, another connection's): the
kernel neither fetches nor checks them, and a row of length 0 reads as
empty. Interpret mode against the pure-jnp oracle.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.paged_attention.kernel import live_slot
from repro.kernels.paged_attention.ops import paged_attention

T, MAXP, P = 8, 4, 40
HQ, HKV, D = 4, 2, 64
# one row each: empty, one token, one full page, a page and a token, full
LENS = (0, 1, T, T + 1, MAXP * T)
OUT_OF_POOL, UNSEALED, FOREIGN = P + 7, P - 1, P - 2


def _inputs(dtype=jnp.float32, seed=0):
    """Live slots hold distinct valid pages; every dead slot holds one of
    the three kinds of garbage page id, in turn."""
    B = len(LENS)
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, HQ, D), dtype)
    kp = jax.random.normal(ks[1], (1, P, HKV, T, D), dtype)
    vp = jax.random.normal(ks[2], (1, P, HKV, T, D), dtype)
    garbage = (OUT_OF_POOL, UNSEALED, FOREIGN)
    bt = np.zeros((B, MAXP), np.int32)
    nxt = 0
    for b, n in enumerate(LENS):
        for p in range(MAXP):
            if p < -(-n // T):
                bt[b, p] = nxt
                nxt += 1
            else:
                bt[b, p] = garbage[(b + p) % 3]
    perm = np.ones((P,), np.int32)
    perm[UNSEALED] = 0
    bitmap = np.ones((P,), np.int32)
    bitmap[FOREIGN] = 0
    return [q, kp, vp, jnp.asarray(bt), jnp.asarray(LENS, jnp.int32),
            jnp.asarray(perm), jnp.array([0, P, 1], jnp.int32),
            jnp.asarray(bitmap)]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ragged_rows_match_ref_with_garbage_in_dead_slots(dtype):
    args = _inputs(dtype)
    o_ref, oob_ref = paged_attention(*args, backend="ref")
    o_k, oob_k = paged_attention(*args, backend="interpret")
    tol = dict(atol=5e-2, rtol=5e-2) if dtype == jnp.bfloat16 \
        else dict(atol=5e-5, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(o_k, np.float32),
                               np.asarray(o_ref, np.float32), **tol)
    # the dead slots' garbage is never dereferenced, so never counted
    assert np.asarray(oob_k).tolist() == [0] * len(LENS)
    assert np.asarray(oob_ref).tolist() == [0] * len(LENS)


def test_zero_length_row_returns_zeros():
    out, _ = paged_attention(*_inputs(), backend="interpret")
    assert LENS[0] == 0
    assert not np.asarray(out[0]).any()
    assert np.asarray(out[1:]).any(axis=(1, 2)).all()


@pytest.mark.parametrize("bad", [OUT_OF_POOL, UNSEALED, FOREIGN])
def test_oob_counts_live_violations_only(bad):
    """The same garbage in a live slot is a violation, counted once, on
    its row alone; the row's other pages still attend as the oracle."""
    args = _inputs()
    row = LENS.index(T + 1)                 # two live pages
    args[3] = args[3].at[row, 1].set(bad)
    o_ref, oob_ref = paged_attention(*args, backend="ref")
    o_k, oob_k = paged_attention(*args, backend="interpret")
    want = [1 if b == row else 0 for b in range(len(LENS))]
    assert np.asarray(oob_k).tolist() == want
    assert np.asarray(oob_ref).tolist() == want
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_ref),
                               atol=5e-5, rtol=1e-4)


@pytest.mark.parametrize("seq_len", LENS)
def test_live_slot_maps_dead_slots_to_last_live_page(seq_len):
    n_live = -(-seq_len // T)
    got = [int(live_slot(p, seq_len, T)) for p in range(MAXP)]
    # live slots read themselves; every dead one, the last live slot
    # (slot 0 for an empty row), so one block index repeats to the end
    assert got == [min(p, max(n_live - 1, 0)) for p in range(MAXP)]
    assert got[:n_live] == list(range(n_live))
    assert set(got[n_live:]) <= {max(n_live - 1, 0)}
