"""Canonical field-stacked CorpusStore: amortized append semantics.

Covers: interleaved appends across capacity-doubling boundaries produce
``arrays()`` bitwise identical to a single build-once ingest (property-
tested, for F=1 and F=3 stores, fed from host-numpy and device-jnp arrays);
capacity-doubling growth accounting; up-front validation of all three
sketch components; and inertness of unused capacity rows under the
estimate kernels (buffers-vs-exact-arrays estimates bitwise equal).
"""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.data.store import PAD_FP, CorpusStore
from repro.kernels import ops


def _rows(rng, fields, b, m):
    fp = rng.integers(0, 100, size=(fields, b, m)).astype(np.int32)
    val = rng.normal(size=(fields, b, m)).astype(np.float32)
    norm = (rng.random((fields, b)) + 0.1).astype(np.float32)
    key = rng.integers(0, 2 ** 31 - 1, size=(fields, b, m)).astype(np.int32)
    return fp, val, norm, key


# ---------------------------------------------------------------------------
# interleaved appends == one-shot ingest (bitwise), across doubling boundaries
# ---------------------------------------------------------------------------
@settings(max_examples=40, deadline=None)
@given(fields=st.integers(1, 3), device=st.integers(0, 1),
       sizes=st.lists(st.integers(1, 7), min_size=1, max_size=6),
       seed=st.integers(0, 2 ** 31 - 1))
def test_interleaved_appends_match_one_shot(fields, device, sizes, seed):
    """F=1 and F=3 stores, host-numpy and device-jnp sources, interleaved
    appends crossing capacity-doubling boundaries == build-once ingest."""
    rng = np.random.default_rng(seed)
    m, total = 16, sum(sizes)
    rows = _rows(rng, fields, total, m)

    one = CorpusStore(m=m, fields=fields, min_capacity=2)
    one.append(*rows)

    # min_capacity=2 forces several capacity doublings mid-sequence
    inc = CorpusStore(m=m, fields=fields, min_capacity=2)
    off = 0
    for b in sizes:
        chunk = tuple(r[:, off:off + b] for r in rows)
        if device:
            chunk = tuple(jnp.asarray(c) for c in chunk)
        inc.append(*chunk)
        off += b
    assert len(inc) == len(one) == total
    for a, b_ in zip(one.arrays(), inc.arrays()):
        assert np.array_equal(np.asarray(a), np.asarray(b_))


def test_capacity_doubles_amortized():
    store = CorpusStore(m=8, fields=1, min_capacity=4)
    caps = []
    rng = np.random.default_rng(0)
    for _ in range(20):
        store.append(*_rows(rng, 1, 1, 8))
        caps.append(store.capacity)
    assert len(store) == 20 and store.capacity == 32
    # growth is doubling: capacities are powers of two of the floor, and
    # the number of distinct capacities is logarithmic in the final size
    assert sorted(set(caps)) == [4, 8, 16, 32]


def test_store_row_multiple_keeps_capacity_divisible():
    """Sharded stores round the capacity floor to the mesh axis size, and
    doubling preserves it -- the sharded query path never re-pads rows."""
    store = CorpusStore(m=8, fields=1, min_capacity=5, row_multiple=3)
    assert store.min_capacity == 6
    rng = np.random.default_rng(4)
    for _ in range(15):
        store.append(*_rows(rng, 1, 1, 8))
        assert store.capacity % 3 == 0
    assert store.capacity == 24


def test_store_single_field_accepts_2d_rows():
    rng = np.random.default_rng(1)
    rows = _rows(rng, 1, 5, 8)
    flat = CorpusStore(m=8, fields=1)
    flat.append(*(r[0] for r in rows))             # [b, m] / [b]
    stacked = CorpusStore(m=8, fields=1)
    stacked.append(*rows)                          # [1, b, m] / [1, b]
    for a, b in zip(flat.arrays(), stacked.arrays()):
        assert np.array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# validation: all three components checked against each other at ingest
# ---------------------------------------------------------------------------
def test_store_append_validates_all_components():
    rng = np.random.default_rng(2)
    fp, val, norm, key = _rows(rng, 3, 4, 8)
    store = CorpusStore(m=8, fields=3)
    with pytest.raises(ValueError):
        store.append(fp, val[:, :3], norm, key)    # mismatched val rows
    with pytest.raises(ValueError):
        store.append(fp, val, norm[:, :3], key)    # mismatched norm rows
    with pytest.raises(ValueError):
        store.append(fp, val, norm, key[:, :3])    # mismatched argkey rows
    with pytest.raises(ValueError):
        store.append(fp, val, norm)                # missing a component
    with pytest.raises(ValueError):
        store.append(fp[:2], val[:2], norm[:2], key[:2])  # wrong field count
    with pytest.raises(ValueError):
        store.append(fp[:, :, :4], val[:, :, :4], norm, key)   # wrong m
    assert len(store) == 0
    store.append(fp, val, norm, key)
    assert len(store) == 4


def test_store_empty_raises_and_zero_rows_noop():
    store = CorpusStore(m=8, fields=1)
    with pytest.raises(ValueError):
        store.arrays()
    with pytest.raises(ValueError):
        store.buffers()
    store.append(np.zeros((1, 0, 8), np.int32), np.zeros((1, 0, 8)),
                 np.zeros((1, 0)), np.zeros((1, 0, 8), np.int32))
    assert len(store) == 0


# ---------------------------------------------------------------------------
# unused capacity rows are inert under the estimate kernels
# ---------------------------------------------------------------------------
def test_spare_capacity_is_inert_in_estimates():
    """Estimates off the full-capacity buffers == estimates off exact-size
    arrays, row for row and bitwise -- the invariant that lets query paths
    skip materializing an exact-size corpus copy."""
    rng = np.random.default_rng(7)
    m, P = 32, 5
    rows = _rows(rng, 1, P, m)
    store = CorpusStore(m=m, fields=1, min_capacity=16)   # capacity 16 > P=5
    store.append(*rows)
    assert store.capacity > len(store)
    fpb, vb, nb, _ = store.buffers()
    assert np.all(np.asarray(fpb)[0, P:] == PAD_FP)

    fq = jnp.asarray(rng.integers(0, 100, size=(1, 2, m)).astype(np.int32))
    vq = jnp.asarray(rng.normal(size=(1, 2, m)).astype(np.float32))
    nq = jnp.ones((1, 2), jnp.float32)

    def estimate(fp, val, norm):
        return ops.icws_estimate_fields(fq, vq, nq, fp, val, norm,
                                        qmap=(0,), cmap=(0,))[0]

    exact = estimate(*(a[None] for a in store.arrays()[:3]))
    padded = estimate(fpb, vb, nb)
    assert padded.shape == (2, store.capacity)
    assert np.all(np.asarray(padded)[:, P:] == 0.0)       # spare rows: zero
    assert np.array_equal(np.asarray(padded)[:, :P], np.asarray(exact))
