"""Bit-packed sketch storage: codec exactness, per-family wire layouts,
and the packed serving path's bitwise contracts.

The packed :class:`repro.data.store.CorpusStore` keeps each family's
bf16-halfword wire format (every f32 value truncated to a bf16 plane of
the same shape, decoded *inside* the estimate kernels) and must satisfy,
per family:

  * pack -> unpack roundtrips every component (keys/fingerprints exactly,
    values to their bf16 truncation, idempotent from the first re-pack);
  * packed-path estimates == the unpacked path run on the bf16-roundtripped
    rows, BITWISE -- the layout saves bytes, it does not fork the math;
  * spare capacity rows of a packed store stay bitwise inert;
  * batched == sequential and tenant-scoped == dedicated on the packed
    serving path, same as the unpacked contracts;
  * packed bytes/row <= 60% of unpacked for ICWS (the tentpole gate) and
    <= 80% for the sampling families (31-bit keys are the information
    floor);
  * packed stores refuse to merge (the ICWS packed layout drops the
    argkeys re-leveling sidecar).
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data import FAMILY_NAMES, make_family, wmh_storage
from repro.data.merge import merge_stores
from repro.data.store import CorpusStore
from repro.data.synthetic import sparse_pair
from repro.kernels.packed import pack_halfwords_f32, unpack_halfwords_f32
from repro.serve import SketchSearchService

QMAP = (0, 1, 0, 2, 0, 1)
CMAP = (0, 0, 1, 0, 2, 1)
STORAGE = wmh_storage(64)


def _bf16_trunc(x):
    """The codec's value map: f32 with the low 16 mantissa bits dropped."""
    return np.asarray(x, np.float32).view(np.uint32) \
        .__and__(np.uint32(0xFFFF0000)).view(np.float32)


def _field_rows(fam, rng, P, F=3):
    vecs = [sparse_pair(rng, n=400, nnz=80, overlap=0.3)[0]
            for _ in range(F * P)]
    comps = fam.sketch_rows(vecs)
    return tuple(jnp.swapaxes(c.reshape((P, F) + c.shape[1:]), 0, 1)
                 for c in comps)


# ---------------------------------------------------------------------------
# halfword codec
# ---------------------------------------------------------------------------
def test_codec_roundtrip_is_bf16_truncation():
    rng = np.random.default_rng(0)
    x = np.concatenate([rng.normal(size=500).astype(np.float32),
                        np.array([0.0, -0.0, 1e-37, -1e37], np.float32)])
    w = pack_halfwords_f32(jnp.asarray(x.reshape(2, 252)))
    assert w.shape == (2, 252) and w.dtype == jnp.bfloat16
    back = np.asarray(unpack_halfwords_f32(w))
    np.testing.assert_array_equal(back, _bf16_trunc(x).reshape(2, 252))
    # idempotent from the first re-pack: packing the decode is the identity
    np.testing.assert_array_equal(
        np.asarray(pack_halfwords_f32(unpack_halfwords_f32(w))),
        np.asarray(w))
    # zero halfwords decode to exact zeros (what keeps pad rows inert)
    assert np.all(np.asarray(
        unpack_halfwords_f32(jnp.zeros((3, 4), jnp.bfloat16))) == 0.0)


def test_codec_keeps_every_width():
    """One halfword per sample: odd widths pack with no pad slot, and the
    packed plane has the shape of the values it holds."""
    x = np.arange(1, 11, dtype=np.float32).reshape(2, 5) / 3
    w = pack_halfwords_f32(jnp.asarray(x))
    assert w.shape == (2, 5)
    np.testing.assert_array_equal(np.asarray(unpack_halfwords_f32(w)),
                                  _bf16_trunc(x))


# ---------------------------------------------------------------------------
# per-family wire layout
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name", FAMILY_NAMES)
def test_pack_unpack_roundtrip_per_family(name):
    fam = make_family(name, storage=STORAGE, seed=5)
    rng = np.random.default_rng(21)
    rows = _field_rows(fam, rng, 4)
    packed = fam.pack_rows(rows)
    specs = tuple(fam.packed_components)
    assert len(packed) == len(specs)
    for comp, spec in zip(packed, specs):
        assert comp.dtype == spec.dtype, spec.name
        assert comp.shape[2:] == spec.trailing, spec.name
    rt = fam.unpack_rows(packed)
    assert len(rt) == len(rows)
    # integer planes (fingerprints / sample keys) survive exactly; value
    # planes come back bf16-truncated; re-packing the roundtrip is the
    # identity (the wire format is a fixed point).  The icws-layout
    # argkeys sidecar (icws and its dmh sibling) is dropped by the packed
    # format -- packed rows are frozen -- and comes back zeroed.
    for a, b in zip(rows, rt):
        a, b = np.asarray(a), np.asarray(b)
        if a.dtype == np.int32 and not (name in ("icws", "dmh")
                                        and b.shape == a.shape
                                        and np.all(b == 0)):
            assert np.array_equal(a, b) or np.array_equal(_bf16_trunc(a), b)
    for p1, p2 in zip(packed, fam.pack_rows(rt)):
        np.testing.assert_array_equal(np.asarray(p1), np.asarray(p2))


@pytest.mark.parametrize("name", FAMILY_NAMES)
def test_packed_estimates_bitwise_equal_unpacked_on_roundtrip(name):
    """THE packed-path contract: estimates off the packed layout equal the
    ordinary unpacked launch run on the bf16-roundtripped rows, bitwise."""
    fam = make_family(name, storage=STORAGE, seed=5)
    rng = np.random.default_rng(31)
    crows = _field_rows(fam, rng, 6)
    qrows = _field_rows(fam, np.random.default_rng(32), 2)
    packed = fam.pack_rows(crows)
    est_p = np.asarray(fam.estimate_fields(qrows, packed,
                                           qmap=QMAP, cmap=CMAP))
    est_u = np.asarray(fam.estimate_fields(qrows, fam.unpack_rows(packed),
                                           qmap=QMAP, cmap=CMAP))
    assert est_p.shape == est_u.shape == (6, 2, 6)
    np.testing.assert_array_equal(est_p, est_u)


# ---------------------------------------------------------------------------
# packed store: layout accounting, inert spares, append contract, merging
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name", FAMILY_NAMES)
def test_packed_store_bytes_per_row_reduction(name):
    fam = make_family(name, storage=STORAGE, seed=5)
    unpacked = CorpusStore(family=fam, fields=3)
    packed = CorpusStore(family=fam, fields=3, packed=True)
    ratio = packed.bytes_per_row() / unpacked.bytes_per_row()
    gate = 0.80 if name in ("ts", "ps") else 0.60
    assert ratio <= gate, (name, ratio)


@pytest.mark.parametrize("name", FAMILY_NAMES)
@pytest.mark.parametrize("fill", [3, 11, 16])
def test_packed_spare_capacity_bitwise_inert(name, fill):
    """Spare capacity rows of a PACKED store estimate to exact zero and
    never perturb live rows -- the same invariant the unpacked store holds,
    now over sentinel fingerprints/keys plus all-zero packed value words."""
    fam = make_family(name, storage=STORAGE, seed=5)
    rng = np.random.default_rng(200 + fill)
    rows = _field_rows(fam, rng, fill)

    store = CorpusStore(family=fam, fields=3, min_capacity=16, packed=True)
    store.append(*rows)
    assert store.capacity == 16 and len(store) == fill
    exact = CorpusStore(family=fam, fields=3, min_capacity=fill, packed=True)
    exact.append(*rows)
    assert exact.capacity == fill

    qcomps = _field_rows(fam, np.random.default_rng(7), 2)
    est_full = np.asarray(fam.estimate_fields(
        qcomps, store.buffers(), qmap=QMAP, cmap=CMAP))
    est_exact = np.asarray(fam.estimate_fields(
        qcomps, exact.buffers(), qmap=QMAP, cmap=CMAP))
    assert est_full.shape == (6, 2, 16)
    assert np.all(est_full[:, :, fill:] == 0.0)
    np.testing.assert_array_equal(est_full[:, :, :fill], est_exact)


def test_packed_store_append_validates_unpacked_rows():
    """Ingest call sites hand the store ordinary unpacked sketch rows; the
    store packs internally.  Shape checks fire against the UNPACKED
    contract, so a mismatch is reported in the caller's terms."""
    fam = make_family("icws", storage=STORAGE, seed=5)
    store = CorpusStore(family=fam, fields=3, packed=True)
    rows = _field_rows(fam, np.random.default_rng(41), 2)
    store.append(*rows)
    assert len(store) == 2
    # stored buffers match the packed component specs, not the row specs
    for buf, spec in zip(store.buffers(), fam.packed_components):
        assert buf.dtype == spec.dtype and buf.shape[2:] == spec.trailing
    with pytest.raises(ValueError):
        store.append(*rows[:-1])                     # missing a component
    bad = tuple(np.asarray(r)[:, :, :3] if np.asarray(r).ndim == 3 else r
                for r in rows)
    with pytest.raises(ValueError):
        store.append(*bad)                           # wrong trailing shape


def test_packed_stores_refuse_to_merge():
    fam = make_family("ts", storage=STORAGE, seed=5)
    rows = _field_rows(fam, np.random.default_rng(43), 2)
    plain = CorpusStore(family=fam, fields=3)
    plain.append(*rows)
    packed = CorpusStore(family=fam, fields=3, packed=True)
    packed.append(*rows)
    with pytest.raises(ValueError, match="packed"):
        merge_stores(packed, packed)
    with pytest.raises(ValueError, match="packed"):
        merge_stores(plain, packed)


# ---------------------------------------------------------------------------
# packed serving path: batched == sequential, tenant == dedicated
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("family", FAMILY_NAMES)
def test_packed_service_batched_equals_sequential(family):
    rng = np.random.default_rng(17)
    svc = SketchSearchService(m=64, seed=2, family=family,
                              keep_host_oracle=False, packed=True)
    keys = np.arange(300)
    signal = rng.normal(size=300)
    svc.ingest("a_corr", keys, signal + 0.1 * rng.normal(size=300))
    svc.ingest("b_noise", keys, rng.normal(size=300))
    svc.ingest("c_disjoint", np.arange(9000, 9300), rng.normal(size=300))
    queries = [(keys, signal + 0.05 * rng.normal(size=300))
               for _ in range(3)] + [(np.arange(30), rng.normal(size=30))]
    batch = svc.search_batch(queries, top_k=3, min_join=10, micro_batch=2)
    seq = [svc.search(k, v, top_k=3, min_join=10) for k, v in queries]
    assert batch == seq
    assert svc.describe()["packed"] is True
    assert batch[0] and batch[0][0].name == "a_corr"


def test_packed_tenant_scoped_equals_dedicated():
    rng = np.random.default_rng(19)
    keys = np.arange(200)
    sig = rng.normal(size=200)
    tabs = {t: [(f"{t}{i}", keys,
                 sig + (0.1 + 0.2 * i) * rng.normal(size=200))
                for i in range(4)]
            for t in ("a", "b")}
    shared = SketchSearchService(m=64, seed=3, keep_host_oracle=False,
                                 packed=True)
    for t, rows in tabs.items():
        shared.ingest_many(rows, tenant=t)
    dedicated = SketchSearchService(m=64, seed=3, keep_host_oracle=False,
                                    packed=True)
    dedicated.ingest_many(tabs["a"])
    queries = [(keys, sig + 0.1 * rng.normal(size=200)) for _ in range(3)]
    assert (shared.search_batch(queries, top_k=3, min_join=10, tenant="a")
            == dedicated.search_batch(queries, top_k=3, min_join=10))


# ---------------------------------------------------------------------------
# pack-on-output sketch kernel
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("m", [8, 9])
def test_icws_sketch_pack_vals_matches_host_pack(m):
    """The pack_vals output == host pack_halfwords_f32 of the val output
    (odd m included), including rows that sketched empty."""
    from repro.kernels.icws_sketch import icws_sketch_pallas
    rng = np.random.default_rng(51)
    B, N = 5, 64
    w = rng.random((B, N)).astype(np.float32)
    w[2] = 0.0                                       # an empty row
    keys = jnp.asarray(rng.integers(0, 2 ** 31 - 1, (B, N)), jnp.int32)
    vals = jnp.asarray(np.sqrt(w))
    w = jnp.asarray(w)
    fp, val, amin, argk, packed = icws_sketch_pallas(
        w, keys, vals, m=m, seed=3, br=2, bm=4, bn=16, pack_vals=True,
        interpret=True)
    ref4 = icws_sketch_pallas(w, keys, vals, m=m, seed=3, interpret=True)
    for a, b in zip((fp, val, amin, argk), ref4):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert packed.shape == (B, m)
    np.testing.assert_array_equal(
        np.asarray(packed).view(np.uint16),
        np.asarray(pack_halfwords_f32(jnp.asarray(val))).view(np.uint16))
    np.testing.assert_array_equal(np.asarray(unpack_halfwords_f32(packed)),
                                  _bf16_trunc(np.asarray(val)))
