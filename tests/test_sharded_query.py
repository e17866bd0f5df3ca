"""Mesh-sharded corpus-query execution == single-device path, bitwise.

The acceptance invariant for the sharded serving path: with the corpus rows
split over a 2+ device ``data`` mesh axis (forced host devices via
``XLA_FLAGS=--xla_force_host_platform_device_count``), the per-shard
estimate launches + per-shard-top-k-and-merge ranking return results
bitwise identical to the single-device launch -- estimates, top-k scores
AND indices (tie order included), and end-to-end SearchResults.

Runs in a subprocess because the forced device count must be set before
jax initializes.
"""
import os
import subprocess
import sys
import textwrap

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import sys; sys.path.insert(0, "src")
    import numpy as np, jax, jax.numpy as jnp
    from repro.data import CorpusStore, DatasetSearchIndex, ICWSFamily
    from repro.data.synthetic import sparse_pair
    from repro.kernels import ops
    from repro.launch.mesh import make_corpus_mesh
    from repro.serve import SketchSearchService

    mesh = make_corpus_mesh()
    assert mesh.shape["data"] == 2, mesh

    rng = np.random.default_rng(3)

    # -- sharded_top_k == top_k on tie-heavy scores (values AND indices)
    for n, k in ((11, 6), (8, 3), (5, 5)):
        score = jnp.asarray(
            rng.integers(-1, 3, size=(4, n)).astype(np.float32))
        v0, i0 = ops.top_k(score, k)
        v1, i1 = ops.sharded_top_k(score, k, mesh=mesh, axis="data")
        assert np.array_equal(np.asarray(v0), np.asarray(v1)), (n, k)
        assert np.array_equal(np.asarray(i0), np.asarray(i1)), (n, k)

    # -- the shard helper on a raw single-field corpus: sharded ==
    #    single-device, bitwise, and rows that do not split over the axis
    #    are refused (a sharded store keeps its capacity divisible)
    fam = ICWSFamily(m=64)
    pairs = {"qmap": (0,), "cmap": (0,)}
    q = (jnp.asarray(rng.integers(0, 30, size=(1, 2, 64)).astype(np.int32)),
         jnp.asarray(rng.normal(size=(1, 2, 64)).astype(np.float32)),
         jnp.ones((1, 2), jnp.float32))
    c = (jnp.asarray(rng.integers(0, 30, size=(1, 6, 64)).astype(np.int32)),
         jnp.asarray(rng.normal(size=(1, 6, 64)).astype(np.float32)),
         jnp.ones((1, 6), jnp.float32))
    u = np.asarray(fam.estimate_fields(q, c, **pairs))
    s = np.asarray(ops.estimate_fields_sharded(
        fam.estimate_fields, q, c, mesh=mesh, axis="data", **pairs))
    assert u.shape == (1, 2, 6) and np.array_equal(u, s)
    try:
        ops.estimate_fields_sharded(fam.estimate_fields, q,
                                    tuple(x[:, :5] for x in c), mesh=mesh,
                                    axis="data", **pairs)
        raise AssertionError("5 rows split over 2 shards")
    except ValueError:
        pass

    # -- a single-field store of device sketches: sharded == unsharded,
    #    bitwise (5 tables: live rows NOT divisible by the 2-way axis)
    vecs = [sparse_pair(rng, n=400, nnz=80, overlap=0.3)[0] for _ in range(5)]
    queries = [sparse_pair(rng, n=400, nnz=80, overlap=0.3)[0]
               for _ in range(3)]
    fam = ICWSFamily(m=128, seed=2)
    plain = CorpusStore(family=fam, fields=1)
    shard = CorpusStore(family=fam, fields=1, mesh=mesh)
    for st in (plain, shard):
        st.append(*fam.sketch_rows(vecs))
    q = tuple(x[None] for x in fam.sketch_rows(queries)[:3])
    e0 = np.asarray(fam.estimate_fields(q, plain.buffers(), **pairs))
    e1 = np.asarray(ops.estimate_fields_sharded(
        fam.estimate_fields, q, shard.buffers(), mesh=mesh, axis="data",
        **pairs))
    assert np.array_equal(e0[:, :, :5], e1[:, :, :5])
    assert not e1[:, :, 5:].any()                  # spare rows are inert
    # the sharded store's buffers are ALLOCATED across the mesh (corpus
    # memory spreads over devices; queries never redistribute the corpus)
    fpb, _, _, _ = shard.buffers()
    assert len(fpb.sharding.device_set) == 2, fpb.sharding
    assert shard.capacity % 2 == 0

    # -- end-to-end index: rankings and every statistic identical,
    #    sequential query and query_batch
    keys = np.arange(500)
    signal = rng.normal(size=500)
    tables = [("corr", keys, signal + 0.2 * rng.normal(size=500)),
              ("noise", keys, rng.normal(size=500)),
              ("disjoint", np.arange(9000, 9500), rng.normal(size=500)),
              ("half", np.arange(250, 750), rng.normal(size=500)),
              ("extra", keys, rng.normal(size=500))]
    qs = [(keys, signal),
          (np.arange(100, 600), rng.normal(size=500)),
          (np.arange(40), rng.normal(size=40))]

    def build(mesh=None):
        idx = DatasetSearchIndex(m=256, seed=1, mesh=mesh,
                                 keep_host_oracle=False)
        for nm, k, v in tables:
            idx.add_table(nm, k, v)
        return idx

    a, b = build(), build(mesh)
    assert a._corpus_axis is None and b._corpus_axis == "data"
    for k_, v_ in qs:
        ra = a.query(k_, v_, top_k=4, min_join=20)
        rb = b.query(k_, v_, top_k=4, min_join=20)
        assert ra == rb and ra, (ra, rb)       # dataclass ==: all stats
    assert (a.query_batch(qs, top_k=4, min_join=20)
            == b.query_batch(qs, top_k=4, min_join=20))

    # -- service front-end accepts the mesh and agrees with single-device
    svc = SketchSearchService(m=256, seed=1, keep_host_oracle=False,
                              mesh=mesh)
    for nm, k, v in tables:
        svc.ingest(nm, k, v)
    assert svc.search_batch(qs, top_k=4, min_join=20, micro_batch=2) == \\
        a.query_batch(qs, top_k=4, min_join=20)
    d = svc.describe()
    assert d["corpus_rows"] == 5.0 and d["corpus_capacity"] >= 5.0

    # -- every serving family: sharded == single-device, bitwise, and
    #    every sharded store buffer (fp/val/norm rows, dense tables, or
    #    sample key/value/tau rows) spreads over the mesh.  Iterates
    #    FAMILY_NAMES so a new family lands in this sweep automatically
    #    (the FC003 rule of repro.analysis checks exactly that).
    from repro.data.families import FAMILY_NAMES
    for fam in FAMILY_NAMES:
        def buildf(m=None):
            idx = DatasetSearchIndex(m=128, seed=1, mesh=m,
                                     keep_host_oracle=False, family=fam)
            for nm, k, v in tables:
                idx.add_table(nm, k, v)
            return idx
        fa, fb = buildf(), buildf(mesh)
        assert (fa.query_batch(qs, top_k=4, min_join=20)
                == fb.query_batch(qs, top_k=4, min_join=20)), fam
        for tb in fb.store.buffers():
            assert len(tb.sharding.device_set) == 2, (fam, tb.sharding)

    # -- packed stores (bit-packed wire layout, unpack-in-kernel): the
    #    sharded packed launch == single-device packed launch, bitwise,
    #    for every family, and the packed buffers spread over the mesh
    for fam in FAMILY_NAMES:
        def buildp(m=None):
            idx = DatasetSearchIndex(m=128, seed=1, mesh=m,
                                     keep_host_oracle=False, family=fam,
                                     packed=True)
            for nm, k, v in tables:
                idx.add_table(nm, k, v)
            return idx
        pa, pb = buildp(), buildp(mesh)
        assert (pa.query_batch(qs, top_k=4, min_join=20)
                == pb.query_batch(qs, top_k=4, min_join=20)), fam
        for tb in pb.store.buffers():
            assert len(tb.sharding.device_set) == 2, (fam, tb.sharding)
    print("SHARDED_OK")
""")


def test_sharded_query_bitwise_identical_to_single_device():
    out = subprocess.run(
        [sys.executable, "-c", _SCRIPT], capture_output=True, text=True,
        cwd=os.path.dirname(os.path.dirname(__file__)))
    assert "SHARDED_OK" in out.stdout, (out.stdout[-2000:], out.stderr[-4000:])
